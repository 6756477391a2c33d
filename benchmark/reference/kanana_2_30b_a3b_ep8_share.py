"""``reference/deepseek_v3.py`` bound to
``configs/kanana-2-30b-a3b.ep8-share.json`` (6 experts a token, scaling
2.448, experts 0-15 held, the 128/64 split, theta, epsilon): the module
that configuration names."""
import os

from benchmark.reference import deepseek_v3

globals().update(deepseek_v3.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "kanana-2-30b-a3b.ep8-share.json")))
