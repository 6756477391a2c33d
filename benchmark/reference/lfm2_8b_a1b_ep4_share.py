"""``reference/lfm2_moe.py`` bound to ``configs/lfm2-8b-a1b.ep4-share.json``
(4 experts a token, experts 0-7 held, the five layers' kinds, one dense
layer, theta, epsilon): the module that configuration names."""
import os

from benchmark.reference import lfm2_moe

globals().update(lfm2_moe.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "lfm2-8b-a1b.ep4-share.json")))
