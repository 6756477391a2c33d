"""``reference/keye_vl2.py`` bound to
``configs/keye-vl-2.0-30b-a3b.ep8-share.json`` (8 experts a token,
experts 0-15 held, ``topk`` 2048, theta, epsilon): the module that
configuration names."""
import os

from benchmark.reference import keye_vl2

globals().update(keye_vl2.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "keye-vl-2.0-30b-a3b.ep8-share.json")))
