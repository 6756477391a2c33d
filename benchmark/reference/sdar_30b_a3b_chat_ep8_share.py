"""``reference/sdar.py`` bound to ``configs/sdar-30b-a3b-chat.ep8-share
.json`` (8 experts a token, experts 0-15 held, blocks of 4, the noise's
epsilon and seed, the mask's row, theta, epsilon): the module that
configuration names."""
import os

from benchmark.reference import sdar

globals().update(sdar.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "sdar-30b-a3b-chat.ep8-share.json")))
