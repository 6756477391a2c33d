"""``reference/smallthinker.py`` bound to
``configs/smallthinker-21b-a3b.ep8-share.json`` (6 experts a token,
experts 0-7 held, the window of 4096 and the two layouts' first four
entries, theta, epsilon): the module that configuration names."""
import os

from benchmark.reference import smallthinker

globals().update(smallthinker.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "smallthinker-21b-a3b.ep8-share.json")))
