"""``reference/smallthinker.py`` bound to the test-size configuration
``tests/data_smallthinker/configs/smallthinker-tiny.json`` (never a
cell)."""
import os

from benchmark.reference import smallthinker

globals().update(smallthinker.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_smallthinker", "configs", "smallthinker-tiny.json")))
