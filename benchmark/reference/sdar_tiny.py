"""``reference/sdar.py`` bound to the test-size configuration
``tests/data_sdar/configs/sdar-tiny.json`` (never a cell)."""
import os

from benchmark.reference import sdar

globals().update(sdar.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_sdar", "configs", "sdar-tiny.json")))
