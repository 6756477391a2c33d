"""``reference/keye_vl2.py`` bound to the test-size configuration
``tests/data_keye/configs/keye-vl2-tiny.json`` (never a cell)."""
import os

from benchmark.reference import keye_vl2

globals().update(keye_vl2.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_keye", "configs", "keye-vl2-tiny.json")))
