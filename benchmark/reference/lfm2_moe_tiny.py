"""``reference/lfm2_moe.py`` bound to the test-size configuration
``tests/data_lfm2_moe/configs/lfm2-moe-tiny.json`` (never a cell)."""
import os

from benchmark.reference import lfm2_moe

globals().update(lfm2_moe.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_lfm2_moe", "configs", "lfm2-moe-tiny.json")))
