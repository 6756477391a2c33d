"""``reference/deepseek_v3.py`` bound to the test-size configuration
``tests/data_deepseek/configs/deepseek-v3-tiny.json`` (never a cell)."""
import os

from benchmark.reference import deepseek_v3

globals().update(deepseek_v3.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_deepseek", "configs", "deepseek-v3-tiny.json")))
