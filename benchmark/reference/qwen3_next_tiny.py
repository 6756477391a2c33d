"""``reference/qwen3_next.py`` bound to the test-size configuration
``tests/data_qwen3_next/configs/qwen3-next-tiny.json`` (never a cell)."""
import os

from benchmark.reference import qwen3_next

globals().update(qwen3_next.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "data_qwen3_next", "configs", "qwen3-next-tiny.json")))
