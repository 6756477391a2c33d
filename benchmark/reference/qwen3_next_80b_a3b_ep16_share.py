"""``reference/qwen3_next.py`` bound to
``configs/qwen3-next-80b-a3b.ep16-share.json`` (10 experts a token,
experts 0-31 held, a full layer every fourth, the rotary's 64 columns,
theta, epsilon): the module that configuration names."""
import os

from benchmark.reference import qwen3_next

globals().update(qwen3_next.bound(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "qwen3-next-80b-a3b.ep16-share.json")))
