"""Fused plan kernel (``kernels/planfuse.py``): its device milliseconds per
request completed in the traced window.

The Pallas call runs as the ``tpu_custom_call`` instruction that XLA names
after its jitted wrapper, ``plan_fuse.<n>``.  The trace's ``XLA Ops``
events carry the instruction's HLO text,
``%plan_fuse.1 = (u32[3328,128], s32[3328,128]) custom-call(...)``; an op
counts when the instruction's name, the text before `` = `` without its
``%``, is ``KERNEL`` or ``KERNEL.<n>``.
"""

import re

KERNEL = "plan_fuse"
_NAME = re.compile(rf"{KERNEL}(\.\d+)?")


def instruction(op: str) -> str:
    """The HLO instruction name of a trace op name."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def read(run):
    t = run["trace"]
    if t is None or not t["requests"]:
        return None
    kernel_s = sum(s for name, s in t["op_seconds"].items()
                   if _NAME.fullmatch(instruction(name)))
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / t["requests"]
