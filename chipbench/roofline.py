"""A kernel's share of its roofline, from the profiler trace alone.

The trace's op events are named by their HLO instruction's text, which
holds the call's result and operand shapes as the kernel receives them:

    %pairwise_dist_kernel.22 = f32[11392,8]{1,0:T(8,128)} custom-call(
        f32[11392,19968]{1,0:T(8,128)} %pad.96, f32[8,19968]{...} %pad.97)

A kernel ``<name>`` is every event whose instruction is named
``<name>_kernel.<n>``.  Its operations come from ``kernel_costs/<name>.py``
(``ops(result, operands)``, shapes as ``(dtype, dims)``); its bytes are
its operands read once and its results written once, unless that file
gives ``nbytes`` too.  Padding the call receives counts as work: the kernel
reads it.  The share is the least time the chip could take for those
operations and bytes, at its published peaks, over the kernel's device
time in the trace.
"""
from __future__ import annotations

import math
import re

from chipbench import found
from chipbench.trace import instruction

SHAPE = re.compile(r"\b(pred|[sufc]\d+|bf16|f8e\w+)\[([\d,]*)\]")
ITEM_BYTES = {"pred": 1, "bf16": 2}


def item_bytes(dtype: str) -> int:
    if dtype in ITEM_BYTES:
        return ITEM_BYTES[dtype]
    if dtype.startswith("f8"):
        return 1
    return int(re.sub(r"\D", "", dtype)) // 8


def shapes(text: str) -> list:
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in SHAPE.finditer(text)]


def call_shapes(text: str) -> tuple:
    """(result shapes, operand shapes) of a custom call's instruction text:
    the result is what stands between ``=`` and the call's opening
    parenthesis, the operands what stands inside the call's parentheses."""
    _, rhs = text.split(" = ", 1)
    head, _, rest = rhs.partition("custom-call(")
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            end = i
            break
    return shapes(head), shapes(rest[:end])


def io_bytes(result: list, operands: list) -> int:
    return sum(item_bytes(t) * math.prod(d) for t, d in result + operands)


def kernel_share(obs: dict, kernel: str):
    """Percent of the roofline, or None when the trace holds no call of
    ``kernel`` (or there is no trace, or no published peak)."""
    t, pk = obs["trace"], obs.get("peaks")
    if t is None or pk is None:
        return None
    cost = found.module("kernel_costs", kernel, obs["root"])
    nbytes_of = getattr(cost, "nbytes", io_bytes)
    seconds = ops = nbytes = 0.0
    for text, (secs, count) in t["op_texts"].items():
        if not instruction(text).startswith(kernel + "_kernel"):
            continue
        result, operands = call_shapes(text)
        seconds += secs
        ops += count * cost.ops(result, operands)
        nbytes += count * nbytes_of(result, operands)
    if seconds <= 0 or ops <= 0:
        return None
    return 100.0 * max(ops / pk.flops, nbytes / pk.hbm_bw) / seconds
