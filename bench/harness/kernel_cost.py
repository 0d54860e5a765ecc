"""Bytes and operations of the program's kernels, from the shapes that
the device trace records for each launch.

A device operation's text in the trace is its HLO instruction, e.g.
``%before_pallas.1 = s32[1,65536]{...} custom-call(s32[5,1]{...} %q,
s32[5,65536]{...} %rows), custom_call_target="tpu_custom_call", ...``:
the result's shape, then each operand's.
"""

from __future__ import annotations

import re
from typing import Optional

_SIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_SIZE) + r")\[([0-9,]*)\]")

#: instruction names of the ``mv_visibility`` Pallas kernels
MV_VISIBILITY = ("%before_pallas", "%visibility_pallas")


def hlo_bytes(text: str) -> int:
    """Bytes of the result and every operand named in an instruction's
    text (the least an operation must move)."""
    head = text.split(", custom_call_target", 1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _SIZE[dtype]
    return total


def mv_visibility_bytes(text: str, stats=None) -> Optional[int]:
    """Bytes one ``mv_visibility`` launch moves: its ``(C, N)`` stamp
    rows and ``(C, 1)`` query in, its ``(1, N)`` mask out.  None for any
    other operation."""
    name = text.split(" = ", 1)[0]
    if not name.startswith(MV_VISIBILITY) or "tpu_custom_call" not in text:
        return None
    return hlo_bytes(text)
