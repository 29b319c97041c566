"""Array shapes of a TPU op event, read from its HLO text.

``%bloom_probe.3 = s32[2881536]{0:T(1024)} custom-call(f32[128,128]{1,0}
%a, s32[2881536]{0} %b), ...`` gives the result ``("s32", (2881536,))``
and the operands ``[("f32", (128, 128)), ("s32", (2881536,))]``.
"""
from __future__ import annotations

import re
from typing import List, Tuple

Shape = Tuple[str, Tuple[int, ...]]
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_CALL = re.compile(r"\s[a-z][a-z0-9_-]*\(")     # " custom-call(", not "T(8"


def _arrays(text: str) -> List[Shape]:
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY.findall(text)]


def result_and_operands(text: str) -> Tuple[Shape, List[Shape]]:
    rest = text.partition(" = ")[2]
    call = _CALL.search(rest)
    result = _arrays(rest[:call.start()])[0]
    args = rest[call.end():]
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    return result, _arrays(args[:end])
