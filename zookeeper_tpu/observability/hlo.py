"""Reading a compiled program's optimised HLO text
(``jax.stages.Compiled.as_text()``) for structure a test or an on-chip
check wants to hold: no timing, nothing device-specific beyond what the
compiler wrote."""

import math
import re
from typing import Iterable

__all__ = ["count_copies_of_size", "count_row_scatters_of_size"]

#: ``%copy.4 = bf16[3072,16,25,64]{3,2,1,0:T(8,128)(2,1)} copy(%buf.1)``,
#: with or without ``ROOT``; an asynchronous ``copy-start`` returns a
#: tuple whose first shape is the copy's. The second group is that
#: shape's layout, which names a memory space (``S(1)``) where the
#: result does not live in HBM.
_COPY = re.compile(
    r"= \(?\w+\[([\d,]*)\]([^ ]*) (?:[^=]*\) )?(?:copy|copy-start|transpose)\("
)


def count_copies_of_size(hlo_text: str, element_counts: Iterable[int]) -> int:
    """How many ``copy`` / ``copy-start`` / ``transpose`` instructions
    of ``hlo_text`` (fused computations included) produce an array with
    one of ``element_counts`` elements in the device's main memory. The
    decode engine asks it of every program with its page pool's leaf
    sizes and with its token table's: such an instruction is a
    re-layout of a whole leaf, which no program should hold
    (docs/DESIGN.md §15, §20). A copy into another memory space (the
    result's layout says ``S(n)``) is the compiler's prefetch of a small
    operand into fast memory, in the layout it has, and is not counted."""
    sizes = {int(n) for n in element_counts}
    count = 0
    for line in hlo_text.splitlines():
        m = _COPY.search(line)
        if m is None or "S(" in m.group(2):
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if math.prod(dims) in sizes:
            count += 1
    return count


#: ``%scatter.10 = bf16[49152,1664]{1,0:T(8,128)(2,1)} scatter(%param_0,
#: ...), update_window_dims={1}, inserted_window_dims={0}, ...``: the
#: result's dimensions and the dimensions of an update that are its
#: window.
_SCATTER = re.compile(
    r"= \(?\w+\[([\d,]*)\][^ ]* (?:[^=]*\) )?scatter\(.*"
    r"update_window_dims=\{([\d,]*)\}"
)


def count_row_scatters_of_size(
    hlo_text: str, element_counts: Iterable[int]
) -> int:
    """How many ``scatter`` instructions of ``hlo_text`` update an array
    with one of ``element_counts`` elements through a window of one
    dimension: one stored row an index. The decode engine's cold prefill
    holds none over a leaf of its page pool (it writes a page an index,
    a window of two or three dimensions: docs/DESIGN.md §20); its decode
    step and its extend program hold one a K/V leaf a layer."""
    sizes = {int(n) for n in element_counts}
    count = 0
    for line in hlo_text.splitlines():
        m = _SCATTER.search(line)
        if m is None:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        window = [d for d in m.group(2).split(",") if d]
        if math.prod(dims) in sizes and len(window) == 1:
            count += 1
    return count
