"""The arithmetic that decides ``correct``: gaps between what the timed
path produced and what the plain reference gives, each held to a limit of
its own from the configuration's file (``limits``)."""

import math
import statistics
from typing import Dict, Iterable, List, Optional, Tuple


def relative_gap(program: float, reference: float) -> float:
    if not (math.isfinite(program) and math.isfinite(reference)):
        return math.inf
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(
    program: Dict[str, float],
    reference: Dict[str, float],
    leaves: Optional[Iterable[str]] = None,
) -> Tuple[float, str]:
    """The largest, over the leaves, of ``|‖p‖ - ‖r‖|`` (the gap between
    the two norms, not the norm of a difference) over the larger of the
    reference's norm of that leaf and of its median leaf: some leaves are
    all but zero, and their rounding must not decide."""
    names = list(reference if leaves is None else leaves)
    if not names:
        return math.inf, ""
    floor = statistics.median(reference[n] for n in reference)
    worst, where = 0.0, names[0]
    for name in names:
        p, r = program.get(name, math.nan), reference[name]
        gap = (
            abs(p - r) / max(r, floor, 1e-30)
            if math.isfinite(p) and math.isfinite(r) else math.inf
        )
        if gap > worst:
            worst, where = gap, name
    return worst, where


def moved_leaves(reference_grad: Dict[str, float], ratio: float = 1e-3) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    ``ratio`` of the median leaf's. The others move under Adam by round-off
    alone and are left out of the comparison of the change."""
    floor = ratio * statistics.median(reference_grad.values())
    return [n for n, g in reference_grad.items() if g >= floor]


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """``(correct, compared)``: every number with a limit has to lie at or
    under it; ``compared`` lists each number beside its limit (``None``:
    reported, not compared)."""
    compared = {}
    correct = True
    for name, value in values.items():
        limit = limits.get(name)
        ok = True
        if limit is not None:
            ok = math.isfinite(value) and value <= limit
        compared[name] = {
            "value": value if math.isfinite(value) else None,
            "limit": limit,
            "ok": ok,
        }
        correct = correct and ok
    if not any(c["limit"] is not None for c in compared.values()):
        correct = False  # nothing was compared: that proves nothing
    return correct, compared
