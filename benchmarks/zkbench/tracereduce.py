"""From the profiler's trace to numbers: busy union, idle share, idle gaps
labelled by what the host was doing, and time by kernel name.

Two steps, so that the arithmetic can be checked on a small recorded trace
without a chip (``tests/benchmarks/data/trace_extract.json``):

1. :func:`extract_xplane` reads the newest ``.xplane.pb`` under a directory
   with ``jax.profiler.ProfileData`` (nothing but jax) into a plain
   dictionary: per device plane the events of the ``XLA Ops`` and ``XLA
   Modules`` lines, and the benchmark's own host markers.
2. :class:`DeviceTrace` reduces that dictionary.

All times inside an extract are nanoseconds on the profiler's clock. The
benchmark's markers (``mark``) carry the host's ``perf_counter_ns`` at the
moment they were written, which ties the program's host spans
(``observability/trace.py``, ``perf_counter_ns``) to the device timeline.
"""

import bisect
import glob
import os
import re
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

MARK_PREFIX = "zkbench_mark:"
#: The host tracer's level the traced runs were measured at: it keeps
#: the benchmark's marks (``TraceAnnotation``s) and leaves out the
#: runtime's verbose (level 3) host events.
HOST_TRACER_LEVEL = 2
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Stats of a device event that the reduction reads, where the trace has
#: them (the TPU's planes do; the names are the profiler's own).
KEPT_STATS = ("hlo_category", "hlo_module", "tf_op", "long_name", "hlo_op")

Interval = Tuple[float, float]


def mark(name: str) -> int:
    """Write an instant host marker into the running profiler trace and
    return ``perf_counter_ns`` taken inside it."""
    import jax

    with jax.profiler.TraceAnnotation(MARK_PREFIX + name):
        return time.perf_counter_ns()


def start_profiler(trace_dir: str) -> None:
    """The profiler as the benchmark runs it: no Python tracer (it slows
    the host it measures) and no HLO protos (the step programs are large);
    host trace events stay on, the benchmark's marks are among them."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = HOST_TRACER_LEVEL
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_profiler() -> None:
    import jax

    jax.profiler.stop_trace()


def newest_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        ),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract_xplane(
    trace_dir: str, device_prefix: str = "/device:TPU", host_fallback: bool = False
) -> dict:
    """The plain-dictionary form of the newest trace under ``trace_dir``.
    ``host_fallback`` (rehearsals on the CPU only): where no device plane
    is found, the host's XLA op events stand in for one, so that the
    reduction's code runs; its numbers are not device numbers."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    out = {"devices": {}, "marks": [], "planes": []}
    fallback = None
    for plane in data.planes:
        lines = list(plane.lines)
        out["planes"].append([plane.name, [ln.name for ln in lines]])
        if plane.name.startswith(device_prefix):
            dev = {"ops": [], "modules": []}
            for line in lines:
                if line.name == OPS_LINE:
                    target = dev["ops"]
                elif line.name == MODULES_LINE:
                    target = dev["modules"]
                else:
                    continue
                for ev in line.events:
                    stats = {}
                    for key, value in ev.stats:
                        if key in KEPT_STATS:
                            stats[key] = str(value)
                    name = short_name(ev.name, stats)
                    target.append(
                        [name, float(ev.start_ns), float(ev.duration_ns),
                         stats]
                    )
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            stand_in = []
            for line in lines:
                for ev in line.events:
                    if ev.name.startswith(MARK_PREFIX):
                        out["marks"].append(
                            [ev.name[len(MARK_PREFIX):], float(ev.start_ns)]
                        )
                    elif host_fallback:
                        stats = {k: str(v) for k, v in ev.stats if k in KEPT_STATS}
                        if "hlo_module" in stats:
                            stand_in.append(
                                [ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), stats]
                            )
            if host_fallback and stand_in:
                fallback = {"ops": stand_in, "modules": []}
    if host_fallback and not out["devices"] and fallback is not None:
        out["devices"]["/host:CPU (rehearsal stand-in)"] = fallback
    return out


_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def short_name(name: str, stats: dict) -> str:
    """The TPU's planes name an op event by the whole text of its HLO
    instruction (``%fusion.7 = (shapes) fusion(operands), kind=kOutput,
    calls=...``). Keep the instruction's own name as the event's name and
    what the reduction matches on as stats: ``kind`` (``kOutput`` is what
    XLA:TPU gives a fusion around a convolution or a matrix product),
    ``target`` (a custom call's, ``tpu_custom_call`` for a Pallas kernel)
    and the start of the text."""
    if " = " not in name:
        return name
    head, text = name.split(" = ", 1)
    for key, pattern in (("kind", _KIND), ("target", _TARGET), ("calls", _CALLS)):
        found = pattern.search(text)
        if found:
            stats[key] = found.group(1)
    stats["text"] = text[:240]
    return head.lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals: the busy set of overlapping events."""
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for start, end in intervals:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            out.append((start, end))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a merged busy set inside ``[lo, hi]``."""
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


_SUFFIX = re.compile(r"[.\d]+$")


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: a step has hundreds of numbered
    fusions, and the breakdown has ten rows."""
    return _SUFFIX.sub("", name) or name


class DeviceTrace:
    """A traced window reduced over the device planes it used.

    ``window_ns`` is ``(start, end)`` on the profiler's clock; with
    ``None`` the window is what the marks ``window_start`` and
    ``window_end`` span. ``host_offset_ns`` is the profiler's clock minus
    ``perf_counter_ns`` (from a mark), used to place host spans."""

    def __init__(
        self,
        extract: dict,
        *,
        chips: int = 1,
        window_ns: Optional[Interval] = None,
        mark_host_ns: Optional[Dict[str, int]] = None,
    ):
        marks = {}
        for name, start in extract.get("marks", []):
            marks.setdefault(name, start)
        if window_ns is None:
            if "window_start" not in marks or "window_end" not in marks:
                raise ValueError(
                    "the trace holds no window marks "
                    f"(found {sorted(marks)})"
                )
            window_ns = (marks["window_start"], marks["window_end"])
        self.lo, self.hi = window_ns
        self.window_s = (self.hi - self.lo) / 1e9
        self.host_offset_ns: Optional[float] = None
        if mark_host_ns:
            for name, host_ns in mark_host_ns.items():
                if name in marks:
                    self.host_offset_ns = marks[name] - host_ns
                    break
        names = sorted(extract["devices"])[:chips]
        if not names:
            raise ValueError(
                "the trace holds no device plane "
                f"(planes: {[p[0] for p in extract.get('planes', [])]})"
            )
        self.planes = {n: extract["devices"][n] for n in names}
        self._busy: Dict[str, List[Interval]] = {}

    # -- selections --------------------------------------------------------

    def _events(self, kind: str, plane: dict):
        for name, start, dur, stats in plane[kind]:
            end = start + dur
            if end <= self.lo or start >= self.hi:
                continue
            yield name, max(start, self.lo), min(end, self.hi), stats

    def busy_intervals(self, name: str) -> List[Interval]:
        """The merged intervals in which plane ``name`` ran an operation
        (of its ops line, else of its modules), reduced once."""
        if name not in self._busy:
            plane = self.planes[name]
            kind = "ops" if plane["ops"] else "modules"
            self._busy[name] = union(
                (s, e) for _, s, e, _ in self._events(kind, plane)
            )
        return self._busy[name]

    # -- the numbers -------------------------------------------------------

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        total = 0.0
        for name in self.planes:
            total += sum(e - s for s, e in self.busy_intervals(name))
        return total / len(self.planes) / 1e9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(
        self,
        match: Callable[[str, dict], bool],
        kind: str = "ops",
        within_module: Optional[str] = None,
    ) -> Tuple[float, int]:
        """Device seconds (averaged over chips) and event count of the
        events ``match(name, stats)`` accepts. Durations are summed, not
        merged: one kernel's calls do not overlap on one chip. With
        ``within_module`` only ops that start inside an ``XLA Modules``
        event whose name holds that string count: the op belongs to that
        program."""
        seconds, count = 0.0, 0
        for plane in self.planes.values():
            inside = None
            if within_module is not None:
                inside = sorted(
                    (s, e) for n, s, e, _ in self._events("modules", plane)
                    if within_module in n
                )
                starts = [s for s, _ in inside]
            for name, start, end, stats in self._events(kind, plane):
                if not match(name, stats):
                    continue
                if inside is not None:
                    i = bisect.bisect_right(starts, start) - 1
                    if i < 0 or start >= inside[i][1]:
                        continue
                seconds += end - start
                count += 1
        n = len(self.planes)
        return seconds / n / 1e9, count // n

    def module_seconds(self, contains: str) -> Tuple[float, int]:
        return self.op_seconds(lambda n, s: contains in n, kind="modules")

    def top_ops(self, k: int = 10) -> List[List[object]]:
        totals: Dict[str, float] = {}
        for plane in self.planes.values():
            for name, start, end, stats in self._events("ops", plane):
                key = op_family(name)
                cat = stats.get("hlo_category") or stats.get("kind")
                if cat and cat != key:
                    key = f"{cat}/{key}"
                totals[key] = totals.get(key, 0.0) + (end - start)
        n = len(self.planes) * 1e9
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n] for name, ns in ranked]

    def idle_gaps(
        self,
        host_spans: Sequence[Tuple[str, int, int]] = (),
        k: int = 10,
        min_gap_ns: float = 20_000.0,
    ) -> List[List[object]]:
        """Idle seconds of the first chip by what the host was doing: each
        gap goes to the host span (``name, start perf_counter_ns, duration
        ns``) that covers most of it, else to ``unattributed``."""
        idle = gaps(self.busy_intervals(next(iter(self.planes))), self.lo, self.hi)
        spans = []
        if self.host_offset_ns is not None:
            for name, start, dur in host_spans:
                s = start + self.host_offset_ns
                spans.append((s, s + dur, name))
            spans.sort()
        totals: Dict[str, float] = {}
        starts = [s for s, _, _ in spans]
        for g0, g1 in idle:
            if g1 - g0 < min_gap_ns:
                label = "short_gaps"
            else:
                cover: Dict[str, float] = {}
                # spans are short and sorted: look a little to the left
                i = max(0, bisect.bisect_right(starts, g0) - 64)
                while i < len(spans) and spans[i][0] < g1:
                    s, e, name = spans[i]
                    overlap = min(e, g1) - max(s, g0)
                    if overlap > 0:
                        cover[name] = cover.get(name, 0.0) + overlap
                    i += 1
                label = "unattributed"
                if cover:
                    best = max(cover.items(), key=lambda kv: kv[1])
                    if best[1] >= 0.5 * (g1 - g0):
                        label = best[0]
            totals[label] = totals.get(label, 0.0) + (g1 - g0)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in ranked]
