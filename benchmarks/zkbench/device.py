"""What the run needs from the machine: the chips, the compile cache, the
clock that set-up is counted on, and the peak of device memory."""

import os
import sys
import time
from typing import Dict, Optional

from zkbench.cells import ROOT

#: ``time.perf_counter()`` when this module was first imported; the
#: process's own age at that moment is added to it (``process_age_s``).
_IMPORT_T = time.perf_counter()


def process_age_s() -> float:
    """Seconds from the start of this process (the kernel's record) to
    now. Set-up is counted from process start, interpreter start-up
    included; where ``/proc`` does not say, from the import of this file."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])  # field 22 of the whole line
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return age
    except (OSError, ValueError, IndexError):
        pass
    return time.perf_counter() - _IMPORT_T


class SetupClock:
    """``setup_s``: process start to the first measured step or request."""

    def __init__(self):
        self._t0 = time.perf_counter() - process_age_s()

    def since_start(self, at: Optional[float] = None) -> float:
        return (time.perf_counter() if at is None else at) - self._t0


def enable_compile_cache() -> str:
    """jax's persistent compilation cache at a fixed place inside the
    checkout (the path is part of the cache's key), or where
    ``JAX_COMPILATION_CACHE_DIR`` puts it. Small programs are cached too:
    with jax's default floor of one second the many small programs of a
    run compile again in every process."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = placed or os.path.join(ROOT, ".jax_cache")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileClock:
    """Seconds the backend spent compiling (or the persistent cache spent
    answering) and how many compiles the cache answered, summed over the
    process. Copied from ``chip_smoke.py`` (PR 21): it splits ``setup_s``
    and counts compiles inside the window (there must be none)."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compile_s": self.seconds,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
        }


def require_chips(chips: int, rehearse: bool) -> Dict[str, object]:
    """The device as jax reports it. Without ``--rehearse`` anything but a
    TPU with at least ``chips`` chips ends the run: exit code 3, no result
    line. A rehearsal runs only where ``JAX_PLATFORMS=cpu`` was set
    explicitly, so that it can never be taken for a chip run."""
    if rehearse and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        print(
            "benchmark: --rehearse needs JAX_PLATFORMS=cpu set explicitly",
            file=sys.stderr,
        )
        raise SystemExit(3)
    try:
        import jax

        devices = jax.devices()
    except Exception as e:  # jax fails at start-up where it finds no chip
        print(f"benchmark: jax found no device: {e}", file=sys.stderr)
        raise SystemExit(3)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        return device
    if device["platform"] != "tpu" or len(devices) < chips:
        print(
            f"benchmark: the cell needs {chips} TPU chip(s); jax found "
            f"{device['count']} x {device['platform']} "
            f"({device['kind']!r}). No result.",
            file=sys.stderr,
        )
        raise SystemExit(3)
    device["count"] = chips
    return device


def runtime_peak_bytes(chips: int) -> int:
    """The runtime's own ``peak_bytes_in_use`` on the fullest of the chips
    used. A device that keeps no such counter is an error, not a 0."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(
                f"{d} reports no peak_bytes_in_use (memory_stats: "
                f"{sorted(stats)}); the peak of device memory cannot be read"
            )
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def executable_of(step):
    """The ``jax.stages.Compiled`` behind a compiled step: the step itself,
    or what a wrapper of the program keeps under ``_compiled``. Anything
    else is an error: the benchmark reads jax's own analysis of the very
    executable that ran the window, or nothing."""
    import jax

    for candidate in (step, getattr(step, "_compiled", None)):
        if isinstance(candidate, jax.stages.Compiled):
            return candidate
    raise RuntimeError(
        f"no jax.stages.Compiled behind {type(step).__name__}: the "
        "temporaries of the timed step cannot be read"
    )


def temp_bytes(executable) -> int:
    """The temporaries one call of ``executable`` holds on one chip, as
    jax's ``memory_analysis()`` gives them."""
    analysis = executable.memory_analysis()
    if analysis is None or not hasattr(analysis, "temp_size_in_bytes"):
        raise RuntimeError("jax gave no memory_analysis() for the timed step")
    return int(analysis.temp_size_in_bytes)


def memory_peak_bytes(chips: int, executable=None, rehearse: bool = False):
    """``(peak, note)``; a rehearsal on the CPU, which keeps no counter and
    prints no device, gets ``(None, note)``. The peak is the runtime's
    ``peak_bytes_in_use`` on the fullest chip, plus, where the entry holds
    the timed step's executable, that step's temporaries: the TPU runtime's
    counter leaves a running program's temporaries out. Every training run shows it (the
    counter stays under the temporaries of a step that ran hundreds of
    times) and ``benchmarks/probes/memory_counter.py`` shows it directly
    (PERF.md section 2). Where the entry holds no executable (serving: the
    engine compiles its own programs) the peak is the counter alone and so
    leaves the largest program's temporaries out; the note says so."""
    if rehearse:
        temps = "none held" if executable is None else temp_bytes(executable)
        return None, (
            f"device memory: not read in a rehearsal (step temporaries: {temps})"
        )
    counter = runtime_peak_bytes(chips)
    if executable is None:
        return counter, (
            f"device memory: runtime peak_bytes_in_use {counter}; the "
            "temporaries of the programs that ran are not in it and not added"
        )
    temps = temp_bytes(executable)
    return counter + temps, (
        f"device memory: runtime peak_bytes_in_use {counter} + temporaries "
        f"of the timed step {temps} (jax memory_analysis of the executable "
        f"that ran the window) = {counter + temps}"
    )
