"""Hardware peak anchors: the ONE table both bench.py and the live
MFU gauges divide by.

MFU is only meaningful relative to a stated roofline, and the roofline
itself is the easiest number to get wrong: above-physics "measured"
peaks, generation-specific int8 factors, datasheet clamps. The
device-side performance ledger (``observability.ledger``) needs the
SAME anchors for its ``zk_serve_mfu`` gauge as
``bench.py`` — two copies would inevitably diverge — so the tables, the
datasheet clamp, and the agreement-gated attempt aggregation live HERE;
``bench.py`` re-exports them unchanged.

Two anchor-resolution paths, deliberately different:

- **bench.py** (offline, owns the device for minutes): measures the
  peak on-chip (matmul chains, marginal timing) and only falls back to
  the tables when measurement fails — ``resolve_peak_flops``.
- **live gauges** (a training/serving process): must never burn device
  time on calibration matmuls, so :func:`reference_peak_flops` resolves
  env override > the recorded v5e measurement on a v5e > the
  datasheet-derived achievable peak (0.93x — the v5e's measured
  fraction of its datasheet) on other table generations.

A device whose ``device_kind`` matches no table row has NO anchor: the
resolvers return ``(None, "unknown")`` and the gauges publish their -1
"unknown" — a CPU backend or a future chip is never rated against the
v5e's peaks (docs/DESIGN.md §14).
"""

import logging
import math
import os
from typing import Optional, Tuple

logger = logging.getLogger(__name__)


def _env_peak(env, name: str) -> Optional[float]:
    """A positive-float env override, or None — a malformed value is
    warn-and-ignored, never raised: these resolve inside gauge updates
    on the training/serving hot paths, whose totality contract
    (docstrings below) a typo'd export must not be able to break."""
    raw = env.get(name)
    if not raw:
        return None
    try:
        value = float(raw)
    except (TypeError, ValueError):
        logger.warning(
            "%s=%r is not a number — ignoring the override", name, raw
        )
        return None
    if not math.isfinite(value) or value <= 0:
        logger.warning(
            "%s=%r is not a finite positive peak — ignoring the override",
            name,
            raw,
        )
        return None
    return value

__all__ = [
    "ACHIEVABLE_FRACTION",
    "BF16_PEAK_FALLBACK",
    "DATASHEET_HEADROOM",
    "INT8_FACTOR_UPPER_BOUND",
    "INT8_PEAK_FALLBACK",
    "TPU_DATASHEET_BF16_TFLOPS",
    "TPU_DATASHEET_HBM_GBPS",
    "TPU_INT8_FACTOR",
    "V5E_KEYS",
    "aggregate_peak_attempts",
    "check_peak_against_datasheet",
    "datasheet_bf16_peak",
    "datasheet_hbm_bandwidth",
    "datasheet_match",
    "reference_hbm_bandwidth",
    "reference_int8_peak_flops",
    "reference_peak_flops",
]

# The v5e's recorded bf16 peak, used when on-chip measurement is
# unavailable: measured on a v5e chip (BASELINE.md round-2
# re-measurement: on-device fori_loop, full-sum dependency, 4096^3 bf16
# matmul -> 184 TFLOP/s, 93% of the v5e datasheet 197). Round 1's 79
# TFLOP/s was a dispatch-bound under-measurement.
BF16_PEAK_FALLBACK = 184e12

# Public datasheet bf16 peaks (TFLOP/s per chip) keyed by substrings of
# jax's ``device_kind`` string. A MEASURED peak above ~1.05x the matching
# datasheet number is physically impossible and therefore a measurement
# failure (a cache answering a repeated request is the proven mechanism:
# rounds 2-4 recorded 268 / 270 / 237.9 TF/s on a 197 TF/s v5e), never
# hardware. Longest-substring match so "v5 lite" wins over a bare "v5".
TPU_DATASHEET_BF16_TFLOPS = {
    "v2": 46.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5litepod": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}

# Headroom above the datasheet number before a measurement is rejected:
# covers clock/rounding slop in the datasheet itself, not caching (which
# produces 1.2-1.4x errors, far outside this band).
DATASHEET_HEADROOM = 1.05

# Recorded v5e int8 MXU peak: measured on this machine with PRE-CAST
# int8 operands (the round-2 177 TOP/s carried an in-loop bf16 cast that
# halved it) — 4096^3 int8 dot_general chain, elementwise int32->int8
# squeeze between iterates, marginal timing: 369-373 TOP/s, ~94% of the
# 394 TOP/s datasheet (2x the bf16 197).
INT8_PEAK_FALLBACK = 369e12

# Per-generation int8-over-bf16 MXU rate: v5e/v5p/v6 double int8;
# v2/v3/v4 run int8 at the bf16 rate (no native int8 MXU doubling).
# Used both as the measurement ceiling (x DATASHEET_HEADROOM) and to
# scale the datasheet fallback — assuming 2x on a v4 would record a
# ~2x-understated MFU under an authoritative-sounding tag. Unknown
# generations use the 2x upper bound for the CLAMP only (permissive),
# never for a fallback value.
TPU_INT8_FACTOR = {
    "v2": 1.0,
    "v3": 1.0,
    "v4": 1.0,
    "v5 lite": 2.0,
    "v5litepod": 2.0,
    "v5e": 2.0,
    "v5p": 2.0,
    "v6 lite": 2.0,
    "v6e": 2.0,
}
INT8_FACTOR_UPPER_BOUND = 2.0

# Public datasheet HBM bandwidths (GB/s per chip), same substring-keyed
# table discipline as the bf16 peaks: the roofline the decode MBU gauge
# (memory-bound programs — docs/DESIGN.md §17) divides by. Deliberately
# the DATASHEET number with no "achievable fraction" prior: unlike the
# flops anchor, no on-chip bandwidth measurement has been recorded in
# this repo, and inventing a transfer fraction would be exactly the
# fabricated-anchor pathology rounds 2-5 document. A sustained-copy
# measurement can later join as a recorded fallback the way
# BF16_PEAK_FALLBACK did.
TPU_DATASHEET_HBM_GBPS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5litepod": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}

#: The v5e table keys: the generation whose RECORDED on-chip measurement
#: (BF16_PEAK_FALLBACK) exists, distinguished by key rather than by
#: comparing datasheet numbers (float identity would silently drift if a
#: table entry were corrected or two generations shared a number).
V5E_KEYS = frozenset({"v5 lite", "v5litepod", "v5e"})

#: The fraction of its datasheet peak a chip achieves on the bench's
#: measurement protocol — the v5e's measured 184/197, used as the
#: transfer prior for generations without a recorded measurement.
ACHIEVABLE_FRACTION = 0.93


def _match_datasheet_table(device_kind, table) -> Optional[Tuple[str, float]]:
    """Longest-substring table match shared by every datasheet lookup
    (flops AND bandwidth — one matching rule, so a future device_kind
    normalization cannot apply to one table and silently miss the
    other). Returns ``(table_key, raw_table_value)`` or None."""
    kind = (device_kind or "").lower()
    best = None
    for key, value in table.items():
        if key in kind and (best is None or len(key) > len(best[0])):
            best = (key, value)
    return best


def datasheet_match(device_kind) -> Optional[Tuple[str, float]]:
    """``(table_key, peak_flops)`` for the longest table key contained in
    ``device_kind``, or None when the generation is unrecognized."""
    best = _match_datasheet_table(device_kind, TPU_DATASHEET_BF16_TFLOPS)
    return None if best is None else (best[0], best[1] * 1e12)


def datasheet_bf16_peak(device_kind) -> Optional[float]:
    """Datasheet bf16 peak (FLOP/s) for a jax ``device_kind`` string, or
    None when the generation is unrecognized (future hardware must not be
    clamped to a stale table)."""
    match = datasheet_match(device_kind)
    return None if match is None else match[1]


def datasheet_hbm_bandwidth(device_kind) -> Optional[float]:
    """Datasheet HBM bandwidth (bytes/s) for a jax ``device_kind``
    string, or None when the generation is unrecognized — the same
    longest-substring matcher as :func:`datasheet_match`."""
    best = _match_datasheet_table(device_kind, TPU_DATASHEET_HBM_GBPS)
    return None if best is None else best[1] * 1e9


def _device_kind(device_kind: Optional[str]) -> Optional[str]:
    """``device_kind`` itself, or the first jax device's when None
    (None again when jax has no backend to ask)."""
    if device_kind is not None:
        return device_kind
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return None


def reference_hbm_bandwidth(
    device_kind: Optional[str] = None, env=None
) -> Tuple[Optional[float], str]:
    """The HBM-bandwidth anchor for the decode engine's ``decode_mbu``,
    resolved WITHOUT touching the device — the bandwidth twin of
    :func:`reference_peak_flops`: ``ZK_BENCH_HBM_BANDWIDTH`` override
    (bytes/s) > the generation's datasheet bandwidth. Returns
    ``(bytes_per_sec, source_tag)``, or ``(None, "unknown")`` for a
    device in no table row; resolution stays total even without
    jax/backends, so a gauge update can never raise (``ledger.mbu``
    maps a missing anchor to the gauge's -1)."""
    env = os.environ if env is None else env
    override = _env_peak(env, "ZK_BENCH_HBM_BANDWIDTH")
    if override is not None:
        return override, "env"
    sheet = datasheet_hbm_bandwidth(_device_kind(device_kind))
    if sheet is not None:
        return sheet, "datasheet"
    return None, "unknown"


def check_peak_against_datasheet(peak, device_kind) -> None:
    """Raise when a measured peak exceeds the datasheet band for this
    device generation — above-physics readings are measurement failures
    (the cached-request pathology), and recording one as
    "measured" corrupts the MFU time series (BENCH_r04: 237.9 TF/s on a
    197 TF/s v5e read as an MFU collapse). Unknown generations pass: a
    stale table must not reject a future chip."""
    sheet = datasheet_bf16_peak(device_kind)
    if sheet is not None and peak > DATASHEET_HEADROOM * sheet:
        raise ValueError(
            f"measured peak {peak / 1e12:.1f} TF/s exceeds the "
            f"{device_kind!r} datasheet {sheet / 1e12:.0f} TF/s by more "
            f"than {DATASHEET_HEADROOM:.2f}x — measurement failure "
            "(cached request?), not hardware"
        )


def aggregate_peak_attempts(attempts, rel_tol=0.05):
    """Agreement-gated aggregation of independent peak attempts: the
    estimate is the median of the largest cluster of attempts that agree
    within ``rel_tol`` (max/min <= 1+rel_tol over the cluster), requiring
    at least two members. Raises when no two attempts agree.

    This replaces max-over-attempts, whose design assumption — "noise can
    only make the chip look slower" — was empirically falsified three
    times (268, 270, 237.9 TF/s fast-side errors on a 197 TF/s part):
    max is precisely the aggregator that amplifies any residual fast-side
    failure mode. When two DISJOINT clusters tie for largest (a bimodal
    session — e.g. two jitter-degraded and two genuine attempts), neither
    is trustworthy and the function refuses rather than guess: anchoring
    on the slow cluster would INFLATE MFU (the round-2 114 TF/s lesson),
    anchoring on the fast one risks the cache pathology.
    """
    vals = sorted(a for a in attempts if a > 0)
    if len(vals) < 2:
        raise ValueError(
            f"need >=2 positive attempts to agree, got {len(vals)} "
            f"from {list(attempts)}"
        )
    best = None
    ambiguous = False  # a DISJOINT equal-size cluster exists
    for i in range(len(vals)):
        j = i
        while j + 1 < len(vals) and vals[j + 1] <= vals[i] * (1 + rel_tol):
            j += 1
        size = j - i + 1
        if size >= 2:
            if best is None or size > best[0]:
                best, ambiguous = (size, i, j), False
            elif size == best[0] and i > best[2]:
                # Only windows sharing NO attempts with the best are a
                # second mode; an equal-size window that overlaps it
                # (e.g. a mild fast outlier within tol of the cluster's
                # max but not its min) is the same cluster shifted and
                # must not veto the measurement.
                ambiguous = True
    if best is None:
        raise ValueError(
            "no two peak attempts agree within "
            f"{rel_tol:.0%}: {[round(v / 1e12, 1) for v in vals]} TF/s — "
            "session too noisy to anchor MFU"
        )
    if ambiguous:
        raise ValueError(
            "ambiguous peak attempts (two disjoint equal-size clusters): "
            f"{[round(v / 1e12, 1) for v in vals]} TF/s — bimodal "
            "session, refusing to pick a cluster"
        )
    _, i, j = best
    cluster = vals[i : j + 1]
    mid = len(cluster) // 2
    if len(cluster) % 2:
        return cluster[mid]
    return 0.5 * (cluster[mid - 1] + cluster[mid])


def reference_peak_flops(
    device_kind: Optional[str] = None, env=None
) -> Tuple[Optional[float], str]:
    """The bf16 peak anchor for LIVE MFU gauges, resolved WITHOUT
    touching the device: ``ZK_BENCH_PEAK_FLOPS`` override > the
    recorded v5e measurement (on a v5e) > the generation's datasheet
    peak scaled by the achievable fraction. Returns ``(peak_flops,
    source_tag)``, or ``(None, "unknown")`` for a device in no table
    row — ``ledger.mfu`` maps a missing anchor to the gauge's -1.

    A live process must never run calibration matmuls (they would steal
    step/dispatch time from the workload being measured), so this is
    deliberately table-driven where ``bench.resolve_peak_flops``
    measures. The two agree by construction: on a v5e the recorded
    measurement IS 0.93x of datasheet; elsewhere both sides apply the
    same 0.93x prior (bench's fallback path) or bench's fresh
    measurement lands within a few percent of it — inside the 10%
    live-vs-offline agreement contract (docs/DESIGN.md §14).

    ``device_kind`` defaults to the first jax device's kind; resolution
    stays total even when jax/backends are unavailable, so a gauge
    update can never raise.
    """
    env = os.environ if env is None else env
    override = _env_peak(env, "ZK_BENCH_PEAK_FLOPS")
    if override is not None:
        return override, "env"
    match = datasheet_match(_device_kind(device_kind))
    if match is None:
        return None, "unknown"
    if match[0] in V5E_KEYS:
        # The recorded on-chip measurement exists for this part.
        return BF16_PEAK_FALLBACK, "v5e_measured"
    return ACHIEVABLE_FRACTION * match[1], "datasheet_scaled"


def reference_int8_peak_flops(
    device_kind: Optional[str] = None, env=None
) -> Tuple[Optional[float], str]:
    """Int8-MXU anchor for live gauges, same resolution discipline as
    :func:`reference_peak_flops` (``ZK_BENCH_INT8_PEAK_FLOPS``
    overrides); the datasheet path scales by the generation's
    int8-over-bf16 factor (1x on v2-v4)."""
    env = os.environ if env is None else env
    override = _env_peak(env, "ZK_BENCH_INT8_PEAK_FLOPS")
    if override is not None:
        return override, "env"
    match = datasheet_match(_device_kind(device_kind))
    if match is None:
        return None, "unknown"
    if match[0] in V5E_KEYS:
        return INT8_PEAK_FALLBACK, "v5e_measured"
    factor = TPU_INT8_FACTOR.get(match[0], 1.0)
    return ACHIEVABLE_FRACTION * factor * match[1], "datasheet_scaled"
