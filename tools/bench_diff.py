"""Bench regression gate: diff two BENCH_r*.json artifacts.

The BENCH_r*.json trajectory is the repo's perf memory, but nothing
machine-checked it: a regression only surfaced if a human re-read two
JSON lines side by side. This tool is the gate — ``bench.py --compare
BENCH_rNN.json`` (and the standalone CLI below) diffs the current run
against a previous artifact with per-metric tolerances and exits
nonzero on regression, so a perf loss fails the run that introduced it
instead of being archaeology five rounds later.

Direction-aware comparison: metric names are classified HIGHER-better
(throughputs, MFU, speedups) or LOWER-better (latencies, step/stall
times) by suffix pattern; identity/config/provenance keys (model,
buckets, shas, sources) are compared for drift but never gate. A
metric present on only one side is reported as added/removed — also
non-gating, since bench legs are env-gated and runs legitimately
differ in coverage. Schema-version mismatch downgrades the whole diff
to report-only: renamed keys would read as removed+regressed.

Tolerances: ``DEFAULT_REL_TOL`` (10%) unless the metric has an entry
in ``TOLERANCES`` — deliberately loose for legs measured through
shared-host jitter (recovery walltimes, percentile tails) and absent
for the informational ``obs_*`` fractions whose gate lives in CI.

CLI:

    python tools/bench_diff.py CURRENT.json PREVIOUS.json \\
        [--tol 0.10] [--json OUT.json] [--allow-regression]

Accepts either a raw bench line object or the committed driver wrapper
(``{"parsed": {...}, ...}``); MULTICHIP_r*.json dryrun records carry no
metric line and are out of scope. Exit codes: 0 ok, 3 regression
(unless ``--allow-regression``), 2 unusable input.
"""

import argparse
import json
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = ["BenchDiff", "classify_metric", "compare", "load_bench_json"]

DEFAULT_REL_TOL = 0.10

#: Per-metric relative tolerance overrides (fraction of the PREVIOUS
#: value the metric may move in the BAD direction before gating).
TOLERANCES = {
    # Percentile tails and thread-scheduling-bound legs are noisy on
    # shared hosts; the gate is for real regressions, not weather.
    "serve_p99_ms": 0.30,
    "shed_p99_ms": 0.50,
    "shed_p50_ms": 0.50,
    "recovery_restore_ms": 0.60,
    "recovery_save_wait_ms": 0.60,
    "ckpt_sync_save_stall_ms": 0.50,
    "ckpt_async_save_stall_ms": 1.00,  # ~1ms quantities, scheduler-bound
    "host_aug_python_images_per_sec_per_core": 0.25,
    "host_aug_images_per_sec_per_core": 0.25,
    "host_aug_native_speedup_per_core": 0.25,
    # Decode serving leg (ZK_BENCH_DECODE): tokens/s is a wall-clock
    # ratio over a scheduler loop with host-side bookkeeping — steadier
    # than percentile tails but still thread/GC-exposed; TTFT p99 is a
    # tail of a handful of prefill cohorts and scatters accordingly.
    "serve_decode_tokens_per_sec_per_chip": 0.25,
    "decode_ttft_p99_ms": 0.50,
    "decode_ttft_p50_ms": 0.40,
    "decode_token_p50_ms": 0.40,
    "decode_prefill_p50_ms": 0.40,
    # Paged-decode-kernel era (docs/DESIGN.md §17): the A/B throughputs
    # gate like the headline (same wall-clock jitter class); the
    # speedup is a RATIO of two jittery numbers and scatters more; MBU
    # divides a millisecond-scale dispatch time into cost-analysis
    # bytes, so shared-host scheduling noise passes straight through.
    "decode_kernel_tokens_per_sec_per_chip": 0.30,
    "decode_reference_tokens_per_sec_per_chip": 0.25,
    "decode_kernel_speedup": 0.35,
    "decode_mbu": 0.35,
    # Speculative-decode era (docs/DESIGN.md §18): both throughputs are
    # the decode leg's jitter class; the speedup is a ratio of two
    # jittery wall-clock numbers; acceptance at the pinned zero-tail
    # workload is ~1.0 by construction — a real drop there means the
    # draft/teacher agreement broke, so it gates tightly.
    "spec_tokens_per_sec_per_chip": 0.25,
    "spec_plain_tokens_per_sec_per_chip": 0.25,
    "spec_speedup": 0.35,
    "spec_acceptance_rate": 0.10,
    # Paged-KV prefix-reuse era (docs/DESIGN.md §20): both TTFT medians
    # are single-dispatch prefill wall times on a shared host (the
    # decode TTFT jitter class); the speedup is their ratio and
    # scatters accordingly.
    "prefix_cold_ttft_p50_ms": 0.40,
    "prefix_warm_ttft_p50_ms": 0.40,
    "prefix_ttft_speedup": 0.35,
    # Binary-kernel era (docs/DESIGN.md §21): the A/B throughputs are
    # single-device forward wall clocks (decode-leg jitter class); the
    # speedup is a ratio of two jittery numbers; the int8-anchored MFU
    # divides a per-iter wall time into cost-analysis FLOPs, so host
    # scheduling noise passes straight through.
    "binary_kernel_images_per_sec_per_chip": 0.25,
    "binary_reference_images_per_sec_per_chip": 0.25,
    "binary_kernel_speedup": 0.35,
    "binary_mfu_vs_measured_int8_peak": 0.30,
    # Disaggregated-serving era (docs/DESIGN.md §22): both topologies'
    # throughputs are the decode leg's wall-clock jitter class; the
    # TTFT tails scatter like the single-mesh ones; the per-handoff
    # transfer median is a sub-millisecond device-put + two dispatches
    # on the CPU reference box, so host scheduling noise dominates.
    "disagg_tokens_per_sec_per_chip": 0.25,
    "disagg_baseline_tokens_per_sec_per_chip": 0.25,
    "disagg_ttft_p50_ms": 0.40,
    "disagg_ttft_p99_ms": 0.50,
    "disagg_baseline_ttft_p50_ms": 0.40,
    "disagg_baseline_ttft_p99_ms": 0.50,
    "transfer_ms_p50": 0.50,
    # Fleet-serving era (docs/DESIGN.md §23): both passes' aggregate
    # tokens/s ride worker HTTP round-trips on top of the decode leg's
    # wall-clock jitter; the TTFT medians are worker-side prefill wall
    # times (the §20 jitter class) and the speedup is their ratio; the
    # routing decision is a sub-millisecond host-side walk, so shared-
    # host scheduling noise passes straight through.
    "fleet_tokens_per_sec": 0.30,
    "fleet_rr_tokens_per_sec": 0.30,
    "fleet_warm_ttft_p50_ms": 0.40,
    "fleet_rr_ttft_p50_ms": 0.40,
    "fleet_cold_ttft_p50_ms": 0.40,
    "fleet_affinity_ttft_speedup": 0.35,
    "fleet_route_ms_p50": 0.50,
    # Trace-SLO guardrails era (docs/DESIGN.md §24): goodput is an
    # open-loop wall-clock ratio over a threaded replay (the decode
    # leg's jitter class, plus scheduler-thread scatter); the admitted
    # p99 TTFT is a tail over a burst cohort whose membership itself
    # shifts with admission timing; shed precision divides two small
    # timing-dependent counts, so it scatters the most.
    "trace_goodput_tokens_per_sec": 0.35,
    "trace_admitted_ttft_p99_ms": 0.60,
    "trace_shed_precision": 0.75,
    # Chunked-prefill era (docs/DESIGN.md §25): the ITL p99 is a tail
    # over client-side token-emission gaps under an open-loop replay
    # (the trace era's jitter class); the improvement ratio divides
    # two such tails, so it scatters doubly; TTFT p99 rides the same
    # replay; goodput is a wall-clock ratio over identical token work.
    "chunked_itl_p99_ms": 0.60,
    "chunked_itl_improvement": 0.50,
    "chunked_ttft_p99_ms": 0.60,
    "chunked_goodput_tokens_per_sec": 0.35,
}

#: HIGHER-better metric name patterns (throughput family). MBU joins
#: MFU: both are utilization-of-roofline ratios where down = regressed.
_HIGHER = re.compile(
    r"(_per_sec|_per_sec_per_chip|_per_sec_per_core|_qps|qps_per_chip"
    r"|^value$|^vs_baseline$|^mfu_|^binary_mfu_|_mfu$|_mbu$|_speedup"
    # Acceptance is the one _rate$ where UP is good (the generic _rate$
    # family — shed rate etc. — is lower-better); checked before _LOWER.
    r"|^spec_acceptance_rate$"
    # §24 shed precision: UP means sheds hit the doomed, not the
    # viable — no suffix family matches it, so it is named explicitly.
    r"|^trace_shed_precision$"
    # §25 ITL improvement: baseline-over-chunked tail ratio — UP means
    # chunking relieves more of the long-prefill stall; no suffix
    # family matches it, so it is named explicitly.
    r"|^chunked_itl_improvement$"
    r"|tokens_per_sec|images_per_sec|steps_overlapped)"
)

#: LOWER-better metric name patterns (latency/stall family). The §22
#: per-handoff transfer median spells its unit before the percentile
#: (it is also the serving result line's key), so it is named
#: explicitly rather than widening the suffix family.
_LOWER = re.compile(
    r"(_ms$|_time_ms$|_p50_ms$|_p95_ms$|_p99_ms$|_stall_ms$|_us$"
    r"|_frac$|_rate$|_wait_ms$|^transfer_ms_p50$"
    # §23 routing-decision latency spells its unit before the
    # percentile like the transfer median; named explicitly too.
    r"|^fleet_route_ms_p50$)"
)

#: Never-gating keys: identity, config, provenance. Drift is REPORTED
#: (a changed model or peak source explains a moved number) but a
#: config difference is not a perf regression.
_INFORMATIONAL = re.compile(
    r"(^model$|^metric$|^unit$|_source$|^binary_compute$|^n_chips$"
    r"|^batch_size$|^unroll$|^serve_bucket$|^seq|_seq_len$|_degree$"
    r"|_flavor$|^pack_residuals$|^git_|^jax_version$|^device_kind$|^platform$"
    r"|^bench_schema_version$|^compiler_options$|^lm_model$"
    r"|^lm_attention$|^lm_batch_size$|^lm_flash_block_|^lm_sp_degree$"
    r"|^host_cores$|^host_aug_native_available$|^shed_requests$"
    r"|^shed_queue_rows$|^sp_batch_size$|^obs_|^ckpt_state_mb$"
    r"|^recovery_restarts$|^sp_seq_len$"
    # Decode-leg workload shape: request count, slot count, budgets and
    # the refill/token tallies they determine are config, not perf.
    r"|^decode_requests$|^decode_slots$|^decode_new_tokens$"
    r"|^decode_refills$|^decode_generated_tokens$"
    # Speculative-leg workload shape (k, model depths, traffic counts).
    r"|^spec_k$|^spec_teacher_layers$|^spec_draft_layers$"
    r"|^spec_requests$|^spec_slots$|^spec_new_tokens$"
    # Prefix-reuse-leg workload shape + cache-effectiveness context:
    # hit rate and CoW count are DETERMINED by the synthetic workload
    # (every request shares one prefix), and pool fill is a capacity
    # statement, not a speed — none of them is a perf direction.
    r"|^prefix_requests$|^prefix_shared_tokens$|^prefix_tail_tokens$"
    r"|^prefix_hit_rate$|^prefix_cow_pages$|^kv_pool_fill$"
    # Binary-kernel-leg workload shape (model, batch, image side).
    r"|^binary_model$|^binary_batch$|^binary_image$"
    # Disaggregated-serving-leg workload shape + transfer volume: role
    # sizes and budgets are config; handoff/page/byte/bounce tallies
    # are DETERMINED by the workload (requests x pages-per-prompt),
    # not a speed.
    r"|^disagg_requests$|^disagg_slots$|^disagg_lanes$"
    r"|^disagg_new_tokens$|^disagg_transfer_handoffs$"
    r"|^disagg_transfer_pages$|^disagg_transfer_bytes$"
    r"|^disagg_host_bounces$|^disagg_generated_tokens$"
    # Fleet-serving-leg workload shape + affinity context: replica/
    # session/turn counts and token budgets are config; the hit rate
    # is DETERMINED by the synthetic workload (the bench RAISES when
    # any turn-2+ request lands cold, so 1.0 by construction) — none
    # of them is a perf direction.
    r"|^fleet_replicas$|^fleet_sessions$|^fleet_turns$"
    r"|^fleet_shared_tokens$|^fleet_tail_tokens$|^fleet_new_tokens$"
    r"|^fleet_affinity_hit_rate$|^fleet_generated_tokens$"
    # Trace-SLO-leg baseline + workload shape: the guardrails-OFF pass
    # exists to contextualize the gated guardrails-on numbers (its
    # whole point is to be worse under overload), and request/outcome
    # tallies are determined by the pinned trace — none is a perf
    # direction of the code under test.
    r"|^trace_baseline_|^trace_requests$|^trace_deadline_ms$"
    r"|^trace_shed_total$|^trace_ok_total$|^trace_deadline_expired$"
    # Chunked-prefill-leg baseline + workload shape: the monolithic
    # pass exists to contextualize the gated chunked numbers (its
    # whole point is to stall), and chunk/prompt/request tallies are
    # pinned workload config — none is a perf direction of the code
    # under test.
    r"|^chunked_baseline_|^chunked_chunk_tokens$|^chunked_long_"
    r"|^chunked_requests$|^chunked_generated_tokens$"
    # Peak ANCHORS and model FLOP counts are measurement context, not
    # code performance: an anchor that moved (re-measured peak, fixed
    # cache pathology — BENCH_r04's 237.9 TF/s) or a FLOPs change (a
    # model edit) EXPLAINS the gated numbers and must not gate itself.
    r"|_peak_tflops$|_peak_tops$|_step_tflops$)"
)


def classify_metric(name: str) -> Optional[str]:
    """"higher" / "lower" / None (non-gating). Informational wins:
    config ints often end in suffixes the direction patterns match."""
    if _INFORMATIONAL.search(name):
        return None
    if _HIGHER.search(name):
        return "higher"
    if _LOWER.search(name):
        return "lower"
    return None


@dataclass
class BenchDiff:
    rows: List[Dict[str, Any]] = field(default_factory=list)
    regressions: List[Dict[str, Any]] = field(default_factory=list)
    improvements: List[Dict[str, Any]] = field(default_factory=list)
    drift: List[Dict[str, Any]] = field(default_factory=list)
    added: List[str] = field(default_factory=list)
    removed: List[str] = field(default_factory=list)
    schema_mismatch: bool = False

    @property
    def ok(self) -> bool:
        return not self.regressions

    def as_dict(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "schema_mismatch": self.schema_mismatch,
            "regressions": self.regressions,
            "improvements": self.improvements,
            "drift": self.drift,
            "added": self.added,
            "removed": self.removed,
            "rows": self.rows,
        }

    def report(self) -> str:
        lines = []
        if self.schema_mismatch:
            lines.append(
                "! bench_schema_version differs: diff is REPORT-ONLY "
                "(renamed keys would read as regressions)"
            )
        for row in self.regressions:
            lines.append(
                "REGRESSION {name}: {prev:g} -> {cur:g} "
                "({delta:+.1%}, tol {tol:.0%}, {direction}-is-better)".format(
                    **row
                )
            )
        for row in self.improvements:
            lines.append(
                "improved   {name}: {prev:g} -> {cur:g} ({delta:+.1%})".format(
                    **row
                )
            )
        for row in self.drift:
            lines.append(
                f"drift      {row['name']}: {row['prev']!r} -> "
                f"{row['cur']!r} (informational)"
            )
        if self.added:
            lines.append(f"added      {', '.join(sorted(self.added))}")
        if self.removed:
            lines.append(f"removed    {', '.join(sorted(self.removed))}")
        if not lines:
            lines.append("no differences beyond tolerance")
        return "\n".join(lines)


def load_bench_json(path: str) -> Dict[str, Any]:
    """Load a bench artifact: a raw ``{"metric": ...}`` line object or
    the committed driver wrapper (``{"parsed": {...}}``)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got {type(doc)}")
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    if "metric" not in doc and "value" not in doc:
        raise ValueError(
            f"{path}: neither a bench line (metric/value keys) nor a "
            "driver wrapper with one under 'parsed'"
        )
    return doc


def compare(
    current: Dict[str, Any],
    previous: Dict[str, Any],
    *,
    rel_tol: float = DEFAULT_REL_TOL,
    tolerances: Optional[Dict[str, float]] = None,
) -> BenchDiff:
    """Diff two bench line objects. Gating only applies to metrics
    present on BOTH sides with a known direction; see module docstring
    for the classification and schema rules."""
    tol_table = dict(TOLERANCES)
    tol_table.update(tolerances or {})
    diff = BenchDiff()
    diff.schema_mismatch = current.get("bench_schema_version") != previous.get(
        "bench_schema_version"
    )
    cur_keys, prev_keys = set(current), set(previous)
    diff.added = sorted(cur_keys - prev_keys)
    diff.removed = sorted(prev_keys - cur_keys)
    for name in sorted(cur_keys & prev_keys):
        cur, prev = current[name], previous[name]
        direction = (
            classify_metric(name)
            if isinstance(cur, (int, float))
            and isinstance(prev, (int, float))
            and not isinstance(cur, bool)
            and not isinstance(prev, bool)
            else None
        )
        if direction is None:
            if cur != prev:
                diff.drift.append({"name": name, "prev": prev, "cur": cur})
            continue
        if prev == 0 or cur < 0 or prev < 0:
            # prev == 0: no relative scale. Negative: the repo-wide -1
            # "unknown" sentinel (MFU without cost analysis, HBM
            # without memory_stats) — a measurement gap on either
            # side, not a perf move. Both report as drift only.
            if cur != prev:
                diff.drift.append({"name": name, "prev": prev, "cur": cur})
            continue
        delta = (cur - prev) / abs(prev)
        tol = tol_table.get(name, rel_tol)
        row = {
            "name": name,
            "prev": prev,
            "cur": cur,
            "delta": delta,
            "tol": tol,
            "direction": direction,
        }
        diff.rows.append(row)
        bad = delta < -tol if direction == "higher" else delta > tol
        good = delta > tol if direction == "higher" else delta < -tol
        if bad and not diff.schema_mismatch:
            diff.regressions.append(row)
        elif good:
            diff.improvements.append(row)
    return diff


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("current", help="current bench JSON")
    parser.add_argument("previous", help="previous bench JSON to gate on")
    parser.add_argument(
        "--tol", type=float, default=DEFAULT_REL_TOL,
        help="default relative tolerance (fraction, e.g. 0.10)",
    )
    parser.add_argument(
        "--json", dest="json_out", default=None,
        help="also write the full diff as JSON here (CI artifact)",
    )
    parser.add_argument(
        "--allow-regression", action="store_true",
        help="report regressions but exit 0 (trajectory-report mode)",
    )
    args = parser.parse_args(argv)
    try:
        current = load_bench_json(args.current)
        previous = load_bench_json(args.previous)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    diff = compare(current, previous, rel_tol=args.tol)
    print(diff.report())
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(diff.as_dict(), f, indent=1)
    if not diff.ok and not args.allow_regression:
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
