#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` also ``breakdown``), and last the numbers that decided
``correct``, each beside its limit (``compared``). The same numbers are the
last lines of standard error. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, the run ends
with exit code 3 and prints no result. ``--rehearse`` (with
``JAX_PLATFORMS=cpu`` set explicitly) drives the same code at the tiny
sizes the data files give under ``rehearsal``; its last line is marked as a
rehearsal and carries counts only: no ``metrics``, no ``device``.

Every name is resolved through ``BENCHMARK.json`` to a data file or a small
reader under ``benchmarks/`` (see ``benchmarks/README.md``); there is no
list of cells, configurations or metrics in any Python file.
"""

import argparse
import json
import os
import shutil
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from zkbench import cells, device as zk_device  # noqa: E402


class Context:
    def __init__(self, args, cell):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse)
        self.with_control = bool(args.with_control)
        self.keep_trace = args.keep_trace
        self.sweep_rates = (
            [float(r) for r in args.sweep_rates.split(",")]
            if args.sweep_rates else None
        )
        self.clock = zk_device.SetupClock()
        self.out_dir = os.path.join(cell.root, ".bench_out", cell.name)
        self.device = None
        self.compile_clock = None

    def phase(self, name: str) -> None:
        """One line on standard error per phase of the run: seconds since
        process start and the host memory this process holds."""
        rss = 0.0
        try:
            with open("/proc/self/statm") as f:
                rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**30
        except (OSError, ValueError, IndexError):
            pass
        print(
            f"benchmark: phase {name} at {self.clock.since_start():.1f}s "
            f"(host rss {rss:.2f} GiB)",
            file=sys.stderr, flush=True,
        )

    def keep_extract(self, extract):
        """Debugging aid (``--keep-trace DIR``): the trace's plain form."""
        if not self.keep_trace:
            return
        os.makedirs(self.keep_trace, exist_ok=True)
        path = os.path.join(self.keep_trace, self.cell.name + ".extract.json")
        with open(path, "w") as f:
            json.dump(extract, f)


def layer_metrics(cell, layer_ctx, device):
    """Each per-layer metric of the cell, read by its own reader. A reader
    that finds nothing to read returns ``None`` and the metric is left out
    of the line."""
    out = {}
    peaks = cell.peaks(device["kind"])
    for metric in cell.per_layer:
        spec, reader = cell.layer_metric(metric["name"])
        value = reader.read(
            dict(layer_ctx, spec=spec, peaks=peaks, cell=cell)
        )
        if value is None:
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument(
        "--with-control", action="store_true",
        help="also put the control (the reference in float8) and the planted "
        "faults in the program's place and judge each by the cell's limits: "
        "every one has to come out not correct (setting limits)",
    )
    parser.add_argument("--keep-trace", default=None)
    parser.add_argument(
        "--sweep-rates", default=None,
        help="serve cells: offer these rates (comma-separated requests/s) one "
        "after another in one process and print what each gave; finds the knee",
    )
    args = parser.parse_args(argv)

    try:
        cell = cells.Cell(args.workload, ROOT)
    except (cells.CellError, OSError, ValueError, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "zookeeper_tpu")):
        print(
            "benchmark: the program (zookeeper_tpu/) is not beside the "
            "benchmark; there is nothing to measure here. No result.",
            file=sys.stderr,
        )
        return 3
    if args.seconds is None:
        args.seconds = cell.run_seconds
    ctx = Context(args, cell)
    ctx.device = device = zk_device.require_chips(cell.chips, ctx.rehearse)
    zk_device.enable_compile_cache()
    ctx.compile_clock = zk_device.CompileClock()
    shutil.rmtree(ctx.out_dir, ignore_errors=True)
    os.makedirs(ctx.out_dir, exist_ok=True)

    try:
        outcome = cell.entry_module().run(ctx)
    except Exception:
        # The one boundary: a run that cannot finish prints no result.
        traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(ctx.out_dir, ignore_errors=True)

    for note in outcome.get("notes", []):
        print(f"benchmark: {note}", flush=True)
    controls = outcome.get("controls") or {}
    for name, verdict in controls.items():
        over = [
            f"{k}={row['value']}>{row['limit']}"
            for k, row in verdict["compared"].items() if not row["ok"]
        ]
        print(
            f"benchmark: in the program's place, {name} is judged correct: "
            f"{str(verdict['correct']).lower()} (over its limit: "
            f"{', '.join(over) or 'nothing'})",
            flush=True,
        )
    print(
        f"benchmark: set-up {json.dumps(ctx.compile_clock.snapshot())}",
        flush=True,
    )
    compared = outcome["compared"]
    result = {
        "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
    }
    if ctx.rehearse:
        result = {"rehearsal": True, **result, "counts": outcome.get("counts", {})}
        if ctx.trace:
            # the readers run, so that a rehearsal exercises them; their
            # values are not device numbers and are not printed
            read = layer_metrics(cell, outcome["layer_ctx"], {"kind": "TPU v5 lite"})
            result["layer_metrics_read"] = sorted(read)
    else:
        names = [m["name"] for m in cell.end_to_end]
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        device_out = dict(device, memory_peak_bytes=outcome["memory_peak_bytes"])
        if ctx.trace:
            layer_ctx = outcome["layer_ctx"]
            trace = layer_ctx["trace"]
            result["metrics"] = layer_metrics(cell, layer_ctx, device)
            device_out["busy_s"] = trace.busy_s()
            device_out["window_s"] = trace.window_s
            result["device"] = device_out
            from zkbench import spans

            result["breakdown"] = {
                "device_ops": trace.top_ops(10),
                "idle_gaps": trace.idle_gaps(
                    spans.as_host_spans(layer_ctx["spans"]), 10
                ),
            }
        else:
            missing = [n for n in names if n not in outcome["end_to_end"]]
            if missing:
                print(
                    f"benchmark: the entry gave no {missing}", file=sys.stderr
                )
                return 1
            result["metrics"] = {
                n: {"value": float(outcome["end_to_end"][n]), "unit": units[n]}
                for n in names
            }
            result["device"] = device_out
    if controls:
        result["controls"] = {n: v["correct"] for n, v in controls.items()}
    result["compared"] = compared
    sys.stdout.flush()
    for name, row in compared.items():
        print(
            f"compared {name}: value={row['value']} limit={row['limit']} "
            f"{'ok' if row['ok'] else 'OVER'}",
            file=sys.stderr,
        )
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
