"""The readers PR 26 adds, each on a hand-made record list or trace: what
the scheduler's window release and the engine's expert-load events record,
and what a program that lacks them (the parent commit) gives: nothing, and
no error."""

import pytest

from zkbench import cells, tracereduce

BENCH = cells.load_benchmark()
CELL = "mellum2.mixed_lengths_closed"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def rec(name, ts_ms, dur_ms=None, *, step=None, attrs=None, thread=1):
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": thread, "thread_name": f"t{thread}", "step": step,
        "slab": None, "attrs": attrs, "rid": None,
    }


def read_metric(name, records=(), work=None, trace=None, cell_name=CELL):
    cell = cells.Cell(cell_name)
    spec, reader = cell.layer_metric(name)
    return reader.read({
        "spans": list(records), "window_host_ns": (0, int(1e9)), "spec": spec,
        "cell": cell, "counters": {}, "work": work or {}, "trace": trace,
        "peaks": PEAKS,
    })


def device_trace(ops, modules):
    """A one-chip trace of ``(name, start_ms, dur_ms, stats)`` events."""
    to_ns = lambda rows: [[n, s * 1e6, d * 1e6, st] for n, s, d, st in rows]
    extract = {
        "devices": {"/device:TPU:0": {"ops": to_ns(ops), "modules": to_ns(modules)}},
        "marks": [["window_start", 0.0], ["window_end", 1e9]], "planes": [],
    }
    return tracereduce.DeviceTrace(
        extract, chips=1, mark_host_ns={"window_start": 0, "window_end": int(1e9)}
    )


OLD = [
    rec("sched_sweep", 1, 0.1, step=1),
    rec("decode_dispatch", 2, 20, step=1),
    rec("sched_iteration_end", 23, step=1),
]


@pytest.mark.parametrize("name", ["window_pages_released_share", "moe_load_max_over_mean"])
def test_a_counter_reader_that_finds_no_event_returns_none(name):
    assert read_metric(name, []) is None
    assert read_metric(name, OLD) is None


def test_window_pages_released_share():
    records = OLD + [
        rec("sched_window_release", 22, 0.05, step=1),
        rec("kv_pages_allocated", 22.1, step=1, attrs={"full": 90, "window": 60}),
        rec("kv_pages_released", 22.1, step=1, attrs={"full": 0, "window": 9}),
        rec("kv_pages_allocated", 45, step=2, attrs={"full": 4, "window": 4}),
        rec("kv_pages_released", 45, step=2, attrs={"full": 0, "window": 7}),
    ]
    assert read_metric("window_pages_released_share", records) == pytest.approx(25.0)
    # the release's span is one of the scheduler's own leaves: the
    # accepted reader of the closed-loop cells counts it
    assert read_metric("sched_host_self_ms.summarize", records) == pytest.approx(0.15)


def test_moe_load_max_over_mean():
    even = [[4, 4, 4, 4], [2, 2, 2, 2]]
    skew = [[8, 0, 4, 4], [2, 2, 2, 2]]
    records = [
        rec("moe_tokens_per_expert", 5, attrs={"program": "decode_step", "counts": even}),
        rec("moe_tokens_per_expert", 9, attrs={"program": "prefill", "counts": skew}),
    ]
    # summed: layer 0 [12, 4, 8, 8] -> 12 / 8; layer 1 even -> 1
    assert read_metric("moe_load_max_over_mean", records) == pytest.approx((1.5 + 1.0) / 2)
    assert read_metric("moe_load_max_over_mean", records[:1]) == pytest.approx(1.0)


def work(model):
    return {
        "model": model, "page_size": 16,
        "decode_steps": [[600] * 64, [2000] * 64],
        "prefills": [(1000, 0), (3000, 0)],
    }


def test_kernel_readers_find_their_ops_by_name_and_nothing_else():
    cell = cells.Cell(CELL)
    model = cell.config["model"]
    shapes = cell.shapes_module("mellum2")
    modules = [
        ("jit_decode_fn(1)", 0, 20, {}), ("jit_decode_fn(1)", 30, 20, {}),
        ("jit_prefill_fn(2)", 60, 40, {}), ("jit_prefill_fn(3)", 110, 90, {}),
    ]
    # the names the v5e's trace gives the three kernels (chip run, PR 26)
    call = {"target": "tpu_custom_call", "text": "bf16[...] custom-call(...)"}
    ops = [
        ("_gmm.1", 1, 8, call), ("_pool_paged_decode_call", 10, 2, call),
        ("_gmm.1", 31, 8, call), ("_pool_paged_decode_call", 40, 2, call),
        ("ragged-dot-none.5", 61, 10, call), ("_flash_forward.2", 75, 5, call),
        ("_gmm.5", 111, 30, call), ("_flash_forward.2", 150, 25, call),
        ("fusion.9", 20, 1, {"kind": "kOutput"}),
    ]
    trace = device_trace(ops, modules)
    w = work(model)
    least = sum(shapes.least_expert_seconds(model, 64, PEAKS) for _ in range(2))
    least += shapes.least_expert_seconds(model, 1000, PEAKS)
    least += shapes.least_expert_seconds(model, 3000, PEAKS)
    assert read_metric("moe_expert_roofline", work=w, trace=trace) == pytest.approx(
        100 * least / 0.056
    )
    nbytes = sum(shapes.live_kv_bytes(model, lens, 16) for lens in w["decode_steps"])
    assert read_metric("decode_attn_roofline.mellum2", work=w, trace=trace) == pytest.approx(
        100 * nbytes / 819e9 / 0.004
    )
    attention = shapes.prompt_attention_ops(model, 1000) + shapes.prompt_attention_ops(model, 3000)
    assert read_metric("flash_prefill_roofline.mellum2", work=w, trace=trace) == pytest.approx(
        100 * attention / 197e12 / 0.030
    )
    # a trace without the kernels (or a program without experts): nothing
    bare = device_trace([("fusion.9", 20, 1, {"kind": "kOutput"})], modules)
    for name in ("moe_expert_roofline", "decode_attn_roofline.mellum2", "flash_prefill_roofline.mellum2"):
        assert read_metric(name, work=w, trace=bare) is None
