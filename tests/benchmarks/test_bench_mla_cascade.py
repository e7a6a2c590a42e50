"""The two per-layer metrics of the cascade decode kernel on hand-made
traces and hand-made ``decode_mla_rows`` events: its roofline counts the
rows the kernel moves (``rows_fetched``) and not the rows the slots attend
(``rows_read``), and each reads nothing, without an error, from a program
that lacks what it reads (the kernel, or the new attributes)."""

import pytest

from zkbench import cells, tracereduce

CELL = "deepseek_v2_lite.prefix16k_closed"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
S = cells.Cell(CELL).shapes_module("deepseek_v2")


def rec(name, ts_ms, attrs):
    return {
        "phase": "i", "name": name, "ts_ns": int(ts_ms * 1e6), "dur_ns": 0,
        "thread_id": 1, "thread_name": "t1", "step": None, "slab": None,
        "attrs": attrs, "rid": None,
    }


def read_metric(name, records=(), work=None, trace=None):
    cell = cells.Cell(CELL)
    spec, reader = cell.layer_metric(name)
    return reader.read({
        "spans": list(records), "window_host_ns": (0, int(1e9)), "spec": spec,
        "cell": cell, "counters": {}, "work": work or {}, "trace": trace,
        "peaks": PEAKS,
    })


def device_trace(ops):
    to_ns = lambda rows: [[n, s * 1e6, d * 1e6, st] for n, s, d, st in rows]
    modules = [
        ("jit_decode_fn(1)", 0, 40, {}), ("jit_decode_fn(1)", 50, 40, {}),
    ]
    extract = {
        "devices": {"/device:TPU:0": {"ops": to_ns(ops), "modules": to_ns(modules)}},
        "marks": [["window_start", 0.0], ["window_end", 1e9]], "planes": [],
    }
    return tracereduce.DeviceTrace(
        extract, chips=1, mark_host_ns={"window_start": 0, "window_end": int(1e9)}
    )


CALL = {"target": "tpu_custom_call", "text": "f32[...] custom-call(...)"}
#: two decode steps of 128 slots at 16,600 and 16,700 rows, 6 ms of the
#: cascade kernel each
KERNEL = [
    ("_mla_cascade_decode_call", 1, 6, CALL), ("_gmm.5", 30, 5, CALL),
    ("_mla_cascade_decode_call", 51, 6, CALL), ("_gmm.5", 80, 5, CALL),
]
STEPS = [[16600] * 128, [16700] * 128]
#: a step's rows: the 16,384-row prefix once, each slot's rows past it
EVENTS = [
    rec("decode_mla_rows", 1 + 50 * i, {
        "rows_read": 128 * n * 6, "rows_shared": 128 * 16384 * 6,
        "rows_cascaded": 128 * 16384 * 6,
        "rows_fetched": (16384 + 128 * (n - 16384)) * 6,
    })
    for i, n in enumerate((16600, 16700))
]


def test_the_roofline_counts_the_rows_the_kernel_moves():
    model = cells.Cell(CELL).config["model"]
    work = {"model": model, "decode_steps": STEPS}
    got = read_metric("mla_cascade_decode_roofline", EVENTS, work, device_trace(KERNEL))
    ops = sum(S.decode_kernel_ops(model, c) for lens in STEPS for c in lens)
    nbytes = 2 * 576 * sum(e["attrs"]["rows_fetched"] for e in EVENTS) + 256 * 6 * (
        2 * 16 * 576 + 4 * 16 * 512
    )
    least = max(ops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least / 0.012)
    # bound by the operations here: 2.3 ms a step against 6 ms
    assert least == ops / 197e12 and 30 < got < 50
    # with every slot's prefix read counted the bytes alone would read 300%
    read_all = 2 * 576 * sum(e["attrs"]["rows_read"] for e in EVENTS) / 819e9
    assert 100 * read_all / 0.012 > 250
    # the bytes are rows_fetched's: a step that moves more rows reads higher
    heavier = [
        rec(e["name"], 1, {**e["attrs"], "rows_fetched": 20 * e["attrs"]["rows_fetched"]})
        for e in EVENTS
    ]
    more = read_metric("mla_cascade_decode_roofline", heavier, work, device_trace(KERNEL))
    assert more > got


def test_the_roofline_holds_its_sums_to_the_steps_the_device_ran():
    """Three events and one step stamped for the two steps the device ran:
    each sum is scaled to two steps."""
    model = cells.Cell(CELL).config["model"]
    work = {"model": model, "decode_steps": STEPS[:1]}
    events = EVENTS + [EVENTS[0]]
    got = read_metric("mla_cascade_decode_roofline", events, work, device_trace(KERNEL))
    ops = 2 * sum(S.decode_kernel_ops(model, c) for c in STEPS[0])
    assert got == pytest.approx(100 * ops / 197e12 / 0.012)


def test_the_row_share_is_rows_cascaded_over_rows_read():
    got = read_metric("mla_cascade_row_share", EVENTS)
    want = 100 * sum(e["attrs"]["rows_cascaded"] for e in EVENTS) / sum(
        e["attrs"]["rows_read"] for e in EVENTS
    )
    assert got == pytest.approx(want) and 97 < got < 99


@pytest.mark.parametrize(
    "name", ["mla_cascade_decode_roofline", "mla_cascade_row_share"]
)
def test_nothing_to_read_is_none(name):
    """The parent program: the per-slot kernel under its old name and the
    events without the cascade's attributes; or no events at all."""
    model = cells.Cell(CELL).config["model"]
    work = {"model": model, "decode_steps": STEPS}
    old_kernel = device_trace(
        [("_mla_paged_decode_call", 1, 20, CALL), ("_mla_paged_decode_call", 51, 20, CALL)]
    )
    old_events = [
        rec("decode_mla_rows", 1, {"rows_read": 1000, "rows_shared": 900}),
    ]
    assert read_metric(name, old_events, work, old_kernel) is None
    assert read_metric(name, (), work, device_trace(KERNEL)) is None
