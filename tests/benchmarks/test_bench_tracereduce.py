"""The trace reduction: busy union, idle share, gap labelling, kernel time
by name: on a hand-made extract, and on a small extract recorded on the
chip (``data/trace_extract.json``)."""

import json
import os

import pytest

from zkbench import tracereduce
from zkbench.tracereduce import DeviceTrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Times in ns on the profiler's clock. Window [1000, 11000]: 10 us.
HAND = {
    "planes": [["/device:TPU:0", ["XLA Modules", "XLA Ops"]]],
    "marks": [["window_start", 1000.0], ["window_end", 11000.0]],
    "devices": {
        "/device:TPU:0": {
            "modules": [
                ["jit_decode_fn(1)", 1000.0, 3000.0, {}],
                ["jit_prefill_fn(2)", 6000.0, 3000.0, {}],
                ["jit_decode_fn(1)", 10000.0, 2000.0, {}],  # half outside
            ],
            "ops": [
                ["fusion.1", 1000.0, 1000.0, {"hlo_category": "convolution fusion"}],
                # overlaps fusion.1 by 500: the union counts it once
                ["custom-call.7", 1500.0, 1500.0, {"hlo_category": "custom-call", "tf_op": "jit(decode_fn)/pool_paged_decode"}],
                ["fusion.2", 3500.0, 500.0, {"hlo_category": "loop fusion"}],
                ["convolution.3", 6000.0, 3000.0, {"hlo_category": "convolution"}],
                ["fusion.9", 10000.0, 2000.0, {"hlo_category": "loop fusion"}],
                ["fusion.5", 20000.0, 100.0, {"hlo_category": "loop fusion"}],  # outside
            ],
        }
    },
}


def test_union_clip_and_gaps():
    merged = tracereduce.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert merged == [(1, 4), (5, 8)]
    assert tracereduce.clip(merged, 2, 6) == [(2, 4), (5, 6)]
    assert tracereduce.gaps(merged, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert tracereduce.op_family("fusion.123") == "fusion"
    assert tracereduce.op_family("custom-call.7") == "custom-call"


def test_busy_union_and_idle_share():
    trace = DeviceTrace(HAND)
    # busy: [1000,3000] + [3500,4000] + [6000,9000] + [10000,11000] = 6500 ns
    assert trace.busy_s() == pytest.approx(6500e-9)
    assert trace.window_s == pytest.approx(10000e-9)
    assert trace.idle_share() == pytest.approx(0.35)


def test_kernel_time_by_name_and_category():
    trace = DeviceTrace(HAND)
    seconds, count = trace.op_seconds(
        lambda name, stats: "pool_paged" in stats.get("tf_op", "")
    )
    assert (seconds, count) == (pytest.approx(1500e-9), 1)
    seconds, count = trace.op_seconds(
        lambda name, stats: "convolution" in stats.get("hlo_category", "")
    )
    assert (seconds, count) == (pytest.approx(4000e-9), 2)
    # modules: the last decode call is clipped at the window's end
    seconds, calls = trace.module_seconds("decode_fn")
    assert (seconds, calls) == (pytest.approx(4000e-9), 2)
    top = dict((name, s) for name, s in trace.top_ops(10))
    assert top["convolution"] == pytest.approx(3000e-9)
    assert top["loop fusion/fusion"] == pytest.approx(1500e-9)


def test_gap_labelling_by_host_span():
    # host clock = profiler clock - 100: the mark at profiler 1000 was
    # written at host 900.
    trace = DeviceTrace(HAND, mark_host_ns={"window_start": 900})
    assert trace.host_offset_ns == 100
    host_spans = [
        ("data_wait", 3900, 2000),  # profiler 4000..6000: covers the big gap
        ("dispatch", 2950, 20),     # profiler 3050..3070: a sliver of a gap
    ]
    labelled = dict(
        (name, s) for name, s in trace.idle_gaps(host_spans, min_gap_ns=400.0)
    )
    # gaps: [3000,3500] (500), [4000,6000] (2000), [9000,10000] (1000)
    assert labelled["data_wait"] == pytest.approx(2000e-9)
    assert labelled["unattributed"] == pytest.approx(1500e-9)
    # without marks on the host's clock every gap is unattributed
    bare = dict((n, s) for n, s in DeviceTrace(HAND).idle_gaps(host_spans, min_gap_ns=400.0))
    assert bare == {"unattributed": pytest.approx(3500e-9)}


def test_two_chips_average():
    two = json.loads(json.dumps(HAND))
    two["devices"]["/device:TPU:1"] = {"modules": [], "ops": [["fusion.1", 1000.0, 1000.0, {}]]}
    trace = DeviceTrace(two, chips=2)
    assert trace.busy_s() == pytest.approx((6500e-9 + 1000e-9) / 2)


def test_no_window_marks_is_an_error():
    with pytest.raises(ValueError):
        DeviceTrace({"devices": HAND["devices"], "marks": []})
    with pytest.raises(ValueError):
        DeviceTrace({"devices": {}, "marks": HAND["marks"], "planes": []})


RECORDED = os.path.join(DATA, "trace_extract.json")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded extract")
def test_recorded_extract_from_the_chip():
    """A slice of a real trace (TPU v5 lite, the training cell): the
    reduction's numbers on it are pinned, so a change to the reduction
    shows."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    trace = DeviceTrace(recorded["extract"])
    expect = recorded["expect"]
    assert trace.window_s == pytest.approx(expect["window_s"])
    assert trace.busy_s() == pytest.approx(expect["busy_s"])
    assert 0.0 <= trace.idle_share() <= 1.0
    conv, n = trace.op_seconds(lambda name, stats: stats.get("kind") == "kOutput")
    assert conv == pytest.approx(expect["koutput_s"]) and n == expect["koutput_events"]
    assert 0.5 < conv / trace.busy_s() < 1.0  # convolutions take most of a step
    assert [name for name, _ in trace.top_ops(3)] == expect["top3"]
    assert trace.module_seconds("train_step")[1] == expect["train_step_modules"] == 4
    # an op inside a module is found by the module's name, and only there
    inside, n_in = trace.op_seconds(
        lambda name, stats: stats.get("kind") == "kOutput", within_module="train_step"
    )
    assert n_in == n and inside == pytest.approx(conv)
    assert trace.op_seconds(lambda name, stats: True, within_module="no_such")[1] == 0
    # busy never passes the window, and gaps + busy make the window
    gaps = sum(s for _, s in trace.idle_gaps((), k=100))
    assert gaps + trace.busy_s() == pytest.approx(trace.window_s)
