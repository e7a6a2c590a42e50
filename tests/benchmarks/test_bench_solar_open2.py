"""What PR 33 adds to the benchmark: the cell ``solar_open2.doc_decode_closed``
rehearses on the CPU, its shapes against hand counts, its nine per-layer
metrics each on a hand-made trace or record list (and nothing, without an
error, from a program that lacks what they read: the parent commit), the
configuration's file against the catalog row's sizes, the entry that draws
named leaves about another mean, and the five controls judged not correct
by the configuration's own limit at the rehearsal's size."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from zkbench import cells, tracereduce
from zkbench.weights import make_weights

BENCH = cells.load_benchmark()
CELL = "solar_open2.doc_decode_closed"
CONFIG = "solar_open2_ep8_4l"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RUN = os.path.join(cells.ROOT, "benchmarks", "run.py")
NEW_METRICS = (
    "serve_mfu.solar_open2", "decode_step_device_ms.solar_open2",
    "decode_attn_roofline.solar_open2", "flash_prefill_roofline.solar_open2",
    "moe_expert_roofline.solar_open2", "kda_prefill_roofline",
    "kda_decode_roofline", "kda_live_slot_share", "moe_held_choice_share",
)
CONTROLS = ("all_fp8", "state_lost", "beta_halved", "decay_a_head", "shared_dropped")


@pytest.mark.parametrize(
    "trace,control", [(0, False), (1, False), (0, True)],
    ids=["trace0", "trace1", "with-control"],
)
def test_the_cell_rehearses(trace, control):
    """``--rehearse`` drives the entry, the program, the reference and the
    comparison at the tiny sizes, the decay's bias drawn about the
    configuration's mean; traced, the readers run and those a CPU trace
    can feed (no device plane, no program names) find their records;
    ``--with-control`` judges the float8 control and the four planted
    faults not correct by the configuration's own limit."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--rehearse", "--seed",
         str(2**31 + 33), "--seconds", "2", "--trace", str(trace)]
        + (["--with-control"] if control else []),
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["counts"]["tokens_compared"] > 0
    if trace:
        assert {
            "kda_live_slot_share", "moe_held_choice_share", "serve_mfu.solar_open2",
        } <= set(last["layer_metrics_read"])
    if control:
        limit = last["compared"]["served_logit_gap"]["limit"]
        assert last["controls"] == {"control_fp8": False}
        for name in CONTROLS:
            (gap,) = re.findall(rf"'control_{name}_widest_gap': ([0-9.e+-]+)", done.stdout)
            assert float(gap) > limit, (name, gap)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    cell = cells.Cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS) <= set(names)
    assert {"prefill_device_ms_per_ktoken", "device_idle_share.summarize",
            "sched_host_self_ms.summarize"} <= set(names)
    for name in names:
        spec, reader = cell.layer_metric(name)
        assert spec["moves"] == "serve_tokens_per_s" and hasattr(reader, "read")
    # appended after everything PR 31 left, in one run
    order = [m["name"] for m in BENCH["per_layer"]]
    first = order.index(NEW_METRICS[0])
    assert order[first : first + len(NEW_METRICS)] == list(NEW_METRICS)
    assert first > order.index("ssm_live_slot_share")
    cells_in_order = [w["name"] for w in BENCH["workloads"]]
    assert cells_in_order.index(CELL) > cells_in_order.index("falcon_h1.chat_decode_closed")
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_configuration_holds_the_catalog_rows_sizes():
    """Every width and head count as published; depth, experts held and
    vocabulary are the three cuts; the program's fields say the same as
    the source's keys."""
    config = cells.Cell(CELL).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = {
        "hidden_size": 4096, "num_attention_heads": 64, "num_key_value_heads": 8,
        "head_dim": 128, "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 1, "gqa_interval": 3,
        "first_k_dense_replace": 0, "partial_rotary_factor": 1,
        "max_position_embeddings": 1048576, "use_rope": False, "use_gqa_gate": True,
        "kda_allow_neg_eigval": True, "kda_use_full_proj": False,
        "linear_attn_config": {
            "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None,
        },
        "gqa_layers": list(range(0, 48, 4)),
    }
    for key, value in published.items():
        assert config[key] == value and config["model"][key] == value, key
    cut = {"num_hidden_layers": 4, "n_routed_experts": 40, "vocab_size": 24576}
    for key, value in cut.items():
        assert config[key] == config["model"][key] == value
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
        "max_position_embeddings": 1048576,
    }
    assert set(config["reduced_from_source"]) == set(cut)
    for key in ("source", "deployment", "assumed", "departures", "precision", "limits_from"):
        assert config[key], key
    program, model = config["program"], config["model"]
    linear = model["linear_attn_config"]
    for field, value in (
        ("d_model", model["hidden_size"]), ("num_heads", model["num_attention_heads"]),
        ("num_kv_heads", model["num_key_value_heads"]), ("head_dim", model["head_dim"]),
        ("num_layers", model["num_hidden_layers"]), ("norm_eps", model["rms_norm_eps"]),
        ("num_experts", model["router_experts"]), ("held_experts", model["held_experts"]),
        ("experts_per_token", model["num_experts_per_tok"]),
        ("expert_dim", model["moe_intermediate_size"]),
        ("shared_expert_dim", model["n_shared_experts"] * model["moe_intermediate_size"]),
        ("kda_heads", linear["num_heads"]), ("kda_head_dim", linear["head_dim"]),
        ("kda_conv_taps", linear["short_conv_kernel_size"]),
        ("kda_gate_rank", model["kda_gate_rank"]), ("kda_chunk", model["kda_chunk"]),
        ("kda_neg_eigval", model["kda_allow_neg_eigval"]),
        ("attention_gate", model["use_gqa_gate"]),
    ):
        assert program["model." + field] == value, field
    assert model["router_experts"] == config["published"]["n_routed_experts"]
    assert model["held_experts"] == [0, model["n_routed_experts"]]
    assert program["model.positions"] == "none" and model["use_rope"] is False
    kinds = ["full" if l in model["gqa_layers"] else "kda" for l in range(4)]
    assert program["model.layer_types"] == kinds == ["full", "kda", "kda", "kda"]
    assert program["vocab_size"] == model["vocab_size"]
    assert program["engine.slots"] == 128 and program["engine.prefix_cache"] is False
    assert program["engine.kv_capacity"] == model["n_positions"] == 8192
    assert program["engine.pool_pages"] == -1 and program["engine.prefill_chunk_tokens"] == 0
    # whole chunks of 64, and the mix's longest prompt and answer fit a slot
    assert all(b % model["kda_chunk"] == 0 for b in program["engine.seq_buckets"])
    mix = cells.Cell(CELL).traffic
    assert max(program["engine.seq_buckets"]) == mix["prompt"]["body"]["max"] == 6144
    assert mix["prompt"]["body"]["max"] + mix["output"]["max"] == 7680 <= 8192
    assert mix["arrivals"] == {"process": "closed", "clients": 256}
    assert config["entry"] == "serve_shift" and config["weights_shift"] == {"kda_dt_bias": -4.0}


def test_shifted_weights_are_the_same_draw_about_another_mean():
    """``entries/serve_shift.py``: the named leaf plus its constant, in
    the leaf's own type; every other leaf bit for bit; a shift that names
    no leaf is refused."""
    import jax.numpy as jnp

    shift = cells.Cell(CELL).entry_module()
    like = {
        "embed": jax.ShapeDtypeStruct((32, 8), jnp.bfloat16),
        "block1": {
            "kda_dt_bias": jax.ShapeDtypeStruct((24,), jnp.bfloat16),
            "kda_A_log": jax.ShapeDtypeStruct((3,), jnp.bfloat16),
        },
    }
    plain = make_weights(like, 7)
    got = shift.shifted(make_weights, {"kda_dt_bias": -4.0})(like, 7)
    assert got["block1"]["kda_dt_bias"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got["block1"]["kda_dt_bias"], np.float32),
        np.asarray(
            (plain["block1"]["kda_dt_bias"].astype(jnp.float32) - 4.0).astype(jnp.bfloat16),
            np.float32,
        ),
    )
    for path in (("embed",), ("block1", "kda_A_log")):
        a, b = got, plain
        for key in path:
            a, b = a[key], b[key]
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    with pytest.raises(ValueError, match="names no leaf"):
        shift.shifted(make_weights, {"dt_biass": -4.0})(like, 7)


# -- shapes -------------------------------------------------------------------

S = cells.Cell(CELL).shapes_module("solar_open2")

#: d 8; 4 query heads over 2 key/value heads of 2; a KDA mixer of 2 heads
#: of 4, gates of rank 3, chunks of 5; a router of 12 of which 3 are held,
#: 4 choices a token, experts of width 6; layers attention, KDA, KDA;
#: vocabulary 11.
SMALL = {
    "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 2, "moe_intermediate_size": 6, "num_hidden_layers": 3,
    "vocab_size": 11, "gqa_layers": [0, 4],
    "linear_attn_config": {"num_heads": 2, "head_dim": 4},
    "kda_gate_rank": 3, "kda_chunk": 5, "router_experts": 12,
    "held_experts": [3, 3], "num_experts_per_tok": 4,
}


def test_shapes_by_hand():
    assert S.widths(SMALL) == (8, 4) and S.layer_counts(SMALL) == (1, 2)
    assert S.attention_layers(SMALL) == [True, False, False]
    # the attention layer: qkv 8*(8+8) = 128, gate and proj 8*8 each
    attention = 128 + 64 + 64
    # a KDA layer (I = 8): qkv and out 4*8*8 = 256, two gates 2*3*(8+8) =
    # 96, beta 8*2 = 16
    kda = 256 + 96 + 16
    # every layer: the router 8*12, the shared expert 3*8*6
    experts = 96 + 144
    assert S.dense_weight_elements(SMALL) == attention + 2 * kda + 3 * experts
    # 4 choices over 12 experts of which 3 are here: 1 held choice a token
    assert S.held_choices_per_token(SMALL) == 1.0
    assert S.expert_ops_per_choice(SMALL) == 6 * 8 * 6
    assert S.matmul_ops_per_token(SMALL) == 2 * S.dense_weight_elements(SMALL) + 3 * 288
    assert S.head_ops(SMALL) == 2 * 8 * 11
    # attention in the one attention layer only
    assert S.attention_ops_at(SMALL, 9) == 4 * 8 * 9
    assert S.prompt_attention_ops(SMALL, 7) == sum(
        S.attention_ops_at(SMALL, c) for c in range(1, 8)
    )
    # the update: 7 h p^2 = 7*2*16 a KDA layer
    assert S.kda_step_ops(SMALL) == 2 * 224
    # the chunked form, a token a head: 10 Q p + 6 p^2 = 200 + 96; the
    # kernel's four products alone 2 Q p + 6 p^2 = 40 + 96
    assert S.kda_scan_ops_per_token(SMALL) == 2 * 2 * 296
    assert S.kda_kernel_ops_per_token(SMALL) == 2 * 2 * 136
    assert S.prompt_ops(SMALL, 7) == (
        7 * (S.matmul_ops_per_token(SMALL) + S.kda_scan_ops_per_token(SMALL))
        + S.prompt_attention_ops(SMALL, 7) + S.head_ops(SMALL)
    )
    assert S.output_token_ops(SMALL, 9) == (
        S.matmul_ops_per_token(SMALL) + S.kda_step_ops(SMALL)
        + S.head_ops(SMALL) + S.attention_ops_at(SMALL, 9)
    )
    # bytes: a key and a value of 4 elements, 2 bytes, ONE layer
    assert S.kv_bytes_per_token(SMALL) == 16
    assert S.live_kv_bytes(SMALL, [3, 9], 4) == (4 + 12) * 16
    # the state: 2*4*4 float32 a KDA layer, in and out for 5 sequences
    assert S.kda_state_bytes(SMALL) == 2 * 128
    assert S.kda_step_bytes(SMALL, 5) == S.ssm_step_bytes(SMALL, 5) == 2 * 5 * 256
    # the kernel over 7 tokens: a head 14 p + 2 Q = 66 bytes a token
    assert S.kda_scan_bytes(SMALL, 7) == 2 * (7 * 2 * 66 + 128)
    # held expert bytes: min(3, choices) experts of 3*8*6 elements
    assert S.expert_bytes(SMALL, 2) == 2 * 144 * 2 and S.expert_bytes(SMALL, 9) == 3 * 144 * 2
    assert S.weight_bytes(SMALL, 2) == (
        (S.dense_weight_elements(SMALL) + 88) * 2 + 3 * S.expert_bytes(SMALL, 2)
    )


def test_shapes_at_the_published_widths_are_the_issues_arithmetic():
    model = cells.Cell(CELL).config["model"]
    assert S.layer_counts(model) == (1, 3)
    assert S.held_choices_per_token(model) == 1.0
    # the issue's table: attention layer 109 M, a KDA layer 137.7 M (its
    # 0.1 M of convolution taps is no matrix), router and shared 17 M
    dense = S.dense_weight_elements(model)
    assert dense == pytest.approx(109.1e6 + 3 * 137.6e6 + 4 * 17.04e6, rel=2e-3)
    held = 4 * S.expert_bytes(model, 128)
    assert held == pytest.approx(5.03e9, rel=2e-3)
    assert S.weight_bytes(model, 128) == pytest.approx(5.03e9 + 1.18e9 + 0.20e9, rel=1e-2)
    assert S.kda_state_bytes(model) / 3 == 64 * 128 * 128 * 4  # 4.19 MB a layer
    assert S.kda_step_bytes(model, 128) == pytest.approx(3.22e9, rel=2e-3)
    assert S.kv_bytes_per_token(model) == 4096
    # the chunked rule is a few percent of a prompt token's operations
    assert S.kda_scan_ops_per_token(model) == pytest.approx(0.035e9, rel=0.05)
    assert S.kda_scan_ops_per_token(model) < 0.03 * S.matmul_ops_per_token(model)
    least = S.least_decode_step_seconds(model, [1600] * 128, 16, PEAKS)
    assert least["least_s"] == least["memory_s"] == pytest.approx(0.0128, rel=0.03)


# -- readers ------------------------------------------------------------------


def rec(name, ts_ms, dur_ms=None, *, step=None, attrs=None):
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": 1, "thread_name": "t1", "step": step, "slab": None,
        "attrs": attrs, "rid": None,
    }


def read_metric(name, records=(), work=None, trace=None):
    """One metric of the cell by its own reader, as ``run.py`` calls it."""
    cell = cells.Cell(CELL)
    spec, reader = cell.layer_metric(name)
    return reader.read({
        "spans": list(records), "window_host_ns": (0, int(1e9)), "spec": spec,
        "cell": cell, "counters": {}, "work": work or {}, "trace": trace,
        "peaks": PEAKS,
    })


def device_trace(ops, modules):
    to_ns = lambda rows: [[n, s * 1e6, d * 1e6, st] for n, s, d, st in rows]
    extract = {
        "devices": {"/device:TPU:0": {"ops": to_ns(ops), "modules": to_ns(modules)}},
        "marks": [["window_start", 0.0], ["window_end", 1e9]], "planes": [],
    }
    return tracereduce.DeviceTrace(
        extract, chips=1, mark_host_ns={"window_start": 0, "window_end": int(1e9)}
    )


@pytest.mark.parametrize(
    "metric,event,attrs,want",
    [
        ("kda_live_slot_share", "decode_kda_slots",
         [{"slots_advanced": 128, "slots_live": 128}, {"slots_advanced": 128, "slots_live": 64}],
         75.0),
        ("moe_held_choice_share", "moe_held_choices",
         [{"program": "decode_step", "choices_held": 500, "choices_routed": 4096},
          {"program": "prefill", "choices_held": 3596, "choices_routed": 28672}],
         12.5),
    ],
    ids=["live-slots", "held-choices"],
)
def test_counter_readers_read_the_engines_events(metric, event, attrs, want):
    records = [rec("decode_dispatch", 2, 10, step=1)] + [
        rec(event, 2 + 12 * i, step=i, attrs=a) for i, a in enumerate(attrs)
    ]
    assert read_metric(metric, records) == pytest.approx(want)
    # a program without the counter (the parent): nothing, no error
    assert read_metric(metric, records[:1]) is None
    assert read_metric(metric, []) is None


def test_kernel_readers_find_their_ops_by_name_and_nothing_else():
    cell = cells.Cell(CELL)
    model = cell.config["model"]
    modules = [
        ("jit_decode_fn(1)", 0, 20, {}), ("jit_decode_fn(1)", 30, 20, {}),
        ("jit_prefill_fn(2)", 60, 40, {}), ("jit_prefill_fn(3)", 110, 90, {}),
    ]
    # a Pallas call is named after the jitted function that holds it; an
    # XLA fusion's name says nothing, and the one-token update's two
    # fusions are found by the shape of the whole state a layer in their
    # HLO text
    call = {"target": "tpu_custom_call", "text": "f32[...] custom-call(...)"}
    reads = {"kind": "kLoop", "text": (
        "(f32[128,64,128]{2,1,0:T(8,128)}, f32[128,64,128]{2,1,0:T(8,128)}) "
        "fusion(f32[128,64,128,128]{3,2,1,0:T(8,128)} %S.1, %bitcast.3), kind=kLoop"
    )}
    writes = {"kind": "kLoop", "text": (
        "f32[128,64,128,128]{3,2,1,0:T(8,128)} fusion(%S.1, %bitcast.4), kind=kLoop"
    )}
    ops = [
        ("multiply_reduce_fusion.4", 1, 5, reads), ("add_fusion.2", 6, 3, writes),
        ("_pool_paged_decode_call", 10, 2, call), ("_gmm.5", 12, 7, call),
        ("multiply_reduce_fusion.4", 31, 5, reads), ("add_fusion.2", 36, 3, writes),
        ("_pool_paged_decode_call", 40, 2, call), ("_gmm.5", 42, 7, call),
        ("_kda_chunk_scan.2", 61, 4, call), ("_flash_forward.2", 75, 5, call),
        ("_gmm.7", 81, 4, call),
        ("_kda_chunk_scan.2", 111, 12, call), ("_flash_forward.2", 150, 25, call),
        ("_gmm.7", 176, 10, call),
        ("fusion.9", 20, 1, {"kind": "kOutput"}),
    ]
    trace = device_trace(ops, modules)
    work = {
        "model": model, "page_size": 16,
        "decode_steps": [[900] * 128, [2000] * 100],
        "prefills": [(700, 0), (5000, 0)],
        "output_contexts": [900] * 128 + [2000] * 100, "output_tokens": 228,
    }
    state = S.kda_step_bytes(model, 128) + S.kda_step_bytes(model, 100)
    assert read_metric("kda_decode_roofline", work=work, trace=trace) == pytest.approx(
        100 * state / 819e9 / 0.016
    )
    least = S.least_kda_scan_seconds(model, 700, PEAKS) + S.least_kda_scan_seconds(model, 5000, PEAKS)
    assert read_metric("kda_prefill_roofline", work=work, trace=trace) == pytest.approx(
        100 * least / 0.016
    )
    kv = sum(S.live_kv_bytes(model, lens, 16) for lens in work["decode_steps"])
    assert read_metric("decode_attn_roofline.solar_open2", work=work, trace=trace) == pytest.approx(
        100 * kv / 819e9 / 0.004
    )
    attention = S.prompt_attention_ops(model, 700) + S.prompt_attention_ops(model, 5000)
    assert read_metric("flash_prefill_roofline.solar_open2", work=work, trace=trace) == pytest.approx(
        100 * attention / 197e12 / 0.030
    )
    # the held experts' rows as the device counted them, a dispatch: a
    # decode step that leaves 3 of a layer's 40 without a row reads 37
    # experts' matrices there; a prefill's rows are compute-bound
    decode_counts = [[3] * 37 + [0] * 3] + [[4] * 32 + [0] * 8] * 3
    prefill_counts = [[130] * 40] * 4
    loads = [
        rec("moe_tokens_per_expert", 3, step=1, attrs={"program": "decode_step", "counts": decode_counts}),
        rec("moe_tokens_per_expert", 62, step=2, attrs={"program": "prefill", "counts": prefill_counts}),
    ]
    block = 3 * 4096 * 1280 * 2
    experts = (37 + 3 * 32) * block / 819e9 + 4 * max(
        5200 * 6 * 4096 * 1280 / 197e12, 40 * block / 819e9
    )
    assert read_metric(
        "moe_expert_roofline.solar_open2", loads, work=work, trace=trace
    ) == pytest.approx(100 * experts / 0.028)
    assert read_metric("moe_expert_roofline.solar_open2", [], work=work, trace=trace) is None
    assert read_metric("decode_step_device_ms.solar_open2", work=work, trace=trace) == pytest.approx(20.0)
    ops_total = sum(S.prompt_ops(model, n) for n, _ in work["prefills"]) + sum(
        S.output_token_ops(model, n) for n in work["output_contexts"]
    )
    assert read_metric("serve_mfu.solar_open2", work=work, trace=trace) == pytest.approx(
        100 * ops_total / 197e12 / 1.0
    )
    # no share of a roofline or of the peak passes 100% on this trace
    for name in NEW_METRICS[:7]:
        if name != "decode_step_device_ms.solar_open2":
            assert 0 < read_metric(name, loads, work=work, trace=trace) < 100, name
    # a trace without the kernels (a program that predates them): nothing
    bare = device_trace([("fusion.9", 20, 1, {"kind": "kOutput"})], modules)
    for name in ("kda_decode_roofline", "kda_prefill_roofline", "moe_expert_roofline.solar_open2"):
        assert read_metric(name, loads, work=work, trace=bare) is None
