"""What PR 31 adds to the benchmark: the cell ``falcon_h1.chat_decode_closed``
rehearses on the CPU, its shapes against hand counts, its seven per-layer
metrics each on a hand-made trace or record list (and nothing, without an
error, from a program that lacks what they read: the parent commit), the
configuration's file against the catalog row's sizes, the entry that draws
named leaves at a gain, and the reference's comparison on the rehearsal's
model under the published multipliers: sound under float32, and by the
configuration's own limit not under float8 nor either planted fault."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from zkbench import cells, compare, tracereduce
from zkbench.weights import make_weights

BENCH = cells.load_benchmark()
CELL = "falcon_h1.chat_decode_closed"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RUN = os.path.join(cells.ROOT, "benchmarks", "run.py")
NEW_METRICS = (
    "serve_mfu.falcon_h1", "decode_step_device_ms.falcon_h1",
    "decode_attn_roofline.falcon_h1", "flash_prefill_roofline.falcon_h1",
    "ssm_decode_roofline", "ssm_prefill_roofline", "ssm_live_slot_share",
)


@pytest.mark.parametrize(
    "trace,control", [(0, False), (1, False), (0, True)],
    ids=["trace0", "trace1", "with-control"],
)
def test_the_cell_rehearses(trace, control):
    """``--rehearse`` drives the entry, the program, the reference and the
    comparison at the tiny sizes, under the published multipliers and the
    configuration's gain; traced, the readers run and those a CPU trace
    can feed (no device plane, no program names) find their records;
    ``--with-control`` judges the float8 control and both planted faults
    not correct by the configuration's own limit."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--rehearse", "--seed",
         str(2**31 + 31), "--seconds", "2", "--trace", str(trace)]
        + (["--with-control"] if control else []),
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["counts"]["tokens_compared"] > 0
    if trace:
        assert {"ssm_live_slot_share", "serve_mfu.falcon_h1"} <= set(
            last["layer_metrics_read"]
        )
    if control:
        limit = last["compared"]["served_logit_gap"]["limit"]
        assert last["controls"] == {"control_fp8": False}
        for name in ("all_fp8", "state_lost", "conv_lost"):
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
    # appended after everything PR 30 left, in one run (a later PR
    # appends after them in turn, so "the last" is not asserted)
    order = [m["name"] for m in BENCH["per_layer"]]
    first = order.index(NEW_METRICS[0])
    assert order[first : first + 7] == list(NEW_METRICS)
    assert first > order.index("flash_prefill_roofline.mellum2")
    cells_in_order = [w["name"] for w in BENCH["workloads"]]
    assert cells_in_order.index(CELL) > cells_in_order.index("mellum2.mixed_lengths_closed")


def test_configuration_holds_the_catalog_rows_sizes():
    """Every width, head count and state size as published; the depth is
    the one cut; the program's fields say the same as the source's keys."""
    config = cells.Cell(CELL).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "falcon_h1_34b_4l"]
    assert entry["reduced"] == ["num_hidden_layers"]
    published = {
        "hidden_size": 5120, "num_attention_heads": 20, "num_key_value_heads": 4,
        "head_dim": 128, "intermediate_size": 21504, "vocab_size": 261120,
        "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
        "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
        "mamba_chunk_size": 128, "rope_theta": 100000000000, "rms_norm_eps": 1e-05,
    }
    for key, value in published.items():
        assert config[key] == value and config["model"][key] == value
    assert config["num_hidden_layers"] == config["model"]["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 72, "max_position_embeddings": 262144}
    for key in ("source", "deployment", "assumed", "departures", "precision", "limits_from"):
        assert config[key]
    program, model = config["program"], config["model"]
    for field, key in (
        ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
        ("mlp_dim", "intermediate_size"), ("ssm_heads", "mamba_n_heads"),
        ("ssm_head_dim", "mamba_d_head"), ("ssm_state", "mamba_d_state"),
        ("ssm_groups", "mamba_n_groups"),
        ("ssm_chunk", "mamba_chunk_size"), ("norm_eps", "rms_norm_eps"),
        ("rope_theta", "rope_theta"), ("num_layers", "num_hidden_layers"),
        ("embedding_multiplier", "embedding_multiplier"),
        ("lm_head_multiplier", "lm_head_multiplier"),
        ("attention_out_multiplier", "attention_out_multiplier"),
        ("key_multiplier", "key_multiplier"),
        ("ssm_in_multiplier", "ssm_in_multiplier"),
        ("ssm_out_multiplier", "ssm_out_multiplier"),
        ("ssm_multipliers", "ssm_multipliers"), ("mlp_multipliers", "mlp_multipliers"),
    ):
        assert program["model." + field] == model[key], field
    assert program["vocab_size"] == model["vocab_size"]
    assert program["engine.slots"] == 128 and program["engine.prefix_cache"] is False
    assert program["engine.prefill_chunk_tokens"] == 0
    # what the program holds as constants is what the source publishes
    from zookeeper_tpu.models.transformer import SSM_CONV_TAPS

    assert model["mamba_d_conv"] == SSM_CONV_TAPS and model["attention_in_multiplier"] == 1
    # the rehearsal runs the published multipliers, at tiny sizes
    rehearsal = config["rehearsal"]
    assert not [k for group in ("model", "program") for k in rehearsal[group] if "multiplier" in k]
    assert config["entry"] == "serve_gain" and config["weights_gain"] == {"ssm_in/kernel": 16.0}


def test_gained_weights_are_the_same_draw_at_another_scale():
    """``entries/serve_gain.py``: the named leaf times its gain, exactly;
    every other leaf bit for bit; a gain that is no power of two, or that
    names no leaf, is refused."""
    import jax.numpy as jnp

    gain = cells.Cell(CELL).entry_module()
    like = {
        "embed": jax.ShapeDtypeStruct((32, 8), jnp.bfloat16),
        "block0": {
            "ssm_in": {"kernel": jax.ShapeDtypeStruct((8, 24), jnp.bfloat16)},
            "not_ssm_in": {"kernel": jax.ShapeDtypeStruct((8, 24), jnp.bfloat16)},
        },
    }
    plain = make_weights(like, 7)
    got = gain.gained(make_weights, {"ssm_in/kernel": 16.0})(like, 7)
    assert got["block0"]["ssm_in"]["kernel"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got["block0"]["ssm_in"]["kernel"], np.float32),
        16.0 * np.asarray(plain["block0"]["ssm_in"]["kernel"], np.float32),
    )
    for path in (("embed",), ("block0", "not_ssm_in", "kernel")):
        a, b = got, plain
        for key in path:
            a, b = a[key], b[key]
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    with pytest.raises(ValueError, match="power of two"):
        gain.gained(make_weights, {"ssm_in/kernel": 12.0})
    with pytest.raises(ValueError, match="names no leaf"):
        gain.gained(make_weights, {"ssm_inn/kernel": 16.0})(like, 7)


# -- shapes -------------------------------------------------------------------

S = cells.Cell(CELL).shapes_module("falcon_h1")

#: d 8; 4 query heads over 2 key/value heads of 2; MLP 6; a mixer of 2
#: heads of 4 with a state of 3 in 1 group, chunks of 5; 3 layers;
#: vocabulary 11.
SMALL = {
    "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 2, "intermediate_size": 6, "num_hidden_layers": 3,
    "vocab_size": 11, "mamba_n_heads": 2, "mamba_d_head": 4,
    "mamba_d_state": 3, "mamba_n_groups": 1, "mamba_chunk_size": 5,
}


def test_shapes_by_hand():
    assert S.widths(SMALL) == (8, 4)
    # a layer: qkv 2*8*(8+8) = 256, proj 2*8*8 = 128, ssm_in 2*8*(16+6+2)
    # = 384, ssm_out 2*8*8 = 128, MLP 6*8*6 = 288
    assert S.matmul_ops_per_token(SMALL) == 3 * (256 + 128 + 384 + 128 + 288)
    assert S.head_ops(SMALL) == 2 * 8 * 11
    assert S.attention_ops_at(SMALL, 9) == 4 * 8 * 9 * 3
    assert S.prompt_attention_ops(SMALL, 7) == sum(
        S.attention_ops_at(SMALL, c) for c in range(1, 8)
    )
    # the update: 5 h p n = 5*2*4*3 a layer
    assert S.ssm_step_ops(SMALL) == 3 * 120
    # the chunked form, a token: 2 Q n g = 30, a head 2 Q p + 4 p n = 88
    assert S.ssm_scan_ops_per_token(SMALL) == 3 * (30 + 2 * 88)
    assert S.prompt_ops(SMALL, 7) == (
        7 * (S.matmul_ops_per_token(SMALL) + S.ssm_scan_ops_per_token(SMALL))
        + S.prompt_attention_ops(SMALL, 7) + S.head_ops(SMALL)
    )
    assert S.output_token_ops(SMALL, 9) == (
        S.matmul_ops_per_token(SMALL) + S.ssm_step_ops(SMALL)
        + S.head_ops(SMALL) + S.attention_ops_at(SMALL, 9)
    )
    # bytes: a key and a value of 4 elements, 2 bytes, 3 layers
    assert S.kv_bytes_per_token(SMALL) == 48
    assert S.live_kv_bytes(SMALL, [3, 9], 4) == (4 + 12) * 48
    # the state: 2*4*3 float32 a layer, in and out for 5 sequences
    assert S.ssm_state_bytes(SMALL) == 3 * 96
    assert S.ssm_step_bytes(SMALL, 5) == 2 * 5 * 3 * 96
    # the scan of 7 tokens: x 8, B and C 3 each (2 bytes), y 8 (4 bytes)
    assert S.ssm_scan_bytes(SMALL, 7) == 3 * (7 * (16 + 12 + 32) + 96)
    assert S.weight_bytes(SMALL) == (S.matmul_ops_per_token(SMALL) / 2 + 88) * 2


def test_shapes_at_the_published_widths_are_the_issues_arithmetic():
    model = cells.Cell(CELL).config["model"]
    layer = S.matmul_ops_per_token(model) / 2 / 4  # elements a layer
    assert layer == pytest.approx(430.1e6, rel=2e-3)
    assert S.ssm_state_bytes(model) / 4 == 32 * 128 * 256 * 4  # 4.19 MB a layer
    assert S.ssm_step_bytes(model, 128) == pytest.approx(4.30e9, rel=2e-3)
    assert S.kv_bytes_per_token(model) == 8192
    assert S.weight_bytes(model) == pytest.approx(3.44e9 + 2.674e9, rel=2e-3)
    # the chunked scan is under 1% of a prompt token's operations
    assert S.ssm_scan_ops_per_token(model) < 0.01 * S.matmul_ops_per_token(model)
    least = S.least_decode_step_seconds(model, [400] * 128, 16, PEAKS)
    assert least["least_s"] == least["memory_s"] == pytest.approx(0.0133, rel=0.05)


# -- readers ------------------------------------------------------------------


def rec(name, ts_ms, dur_ms=None, *, step=None, attrs=None):
    return {
        "phase": "i" if dur_ms is None else "X", "name": name,
        "ts_ns": int(ts_ms * 1e6), "dur_ns": int((dur_ms or 0) * 1e6),
        "thread_id": 1, "thread_name": "t1", "step": step, "slab": None,
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


def device_trace(ops, modules):
    to_ns = lambda rows: [[n, s * 1e6, d * 1e6, st] for n, s, d, st in rows]
    extract = {
        "devices": {"/device:TPU:0": {"ops": to_ns(ops), "modules": to_ns(modules)}},
        "marks": [["window_start", 0.0], ["window_end", 1e9]], "planes": [],
    }
    return tracereduce.DeviceTrace(
        extract, chips=1, mark_host_ns={"window_start": 0, "window_end": int(1e9)}
    )


def test_live_slot_share_reads_the_engines_counter():
    records = [
        rec("decode_dispatch", 2, 10, step=1),
        rec("decode_ssm_slots", 2, step=1, attrs={"slots_advanced": 128, "slots_live": 128}),
        rec("decode_ssm_slots", 14, step=2, attrs={"slots_advanced": 128, "slots_live": 64}),
    ]
    assert read_metric("ssm_live_slot_share", records) == pytest.approx(75.0)
    # a program without the counter (the parent): nothing, no error
    assert read_metric("ssm_live_slot_share", records[:1]) is None
    assert read_metric("ssm_live_slot_share", []) is None


def test_kernel_readers_find_their_ops_by_name_and_nothing_else():
    cell = cells.Cell(CELL)
    model = cell.config["model"]
    modules = [
        ("jit_decode_fn(1)", 0, 20, {}), ("jit_decode_fn(1)", 30, 20, {}),
        ("jit_prefill_fn(2)", 60, 40, {}), ("jit_prefill_fn(3)", 110, 90, {}),
    ]
    # a Pallas call is named after the jitted function that holds it; an
    # XLA fusion's name says nothing, and the one-token update is found
    # by the shape of the whole state a layer in its HLO text
    call = {"target": "tpu_custom_call", "text": "f32[...] custom-call(...)"}
    update = {"kind": "kLoop", "text": (
        "(f32[128,32,128]{2,1,0:T(8,128)}, f32[128,32,128,256]{3,2,1,0:T(8,128)}) "
        "fusion(%bitcast.3, %S.1), kind=kLoop, calls=%fused_computation"
    )}
    ops = [
        ("multiply_reduce_fusion.4", 1, 8, update), ("_pool_paged_decode_call", 10, 2, call),
        ("multiply_reduce_fusion.4", 31, 8, update), ("_pool_paged_decode_call", 40, 2, call),
        ("_ssm_chunk_scan.2", 61, 4, call), ("_flash_forward.2", 75, 5, call),
        ("_ssm_chunk_scan.2", 111, 12, call), ("_flash_forward.2", 150, 25, call),
        ("fusion.9", 20, 1, {"kind": "kOutput"}),
    ]
    trace = device_trace(ops, modules)
    work = {
        "model": model, "page_size": 16,
        "decode_steps": [[300] * 128, [900] * 100],
        "prefills": [(100, 0), (1000, 0)],
        "output_contexts": [300] * 128 + [900] * 100, "output_tokens": 228,
    }
    state = S.ssm_step_bytes(model, 128) + S.ssm_step_bytes(model, 100)
    assert read_metric("ssm_decode_roofline", work=work, trace=trace) == pytest.approx(
        100 * state / 819e9 / 0.016
    )
    least = S.least_ssm_scan_seconds(model, 100, PEAKS) + S.least_ssm_scan_seconds(model, 1000, PEAKS)
    assert read_metric("ssm_prefill_roofline", work=work, trace=trace) == pytest.approx(
        100 * least / 0.016
    )
    kv = sum(S.live_kv_bytes(model, lens, 16) for lens in work["decode_steps"])
    assert read_metric("decode_attn_roofline.falcon_h1", work=work, trace=trace) == pytest.approx(
        100 * kv / 819e9 / 0.004
    )
    attention = S.prompt_attention_ops(model, 100) + S.prompt_attention_ops(model, 1000)
    assert read_metric("flash_prefill_roofline.falcon_h1", work=work, trace=trace) == pytest.approx(
        100 * attention / 197e12 / 0.030
    )
    assert read_metric("decode_step_device_ms.falcon_h1", work=work, trace=trace) == pytest.approx(20.0)
    ops_total = sum(S.prompt_ops(model, n) for n, _ in work["prefills"]) + sum(
        S.output_token_ops(model, n) for n in work["output_contexts"]
    )
    assert read_metric("serve_mfu.falcon_h1", work=work, trace=trace) == pytest.approx(
        100 * ops_total / 197e12 / 1.0
    )
    # a trace without the kernels (a program that predates them): nothing
    bare = device_trace([("fusion.9", 20, 1, {"kind": "kOutput"})], modules)
    for name in ("ssm_decode_roofline", "ssm_prefill_roofline"):
        assert read_metric(name, work=work, trace=bare) is None


# -- the reference's comparison ----------------------------------------------


def test_reference_comparison_is_sound_in_float32_and_not_under_the_controls():
    """The tiny model's own greedy continuation, by the reference: every
    served token is the reference's choice (gap 0); the float8 control
    and both planted faults are read at the same positions, each by its
    own name, and the run's number is the smallest of them."""
    import jax.numpy as jnp

    cell = cells.Cell(CELL)
    reference = cell.reference_module()
    config = cells.merged(cell.config, cell.config["rehearsal"])
    model = config["model"]
    d, layers, vocab = int(model["hidden_size"]), int(model["num_hidden_layers"]), int(model["vocab_size"])
    h, p, n, g = (int(model[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    q = int(model["num_attention_heads"]) * int(model["head_dim"])
    kv = int(model["num_key_value_heads"]) * int(model["head_dim"])
    f, inner, ch = int(model["intermediate_size"]), h * p, h * p + 2 * g * n
    like = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    block = {
        "RMSNorm_0": {"scale": like(d)}, "RMSNorm_1": {"scale": like(d)},
        "qkv": {"kernel": like(d, q + 2 * kv)}, "proj": {"kernel": like(q, d)},
        "gate": {"kernel": like(d, f)}, "up": {"kernel": like(d, f)},
        "down": {"kernel": like(f, d)},
        "ssm_in": {"kernel": like(d, 2 * inner + 2 * g * n + h)},
        "ssm_out": {"kernel": like(inner, d)}, "ssm_norm": {"scale": like(inner)},
        "ssm_conv_kernel": like(4, ch), "ssm_conv_bias": like(ch),
        "A_log": like(h), "D": like(h), "dt_bias": like(h),
    }
    tree = {"embed": like(vocab, d), "head": like(d, vocab), "RMSNorm_0": {"scale": like(d)}}
    tree.update({f"block{i}": block for i in range(layers)})
    # the benchmark's own weights as the cell's entry draws them: under
    # the published multipliers, with the configuration's gain in front
    # of the mixer (without it the two faults move no token)
    params = cell.entry_module().gained(make_weights, config["weights_gain"])(tree, 31)
    limit = config["limits"]["served_logit_gap"]

    def greedy(prompt, steps):
        tokens = list(prompt)
        for _ in range(steps):
            padded = np.zeros(128, np.int32)
            padded[: len(tokens)] = tokens
            with jax.default_matmul_precision("highest"):
                logits = reference.forward(params, model, jnp.asarray(padded))
            tokens.append(int(jnp.argmax(logits[len(tokens) - 1])))
        return np.asarray(tokens[len(prompt):], np.int32)

    rng = np.random.default_rng(3)
    sequences = []
    for length in (9, 21):
        prompt = rng.integers(0, vocab, size=length).astype(np.int32)
        sequences.append({"prompt": prompt, "served": greedy(prompt, 10)})
    with jax.default_matmul_precision("highest"):
        found = reference.served_token_gaps(params, model, sequences, 128, lowp_control=True)
    assert found["tokens_compared"] == 20 and found["widest_gap"] < 1e-5
    names = ["all_fp8", "state_lost", "conv_lost"]
    gaps = [found[f"control_{name}_widest_gap"] for name in names]
    assert all(gap > limit for gap in gaps), dict(zip(names, gaps))
    assert found["control_widest_gap"] == min(gaps)
    ok, _ = compare.judge({"served_logit_gap": found["widest_gap"]}, config["limits"])
    bad, _ = compare.judge({"served_logit_gap": found["control_widest_gap"]}, config["limits"])
    assert ok and not bad
    # and the gain is what lets the comparison see the mixer: the plain
    # draw under the published multipliers hides a lost state
    blind = reference.served_token_gaps(make_weights(tree, 31), model, sequences, 128, lowp_control=True)
    assert blind["control_state_lost_widest_gap"] <= limit
