"""What the LongCat-Flash configuration adds to the benchmark: the cell
``longcat_flash.chat4k_decode_closed`` rehearses on the CPU (and reads the
control and the eight planted faults), the benchmark lists the
cell and its metrics (present, not an exact list), the configuration's file
against the catalog row, its shapes against hand counts, the new reader
and the reused ones on hand-made traces and records (and nothing, without
an error, from a program that lacks what they read: the parent commit)."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from zkbench import cells, tracereduce

BENCH = cells.load_benchmark()
CELL = "longcat_flash.chat4k_decode_closed"
CONFIG = "longcat_flash_ep32_4l"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RUN = os.path.join(cells.ROOT, "benchmarks", "run.py")
# A JSON-lines catalog of published model configurations, one row a model.
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")
NEW_METRICS = (
    "decode_step_device_ms.longcat_flash", "serve_mfu.longcat_flash",
    "mla_cascade_decode_roofline.longcat_flash",
    "mla_extend_roofline.longcat_flash", "moe_expert_roofline.longcat_flash",
    "moe_held_choice_share.longcat_flash", "moe_zero_choice_share",
    "device_idle_share.longcat_flash",
)
SHARED_METRICS = (
    "decode_enqueue_host_ms.summarize", "decode_launch_lag_ms.summarize",
    "decode_readback_lag_ms.summarize", "idle_unspanned_share.summarize",
    "sched_stall_ms.summarize", "prefill_device_ms_per_ktoken",
)
CONTROLS = (
    "all_fp8", "zero_dropped", "bias_ignored", "topk_renormalised",
    "scale_off", "moe_from_second", "kv_scale_off", "q_scale_off", "q_norm_off",
)


@pytest.mark.parametrize(
    "trace,control", [(1, False), (0, True)], ids=["trace1", "with-control"],
)
def test_the_cell_rehearses(trace, control):
    """``--rehearse`` drives the entry (the router, its bias and the
    experts' output drawn at their gains), the program, the reference and
    the comparison at the tiny sizes; traced, the readers a CPU trace can
    feed find their records; ``--with-control`` reads the float8 control
    and the eight planted faults, which move the served tokens (all but
    the two the tiny router hardly feels past the configuration's own
    limit)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--rehearse", "--seed",
         str(2**31 + 43), "--seconds", "2", "--trace", str(trace)]
        + (["--with-control"] if control else []),
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["counts"]["tokens_compared"] > 0
    assert last["counts"]["prefix_tokens"] == 0
    if trace:
        assert {
            "moe_zero_choice_share", "moe_held_choice_share.longcat_flash",
            "serve_mfu.longcat_flash", "device_idle_share.longcat_flash",
        } <= set(last["layer_metrics_read"])
    if control:
        limit = last["compared"]["served_logit_gap"]["limit"]
        assert set(last["controls"]) == {"control_fp8"}
        gaps = {
            name: float(re.findall(rf"'control_{name}_widest_gap': ([0-9.e+-]+)", done.stdout)[0])
            for name in CONTROLS
        }
        # at the tiny size the router has 24 columns and picks 4, so the
        # bias and the renormalisation move fewer served tokens than at
        # the published widths (PERF.md section 2): each moves some
        for name, gap in gaps.items():
            assert gap > (0 if name in ("bias_ignored", "topk_renormalised") else limit), (name, gap)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    cell = cells.Cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= names
    assert "mla_decode_roofline" not in names  # its kernel is gone
    for name in names:
        spec, reader = cell.layer_metric(name)
        assert spec["moves"] == "serve_tokens_per_s" and hasattr(reader, "read")
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] == CONFIG and entry["traffic"] == "chat4k_decode_closed"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] in {
            m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW_METRICS
        }


def test_configuration_is_the_catalog_row_but_its_cuts():
    """The top-level keys are the catalog's ``config`` but the three cuts
    (4 layers, 16 held experts, an eighth of the vocabulary), and the same
    again under ``model``; the program's fields say what the source's keys
    say; the published counts and the deployment stand beside them."""
    if not os.path.exists(CATALOG):
        pytest.skip("MODEL_CONFIG_CATALOG names no catalog file")
    row = next(
        json.loads(line) for line in open(CATALOG)
        if json.loads(line)["name"] == "LongCat-Flash-Chat"
    )
    config = cells.Cell(CELL).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["source"] == row["source_url"] == config["source"]
    cuts = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert entry["reduced"] == list(cuts)
    for key, value in row["config"].items():
        want = cuts.get(key, value)
        assert config[key] == want and config["model"][key] == want, key
    assert config["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "zero_expert_num": 256,
        "vocab_size": 131072, "max_position_embeddings": 131072,
    }
    assert "32 chips" in config["deployment"]
    for key in ("mla_scales", "rope_pairing", "tie_word_embeddings", "weights"):
        assert key in config["assumed"], key
    program, model = config["program"], config["model"]
    for field, value in (
        ("d_model", model["hidden_size"]), ("num_heads", model["num_attention_heads"]),
        ("num_layers", 2 * model["num_layers"]), ("norm_eps", model["rms_norm_eps"]),
        ("mla_kv_rank", model["kv_lora_rank"]), ("mla_q_rank", model["q_lora_rank"]),
        ("mla_nope_dim", model["qk_nope_head_dim"]),
        ("mla_rope_dim", model["qk_rope_head_dim"]), ("mla_v_dim", model["v_head_dim"]),
        ("mla_lora_scales", model["mla_scale_q_lora"] and model["mla_scale_kv_lora"]),
        ("num_experts", model["router_experts"]),
        ("moe_zero_experts", model["zero_expert_num"]),
        ("experts_per_token", model["moe_topk"]),
        ("expert_dim", model["expert_ffn_hidden_size"]),
        ("held_experts", model["held_experts"]), ("mlp_dim", model["ffn_hidden_size"]),
        ("moe_scale", model["routed_scaling_factor"]), ("rope_theta", model["rope_theta"]),
    ):
        assert program["model." + field] == value, field
    assert model["held_experts"] == [0, model["n_routed_experts"]]
    assert program["model.moe_shortcut"] and program["model.moe_router_bias"]
    assert program["model.moe_renormalize"] is False
    assert program["vocab_size"] == model["vocab_size"]
    mix = cells.Cell(CELL).traffic
    assert mix["prompt"]["shared_prefix_tokens"] == 0
    longest = mix["prompt"]["body"]["max"]
    assert max(program["engine.seq_buckets"]) == longest == 4096
    assert longest + mix["output"]["max"] == model["n_positions"] == program["seq_len"]
    assert program["engine.kv_capacity"] == model["n_positions"]
    assert program["engine.prefix_cache"] is False and program["engine.slots"] == 128
    assert mix["arrivals"]["clients"] == 2 * program["engine.slots"]
    assert config["entry"] == "serve_gain" and config["expect"] == {
        "decode_attention_flavor": "pallas_mla"
    }


# -- shapes -------------------------------------------------------------------

S = cells.Cell(CELL).shapes_module("longcat_flash")

#: d 8; 2 heads of nope 3 + rope 2, values 4; a query rank of 6, a latent
#: of 5; one LongCat layer (2 sub-layers) with dense SwiGLUs of 7, a router
#: of 6 real and 3 identity columns, top 3, experts of 2, 2 held;
#: vocabulary 11.
SMALL = {
    "hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
    "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 5, "q_lora_rank": 6,
    "ffn_hidden_size": 7, "expert_ffn_hidden_size": 2, "router_experts": 6,
    "zero_expert_num": 3, "held_experts": [0, 2], "moe_topk": 3,
    "num_layers": 1, "vocab_size": 11,
}


def test_shapes_by_hand():
    assert S.sublayers(SMALL) == 2 and S.latent_row(SMALL) == 7
    assert S.router_width(SMALL) == 9
    # attention: q_a 8*6, q_b 6*2*5, kv_a 8*7, kv_b 5*2*7, out 2*4*8
    assert S.attention_weight_elements(SMALL) == 48 + 60 + 56 + 70 + 64
    # two sub-layers' attention and SwiGLU (3*8*7), one router 8*9
    assert S.dense_weight_elements(SMALL) == 2 * (298 + 168) + 72
    # a held choice 6*8*2; 3 choices x 2 held of 9 columns
    assert S.expert_ops_per_choice(SMALL) == 96
    assert S.held_choices_per_token(SMALL) == pytest.approx(3 * 2 / 9)
    assert S.matmul_ops_per_token(SMALL) == pytest.approx(
        2 * S.dense_weight_elements(SMALL) + (6 / 9) * 96
    )
    assert S.head_ops(SMALL) == 2 * 8 * 11
    # causal attention, expanded: 2 heads x (5 + 4) a row, 2 sub-layers
    assert S.window_attention_ops(SMALL, 4) == 2 * 18 * (4 * 5)
    assert S.output_token_ops(SMALL, 9) == pytest.approx(
        S.matmul_ops_per_token(SMALL) + S.head_ops(SMALL) + 2 * 2 * 9 * 18
    )
    assert S.prompt_ops(SMALL, 4) == pytest.approx(
        4 * S.matmul_ops_per_token(SMALL) + S.window_attention_ops(SMALL, 4) + S.head_ops(SMALL)
    )
    # the absorbed kernel over 9 rows: 2 x 9 x 2 heads x (7 + 5), 2 sub-layers
    assert S.decode_kernel_ops(SMALL, 9) == 2 * 2 * 9 * 2 * 12
    assert S.decode_kernel_bytes(SMALL, 9) == 2 * (2 * 7 * 9 + 2 * 2 * 7 + 4 * 2 * 5)
    assert S.live_latent_bytes(SMALL, [3, 9]) == 2 * 2 * 7 * 12
    # held expert bytes: at most the 2 held, 3*8*2 elements each
    assert S.expert_bytes(SMALL, 1) == 48 * 2 and S.expert_bytes(SMALL, 9) == 2 * 48 * 2


def test_shapes_at_the_published_widths_by_hand():
    model = cells.Cell(CELL).config["model"]
    assert S.latent_row(model) == 576 and S.sublayers(model) == 8
    assert S.router_width(model) == 768
    # a sub-layer's attention 90.6M; a layer outside its experts 638.9M
    assert S.attention_weight_elements(model) == pytest.approx(90.57e6, rel=1e-3)
    assert S.dense_weight_elements(model) / 4 == pytest.approx(638.9e6, rel=1e-3)
    assert S.expert_elements(model) == pytest.approx(37.75e6, rel=1e-3)
    # 5.17B parameters, 10.35 GB in bfloat16
    assert 2 * S.parameter_elements(model) == pytest.approx(10.35e9, rel=2e-3)
    # 12 x 16 / 768 held choices a token a layer
    assert S.held_choices_per_token(model) == 0.25
    # 10,240 B of latent rows a token (8 sub-layers x 640 lanes x 2 B) in
    # the pool; 9,216 B of them are values
    assert S.live_latent_bytes(model, [1]) == 8 * 576 * 2


# -- readers ------------------------------------------------------------------


def rec(name, ts_ms, *, attrs=None):
    return {
        "phase": "i", "name": name, "ts_ns": int(ts_ms * 1e6), "dur_ns": 0,
        "thread_id": 1, "thread_name": "t1", "step": None, "slab": None,
        "attrs": attrs, "rid": None,
    }


def read_metric(name, records=(), work=None, trace=None, counters=None):
    cell = cells.Cell(CELL)
    spec, reader = cell.layer_metric(name)
    return reader.read({
        "spans": list(records), "window_host_ns": (0, int(1e9)), "spec": spec,
        "cell": cell, "counters": counters or {}, "work": work or {},
        "trace": trace, "peaks": PEAKS,
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


def test_the_readers_find_their_kernels_and_counters():
    model = cells.Cell(CELL).config["model"]
    call = {"target": "tpu_custom_call", "text": "f32[...] custom-call(...)"}
    modules = [
        ("jit_decode_fn(1)", 0, 40, {}), ("jit_decode_fn(1)", 50, 40, {}),
        ("jit_prefill_fn(2)", 100, 30, {}),
    ]
    ops = [
        ("_mla_cascade_decode_call", 1, 5, call), ("_gmm.5", 30, 5, call),
        ("_mla_cascade_decode_call", 51, 5, call), ("_gmm.5", 80, 5, call),
        ("_mla_extend_call", 101, 6, call), ("_gmm.7", 110, 4, call),
    ]
    trace = device_trace(ops, modules)
    steps = [[1600] * 128, [1601] * 128]
    work = {
        "model": model, "page_size": 64, "decode_steps": steps,
        "prefills": [(2000, 0)], "output_contexts": steps[0] + steps[1],
        "output_tokens": 256,
    }
    fetched = [
        rec("decode_mla_rows", t, attrs={"rows_read": n, "rows_shared": 0,
                                         "rows_cascaded": 0, "rows_fetched": n})
        for t, n in ((1, 128 * 1601 * 8), (51, 128 * 1602 * 8))
    ]
    got = read_metric("mla_cascade_decode_roofline.longcat_flash", fetched, work, trace)
    ops_s = sum(S.decode_kernel_ops(model, c) for s in steps for c in s) / 197e12
    bytes_s = (
        256 * S.decode_kernel_bytes(model, 0)
        + 2 * 576 * sum(r["attrs"]["rows_fetched"] for r in fetched)
    ) / 819e9
    assert got == pytest.approx(100 * max(ops_s, bytes_s) / 0.010)
    window = S.least_window_kernel_seconds(model, 2000, 0, PEAKS)
    assert read_metric(
        "mla_extend_roofline.longcat_flash", work=work, trace=trace
    ) == pytest.approx(100 * window / 0.006)
    load = [
        rec("moe_tokens_per_expert", 2, attrs={"counts": [[2] * 14 + [0, 0]] * 4}),
    ]
    least = 4 * max(
        28 * S.expert_ops_per_choice(model) / 197e12, S.expert_bytes(model, 14) / 819e9
    )
    assert read_metric(
        "moe_expert_roofline.longcat_flash", load, work, trace
    ) == pytest.approx(100 * least / 0.014)
    choices = [
        rec("moe_zero_choices", 1, attrs={"choices_zero": 2000, "choices_routed": 6144}),
        rec("moe_zero_choices", 51, attrs={"choices_zero": 2096, "choices_routed": 6144}),
        rec("moe_held_choices", 1, attrs={"choices_held": 128, "choices_routed": 6144}),
    ]
    assert read_metric("moe_zero_choice_share", choices) == pytest.approx(100 / 3)
    assert read_metric(
        "moe_held_choice_share.longcat_flash", choices
    ) == pytest.approx(100 * 128 / 6144)
    assert read_metric(
        "decode_step_device_ms.longcat_flash", work=work, trace=trace
    ) == pytest.approx(40.0)
    for name in (
        "mla_cascade_decode_roofline.longcat_flash", "mla_extend_roofline.longcat_flash",
        "moe_expert_roofline.longcat_flash", "serve_mfu.longcat_flash",
    ):
        value = read_metric(name, fetched + load, work, trace)
        assert value is not None and 0 < value < 100, (name, value)
    # the parent commit: no identity counter -> nothing, no error
    assert read_metric("moe_zero_choice_share", choices[2:]) is None
    assert read_metric("moe_zero_choice_share", []) is None


def test_the_references_experts_are_every_expert_masked():
    """The reference computes the chosen rows of each held expert only (a
    capacity an expert, the rest masked) and the identity part for its
    chosen rows; the same MoE with every held expert computed for every
    token and weighted by the routing, the absent experts and the identity
    columns apart, agrees to 1e-5."""
    import jax
    import jax.numpy as jnp

    ref = cells.Cell(CELL).reference_module()
    rng = np.random.default_rng(0)
    s, d, f, real, zero, k, first, count = 40, 16, 8, 10, 5, 4, 3, 4
    w = {
        "router": jnp.asarray(rng.normal(size=(d, real + zero)), jnp.float32),
        "router_bias": jnp.asarray(rng.normal(size=(real + zero,)) * 0.05, jnp.float32),
        "experts_gate": jnp.asarray(rng.normal(size=(d, count * f)) / 4, jnp.float32),
        "experts_up": jnp.asarray(rng.normal(size=(d, count * f)) / 4, jnp.float32),
        "experts_down": jnp.asarray(rng.normal(size=(f, count * d)) / 3, jnp.float32),
    }
    u = jnp.asarray(rng.normal(size=(s, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        top_w, top_e, counts = ref.route(
            u, w, top_k=k, renormalize=False, bias=True, scale=6.0, real=real
        )
        got = ref.experts(
            u, top_w, top_e, w, cap=256, lowp=False, held=(first, count),
            real=real, identity=True,
        )
        routing = jnp.sum(jax.nn.one_hot(top_e, real + zero) * top_w[..., None], axis=1)
        want = jnp.sum(routing[:, real:], axis=1, keepdims=True) * u
        for i in range(count):
            out = ref._swiglu(
                u, w["experts_gate"][:, i * f : (i + 1) * f],
                w["experts_up"][:, i * f : (i + 1) * f],
                w["experts_down"][:, i * d : (i + 1) * d], False,
            )
            want = want + routing[:, first + i : first + i + 1] * out
    assert int(np.sum(np.asarray(counts)[real:])) > 0
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
