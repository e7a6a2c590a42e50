"""What PR 37 adds to the benchmark: the cell
``deepseek_v2_lite.prefix16k_closed`` rehearses on the CPU (and judges the
control and the seven planted faults not correct), the configuration's
file against the catalog row, its shapes against hand counts, its new
per-layer metrics on hand-made traces and records (and nothing, without an
error, from a program that lacks what they read: the parent commit), and
the reference's experts (the chosen rows only) against every expert
computed for every token."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkbench import cells, tracereduce

BENCH = cells.load_benchmark()
CELL = "deepseek_v2_lite.prefix16k_closed"
CONFIG = "deepseek_v2_lite_6l"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RUN = os.path.join(cells.ROOT, "benchmarks", "run.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = (
    "mla_decode_roofline", "mla_extend_roofline", "mla_shared_row_share",
    "prefix_hit_share.deepseek_v2", "decode_step_device_ms.deepseek_v2",
    "serve_mfu.deepseek_v2", "moe_expert_roofline.deepseek_v2",
    "device_idle_share.deepseek_v2",
)
CONTROLS = (
    "all_fp8", "mscale_off", "yarn_on_rotation", "k_pe_unrotated",
    "kv_norm_off", "topk_renormalised", "shared_dropped", "prefix_skipped",
)


@pytest.mark.parametrize(
    "trace,control", [(0, False), (1, False), (0, True)],
    ids=["trace0", "trace1", "with-control"],
)
def test_the_cell_rehearses(trace, control):
    """``--rehearse`` drives the entry (the latent projections drawn at
    their gain), the program, the reference and the comparison at the
    tiny sizes, a shared prefix served from the cache; traced, the
    readers a CPU trace can feed find their records; ``--with-control``
    judges the float8 control and the seven planted faults not correct by
    the configuration's own limit."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--rehearse", "--seed",
         str(2**31 + 37), "--seconds", "2", "--trace", str(trace)]
        + (["--with-control"] if control else []),
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["counts"]["tokens_compared"] > 0
    assert last["counts"]["prefix_tokens"] > 0.7 * last["counts"]["prompt_tokens"]
    if trace:
        assert {
            "mla_shared_row_share", "prefix_hit_share.deepseek_v2",
            "serve_mfu.deepseek_v2",
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
    # PR 35's five host-split metrics of the closed loops, then its own
    dispatch = [
        f"{n}.summarize" for n in (
            "decode_enqueue_host_ms", "decode_launch_lag_ms",
            "decode_readback_lag_ms", "idle_unspanned_share", "sched_stall_ms",
        )
    ]
    assert names == dispatch + list(NEW_METRICS)
    for name in names:
        spec, reader = cell.layer_metric(name)
        assert spec["moves"] == "serve_tokens_per_s" and hasattr(reader, "read")
    order = [m["name"] for m in BENCH["per_layer"]]
    assert order[-len(NEW_METRICS):] == list(NEW_METRICS)
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_configuration_is_the_catalog_row_but_its_depth():
    """The top-level keys are the catalog's ``config`` as it stands but
    ``num_hidden_layers`` (the one cut), and the same again under
    ``model``; the program's fields say what the source's keys say."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    row = next(
        json.loads(line) for line in open(CATALOG)
        if json.loads(line)["name"] == "DeepSeek-V2-Lite"
    )
    config = cells.Cell(CELL).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["source"] == row["source_url"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        want = 6 if key == "num_hidden_layers" else value
        assert config[key] == want and config["model"][key] == want, key
    assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == 27
    program, model = config["program"], config["model"]
    rs = model["rope_scaling"]
    for field, value in (
        ("d_model", model["hidden_size"]), ("num_heads", model["num_attention_heads"]),
        ("num_layers", model["num_hidden_layers"]), ("norm_eps", model["rms_norm_eps"]),
        ("mla_kv_rank", model["kv_lora_rank"]), ("mla_nope_dim", model["qk_nope_head_dim"]),
        ("mla_rope_dim", model["qk_rope_head_dim"]), ("mla_v_dim", model["v_head_dim"]),
        ("num_experts", model["n_routed_experts"]),
        ("experts_per_token", model["num_experts_per_tok"]),
        ("expert_dim", model["moe_intermediate_size"]),
        ("shared_expert_dim", model["n_shared_experts"] * model["moe_intermediate_size"]),
        ("dense_layers", model["first_k_dense_replace"]),
        ("mlp_dim", model["intermediate_size"]),
        ("moe_renormalize", model["norm_topk_prob"]),
        ("rope_theta", model["rope_theta"]), ("yarn_factor", rs["factor"]),
        ("yarn_original_len", rs["original_max_position_embeddings"]),
        ("yarn_beta_fast", rs["beta_fast"]), ("yarn_beta_slow", rs["beta_slow"]),
        ("yarn_mscale", rs["mscale"]), ("yarn_mscale_all_dim", rs["mscale_all_dim"]),
        ("tie_embeddings", model["tie_word_embeddings"]),
    ):
        assert program["model." + field] == value, field
    assert program["vocab_size"] == model["vocab_size"] == 102400
    assert program["seq_len"] == program["engine.kv_capacity"] == model["n_positions"]
    mix = cells.Cell(CELL).traffic
    longest = mix["prompt"]["shared_prefix_tokens"] + mix["prompt"]["body"]["max"]
    assert program["engine.seq_buckets"] == [longest] == [17408]
    assert longest + mix["output"]["max"] == model["n_positions"] == 18432
    assert max(program["engine.extend_buckets"]) == mix["prompt"]["body"]["max"]
    assert program["engine.prefix_cache"] is True and program["engine.slots"] == 128
    assert config["entry"] == "serve_gain" and config["expect"] == {
        "decode_attention_flavor": "pallas_mla"
    }


# -- shapes -------------------------------------------------------------------

S = cells.Cell(CELL).shapes_module("deepseek_v2")

#: d 8; 2 heads of nope 3 + rope 2, values 4; a latent of 5; one dense
#: layer of 7 then two expert layers: 6 experts of 2, top 3, 2 shared;
#: vocabulary 11.
SMALL = {
    "hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
    "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 5,
    "intermediate_size": 7, "moe_intermediate_size": 2, "n_routed_experts": 6,
    "num_experts_per_tok": 3, "n_shared_experts": 2, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "vocab_size": 11,
}


def test_shapes_by_hand():
    assert S.layer_counts(SMALL) == (1, 2) and S.latent_row(SMALL) == 7
    # attention: q 8*2*5 = 80, kv_a 8*7 = 56, kv_b 5*2*7 = 70, out 2*4*8 = 64
    assert S.attention_weight_elements(SMALL) == 80 + 56 + 70 + 64
    # the dense MLP 3*8*7; an expert layer's router 8*6 and shared 3*8*2*2
    assert S.dense_weight_elements(SMALL) == 3 * 270 + 168 + 2 * (48 + 96)
    assert S.matmul_ops_per_token(SMALL) == 2 * S.dense_weight_elements(SMALL) + 2 * 3 * 96
    assert S.head_ops(SMALL) == 2 * 8 * 11
    # causal attention, expanded: 2 heads x (5 + 4) a row, 3 layers
    assert S.window_attention_ops(SMALL, 4) == 3 * 18 * (4 * 5)
    assert S.window_attention_ops(SMALL, 4, 2) == 3 * 18 * (20 - 6)
    assert S.output_token_ops(SMALL, 9) == (
        S.matmul_ops_per_token(SMALL) + S.head_ops(SMALL) + 2 * 3 * 9 * 18
    )
    assert S.prompt_ops(SMALL, 4, 2) == (
        2 * S.matmul_ops_per_token(SMALL) + S.window_attention_ops(SMALL, 4, 2)
        + S.head_ops(SMALL)
    )
    # the absorbed kernel over 9 rows: 2 x 9 x 2 heads x (7 + 5), 3 layers
    assert S.decode_kernel_ops(SMALL, 9) == 3 * 2 * 9 * 2 * 12
    assert S.decode_kernel_bytes(SMALL, 9) == 3 * (2 * 7 * 9 + 2 * 2 * 7 + 4 * 2 * 5)
    assert S.live_latent_bytes(SMALL, [3, 9]) == 2 * 3 * 7 * 12
    assert S.expert_bytes(SMALL, 2) == 2 * 48 * 2 and S.expert_bytes(SMALL, 9) == 6 * 48 * 2


def test_shapes_at_the_published_widths_are_the_issues_arithmetic():
    model = cells.Cell(CELL).config["model"]
    assert S.latent_row(model) == 576 and S.layer_counts(model) == (1, 5)
    assert S.attention_weight_elements(model) == pytest.approx(13.77e6, rel=2e-3)
    # the issue: 6.43 GB of weights a step (all 64 experts touched at 768 choices)
    assert S.weight_bytes(model, 128) == pytest.approx(6.43e9, rel=0.02)
    # 14.8 GB of latent rows at 128 slots x 16,725 rows x 6 layers
    assert S.live_latent_bytes(model, [16725] * 128) == pytest.approx(14.8e9, rel=0.01)
    # bandwidth-bound: 34,816 operations against 1,152 bytes a row a layer
    assert S.least_decode_kernel_seconds(model, [16725] * 128, PEAKS) == pytest.approx(
        S.live_latent_bytes(model, [16725] * 128) / 819e9, rel=0.01
    )


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
        ("jit_extend_fn(2)", 100, 30, {}),
    ]
    ops = [
        ("_mla_paged_decode_call", 1, 25, call), ("_gmm.5", 30, 5, call),
        ("_mla_paged_decode_call", 51, 25, call), ("_gmm.5", 80, 5, call),
        ("_mla_extend_call", 101, 6, call), ("_gmm.7", 110, 4, call),
    ]
    trace = device_trace(ops, modules)
    work = {
        "model": model, "page_size": 16,
        "decode_steps": [[16600] * 128, [16700] * 128],
        "prefills": [(16584, 16384)],
        "output_contexts": [16600] * 128 + [16700] * 128, "output_tokens": 256,
    }
    least = sum(
        S.least_decode_kernel_seconds(model, lens, PEAKS) for lens in work["decode_steps"]
    )
    assert read_metric("mla_decode_roofline", work=work, trace=trace) == pytest.approx(
        100 * least / 0.050
    )
    window = S.least_window_kernel_seconds(model, 16584, 16384, PEAKS)
    assert read_metric("mla_extend_roofline", work=work, trace=trace) == pytest.approx(
        100 * window / 0.006
    )
    rows = [
        rec("decode_mla_rows", 1, attrs={"rows_read": 1000, "rows_shared": 900}),
        rec("decode_mla_rows", 51, attrs={"rows_read": 1000, "rows_shared": 980}),
    ]
    assert read_metric("mla_shared_row_share", rows) == pytest.approx(94.0)
    assert read_metric(
        "prefix_hit_share.deepseek_v2", counters={"prompt_tokens": 200, "prefix_tokens": 150}
    ) == pytest.approx(75.0)
    assert read_metric("decode_step_device_ms.deepseek_v2", work=work, trace=trace) == pytest.approx(40.0)
    for name in ("mla_decode_roofline", "mla_extend_roofline", "serve_mfu.deepseek_v2"):
        assert 0 < read_metric(name, work=work, trace=trace) < 100, name
    # the parent commit: no latent kernels, no counters -> nothing, no error
    bare = device_trace([("_gmm.5", 30, 5, call)], modules)
    for name in ("mla_decode_roofline", "mla_extend_roofline"):
        assert read_metric(name, work=work, trace=bare) is None
    assert read_metric("mla_shared_row_share", []) is None


def test_the_references_experts_are_every_expert_masked():
    """The reference computes the chosen rows of each expert only (a
    capacity an expert, the rest masked); the same layer with every expert
    computed for every token and weighted by the routing agrees to 1e-5."""
    ref = cells.Cell(CELL).reference_module()
    rng = np.random.default_rng(0)
    s, d, f, e, k = 40, 16, 8, 6, 2
    w = {
        "RMSNorm_1": {"scale": jnp.ones((d,))},
        "router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
        "experts_gate": jnp.asarray(rng.normal(size=(d, e * f)) / 4, jnp.float32),
        "experts_up": jnp.asarray(rng.normal(size=(d, e * f)) / 4, jnp.float32),
        "experts_down": jnp.asarray(rng.normal(size=(f, e * d)) / 3, jnp.float32),
        "shared_gate": {"kernel": jnp.asarray(rng.normal(size=(d, f)), jnp.float32)},
        "shared_up": {"kernel": jnp.asarray(rng.normal(size=(d, f)), jnp.float32)},
        "shared_down": {"kernel": jnp.asarray(rng.normal(size=(f, d)), jnp.float32)},
    }
    h = jnp.asarray(rng.normal(size=(s, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        u2, top_w, top_e, counts = ref.route(h, w, eps=1e-6, top_k=k, renormalize=False)
        got = ref.expert_half(h, u2, top_w, top_e, w, cap=32, lowp=False, shared=True)
        routing = jnp.sum(jax.nn.one_hot(top_e, e) * top_w[..., None], axis=1)
        want = h + ref._swiglu(
            u2, w["shared_gate"]["kernel"], w["shared_up"]["kernel"],
            w["shared_down"]["kernel"], False,
        )
        for i in range(e):
            out = ref._swiglu(
                u2, w["experts_gate"][:, i * f : (i + 1) * f],
                w["experts_up"][:, i * f : (i + 1) * f],
                w["experts_down"][:, i * d : (i + 1) * d], False,
            )
            want = want + routing[:, i : i + 1] * out
    assert int(np.max(np.asarray(counts))) <= 32
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
