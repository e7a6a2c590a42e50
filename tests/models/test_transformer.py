"""TransformerLM: the long-context model family through the SAME
Model/configure/train-step machinery as the CNN zoo."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.models import TransformerLM
from zookeeper_tpu.training import TrainState, make_train_step


def make_model(extra=None, seq=32, vocab=61):
    m = TransformerLM()
    configure(
        m,
        {
            "num_layers": 2,
            "d_model": 64,
            "num_heads": 2,
            "max_seq_len": 64,
            **(extra or {}),
        },
        name="m",
    )
    module = m.build((seq,), num_classes=vocab)
    params, state = m.initialize(module, (seq,))
    return m, module, params, state


def corpus_windows(seq=32, vocab=61, n=8, seed=0):
    """``(tokens, next_tokens)`` int32 windows over ONE fixed periodic
    corpus (the 7-token pattern is seed-independent; ``seed`` only
    varies which windows are sampled) — a memorizable task a 2-layer
    model learns in tens of steps. The single source of truth for the
    file's LM training data."""
    base = np.random.default_rng(42).integers(0, vocab, 7)
    stream = np.tile(base, max(seq, 64))
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, len(stream) - seq - 1, n)
    toks = np.stack([stream[s : s + seq] for s in starts]).astype(np.int32)
    nxt = np.stack(
        [stream[s + 1 : s + seq + 1] for s in starts]
    ).astype(np.int32)
    return toks, nxt


def lm_batch(seq=32, vocab=61, batch=8, seed=0):
    toks, nxt = corpus_windows(seq=seq, vocab=vocab, n=batch, seed=seed)
    return {
        "input": jnp.asarray(toks),
        "target": jnp.asarray(nxt),
    }


def test_forward_shapes_and_fp32_logits():
    _, module, params, state = make_model()
    batch = lm_batch()
    logits = module.apply(
        {"params": params, **state}, batch["input"], training=False
    )
    assert logits.shape == (8, 32, 61)
    assert logits.dtype == jnp.float32


@pytest.mark.parametrize("extra", [
    {},
    {"mlp": "moe", "num_experts": 4, "experts_per_token": 2,
     "expert_dim": 16, "tie_embeddings": False, "positions": "rope"},
], ids=["gpt2", "experts_untied_head"])
def test_apply_checks_parameter_shapes_without_tracing_initializers(
    extra, monkeypatch
):
    """flax re-evaluates every parameter's initializer abstractly on
    every apply to compare shapes (``Scope.param``); this model compares
    the shape it already holds (``_param``): no ``eval_shape`` a trace,
    and a leaf of another shape is still refused by flax's own error."""
    from flax.errors import ScopeParamShapeError

    _, module, params, _ = make_model({"attention": "dense", **extra})
    tokens = lm_batch()["input"]
    calls = []
    eval_shape = jax.eval_shape
    monkeypatch.setattr(
        jax, "eval_shape",
        lambda *a, **k: calls.append(1) or eval_shape(*a, **k),
    )
    jax.jit(lambda p: module.apply({"params": p}, tokens)).lower(params)
    assert not calls
    monkeypatch.undo()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        wrong = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros((x.shape[0] + 1, *x.shape[1:]), x.dtype)
            if p == path else x,
            params,
        )
        with pytest.raises(ScopeParamShapeError):
            module.apply({"params": wrong}, tokens)


UNTIED = {"tie_embeddings": False}


@pytest.mark.parametrize("extra", [{}, UNTIED], ids=["tied", "untied"])
@pytest.mark.parametrize("width", [96, 160])
def test_held_tables_with_padded_rows_give_the_bound_trees_logits(
    width, extra
):
    """A serving tree holds the tables ``_embed`` gathers from with rows
    of whole 128-lane tiles (96 -> 128, 160 -> 256; zeros that are
    sliced off before anything reads them) and, where the head is tied,
    the table as bound a second time as ``tied_head``: every method's
    logits are bit for bit the bound tree's. ``init`` never makes the
    second home, and training never sees either."""
    from zookeeper_tpu.serving.decode import allocate_page_pool

    _, module, params, _ = make_model(
        {"attention": "dense", "d_model": width, "num_heads": 4, **extra}
    )
    tied = not extra
    assert "tied_head" not in params
    assert params["embed"].shape == (61, width)
    rows = module.table_row_width
    assert rows == -(-width // 128) * 128
    held = module.serving_variables({"params": params})["params"]
    assert held["embed"].shape == (61, rows)
    assert held["pos"].shape == (64, rows)
    assert ("tied_head" in held) == tied
    if tied:
        assert held["tied_head"] is params["embed"]
    for name in ("embed", "pos"):
        np.testing.assert_array_equal(
            np.asarray(held[name][:, :width]), np.asarray(params[name])
        )
        assert not np.asarray(held[name][:, width:]).any()

    tokens = lm_batch()["input"][:2, :16]
    lengths = jnp.asarray([16, 11], jnp.int32)
    cache = allocate_page_pool(
        module.num_layers, 16, 4, module.kv_heads, module.head_dim,
        jnp.float32,
    )
    table = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)

    @jax.jit
    def run(p):
        v = {"params": p}
        whole = module.apply(v, tokens)
        first, _ = module.apply(v, tokens, lengths, method="prefill")
        wide, filled = module.apply(
            v, tokens[:, :8], jnp.zeros(2, jnp.int32), cache, table,
            method="decode_verify_paged",
        )
        one, _ = module.apply(
            v, tokens[:, 8], jnp.full(2, 8, jnp.int32), filled, table,
            method="decode_step_paged",
        )
        return whole, first, wide, one

    for a, b in zip(run(params), run(held)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("extra", [{}, UNTIED], ids=["tied", "untied"])
def test_tables_of_whole_tiles_are_held_as_bound(extra):
    """At a width of whole tiles the serving tree's tables ARE the bound
    arrays, no second home is named, and ``_embed``'s slice of the first
    ``d_model`` columns traces to nothing: the forward lowers to the
    text it lowered to before the slice was there (a bare gather, an
    add, a convert)."""
    _, module, params, _ = make_model(
        {"attention": "dense", "d_model": 128, "num_heads": 4, **extra}
    )
    given = {"params": params}
    assert module.serving_tree(given) is given
    held = module.serving_variables(given)
    assert jax.tree.structure(held) == jax.tree.structure(given)
    assert held["params"]["embed"] is params["embed"]
    assert held["params"]["pos"] is params["pos"]
    tokens = lm_batch()["input"]

    def embed(p, positions=None):
        return module.apply(
            {"params": p}, tokens, positions, method="_embed"
        )

    def plain(p, positions=None):
        x = p["embed"][tokens]
        if positions is None:
            x = x + p["pos"][None, : tokens.shape[1]]
        else:
            x = x + p["pos"][jnp.clip(positions, 0, 63)]
        return x.astype(module.dtype)

    def ops_of(fn, *args):
        text = jax.jit(fn).lower(params, *args).as_text()
        return [
            line.split("=", 1)[1].split()[0]
            for line in text.splitlines() if " = stablehlo." in line
        ]

    positions = jnp.zeros(tokens.shape, jnp.int32)
    assert ops_of(embed) == ops_of(plain)
    assert ops_of(embed, positions) == ops_of(plain, positions)


def test_param_refuses_a_table_of_any_third_shape():
    """``embed`` and ``pos`` are accepted as bound or with rows of whole
    tiles and in no other shape; a padded ``embed`` only where the head
    has a home of its own (``tied_head``, itself held to the bound
    shape, or an untied ``head``); flax's own error either way."""
    from flax.errors import ScopeParamShapeError

    tokens = lm_batch()["input"]

    def padded(table, columns):
        return jnp.pad(table, ((0, 0), (0, columns - table.shape[1])))

    for extra in ({}, UNTIED):
        _, module, params, _ = make_model(
            {"attention": "dense", "d_model": 96, "num_heads": 4, **extra}
        )
        held = module.serving_variables({"params": params})["params"]
        module.apply({"params": held}, tokens)  # the held tree is fine
        for name in ("embed", "pos"):
            for columns in (97, 256):
                wrong = {**held, name: padded(params[name], columns)}
                with pytest.raises(ScopeParamShapeError):
                    module.apply({"params": wrong}, tokens)
            taller = {**held, name: jnp.pad(held[name], ((0, 1), (0, 0)))}
            with pytest.raises(ScopeParamShapeError):
                module.apply({"params": taller}, tokens)
        if not extra:
            no_home = {k: v for k, v in held.items() if k != "tied_head"}
            with pytest.raises(ScopeParamShapeError):
                module.apply({"params": no_home}, tokens)
            wrong_home = {**held, "tied_head": held["embed"]}
            with pytest.raises(ScopeParamShapeError):
                module.apply({"params": wrong_home}, tokens)
            # beside a bound table the second home is not needed, but
            # is the same table if it is there
            module.apply({"params": {**params, "tied_head": params["embed"]}}, tokens)


def test_flash_and_dense_attention_agree():
    """The model-level parity check: identical params, the two
    attention tiers produce the same logits (flash is exact; fp32 on
    the CPU CI path, so the tolerance is tight — loosen only for a
    bf16 variant)."""
    m, module_f, params, state = make_model({"attention": "flash"})
    m2, module_d, _, _ = make_model({"attention": "dense"})
    batch = lm_batch()
    lf = module_f.apply(
        {"params": params, **state}, batch["input"], training=False
    )
    ld = module_d.apply(
        {"params": params, **state}, batch["input"], training=False
    )
    np.testing.assert_allclose(
        np.asarray(lf), np.asarray(ld), atol=1e-4, rtol=1e-4
    )


@pytest.mark.slow
def test_lm_learns_next_token():
    """The existing train step works unchanged for LM batches (the CE
    and accuracy broadcast over positions): loss on a periodic corpus
    drops sharply and accuracy rises far above chance."""
    _, module, params, state = make_model()
    ts = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=state,
        tx=optax.adam(3e-3),
    )
    step = jax.jit(make_train_step())
    first = None
    for i in range(60):
        ts, metrics = step(ts, lm_batch(seed=i))
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    acc = float(metrics["accuracy"])
    assert last < first * 0.5, (first, last)
    assert acc > 0.5, acc  # chance is ~1/61


def _sharded_parity_run(module, params, state, batch, partitioner):
    """One train step single-device and under ``partitioner``; returns
    ``(sharded_state, sharded_metrics)`` after asserting the loss and
    every updated param match the single-device run (1e-5, the
    cross-device-reduction-order tolerance)."""
    make_ts = lambda: TrainState.create(
        apply_fn=module.apply,
        params=jax.tree.map(jnp.copy, params),
        model_state=state,
        tx=optax.adam(1e-3),
    )
    ts1, m1 = jax.jit(make_train_step())(make_ts(), batch)

    ts2 = partitioner.shard_state(make_ts())
    step = partitioner.compile_step(make_train_step(), ts2)
    ts2, m2 = step(
        ts2, jax.device_put(batch, partitioner.batch_sharding())
    )
    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree.leaves(jax.device_get(ts1.params)),
        jax.tree.leaves(jax.device_get(ts2.params)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    return ts2, m2


@pytest.mark.slow
def test_dp_sharded_step_matches_single_device():
    """The LM trains under the same DataParallelPartitioner as the CNN
    zoo — one step on the 8-device mesh is bit-comparable to the
    single-device step. (8-virtual-device parity tail: certification
    tier — the fast tier keeps the single-device flash/dense parity
    check, `test_flash_and_dense_attention_agree`.)"""
    from zookeeper_tpu.parallel import DataParallelPartitioner

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    _, module, params, state = make_model()
    part = DataParallelPartitioner()
    configure(part, {}, name="p")
    part.setup()
    _sharded_parity_run(module, params, state, lm_batch(), part)


def test_build_rejections():
    m = TransformerLM()
    configure(m, {"num_layers": 1, "d_model": 30, "num_heads": 4}, name="m")
    with pytest.raises(ValueError, match="divisible"):
        m.build((32,), num_classes=10)

    m2 = TransformerLM()
    configure(m2, {"max_seq_len": 16}, name="m2")
    with pytest.raises(ValueError, match="max_seq_len"):
        m2.build((32,), num_classes=10)

    m3 = TransformerLM()
    configure(m3, {"attention": "sparse"}, name="m3")
    with pytest.raises(ValueError, match="attention"):
        m3.build((32,), num_classes=10)

    m4 = TransformerLM()
    configure(m4, {}, name="m4")
    with pytest.raises(ValueError, match="seq_len"):
        m4.build((32, 32, 3), num_classes=10)


@pytest.mark.slow
def test_sequence_parallel_lm_train_step_matches_single_device():
    """(8-virtual-device parity tail, certification tier — the dryrun's
    sp-lm leg covers the composed recipe on every driver round.)

    The long-context pod recipe end to end: ring_flash_attention
    (flash kernels inside a ppermute ring) plugs into the model as an
    attention CALLABLE over a dp x sp mesh, and one full train step —
    forward, backward through the composed tier, Adam update — matches
    the single-device dense model's loss and updated params."""
    from functools import partial

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from zookeeper_tpu.models.transformer import TransformerLMModule
    from zookeeper_tpu.ops import ring_flash_attention

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "sp"))

    def make_module(attention):
        return TransformerLMModule(
            vocab_size=61, num_layers=2, d_model=64, num_heads=2,
            mlp_ratio=4, attention=attention, max_seq_len=64,
            dtype=jnp.float32,
        )

    dense = make_module("dense")
    sp = make_module(
        partial(
            ring_flash_attention,
            mesh=mesh, seq_axis="sp", batch_axis="data",
            block_q=8, block_k=8,
        )
    )
    batch = lm_batch(seq=32)
    rng = jax.random.PRNGKey(0)
    variables = dense.init(rng, batch["input"], training=False)
    params = variables["params"]

    def run(module, params, batch):
        ts = TrainState.create(
            apply_fn=module.apply,
            params=jax.tree.map(jnp.copy, params),
            model_state={},
            tx=optax.adam(1e-3),
        )
        ts, metrics = jax.jit(make_train_step())(ts, batch)
        return ts, metrics

    ts_ref, m_ref = run(dense, params, batch)

    # The SP run: batch sharded over data, sequence over sp (the
    # attention's shard_map re-shards q/k/v internally; everything else
    # is an ordinary pjit program over the same mesh).
    sharded = jax.device_put(
        batch, NamedSharding(mesh, P("data", "sp"))
    )
    ts_sp, m_sp = run(sp, params, sharded)

    np.testing.assert_allclose(
        float(m_ref["loss"]), float(m_sp["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m_ref["accuracy"]), float(m_sp["accuracy"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree.leaves(jax.device_get(ts_ref.params)),
        jax.tree.leaves(jax.device_get(ts_sp.params)),
    ):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_module_rejects_unknown_attention_tier():
    from zookeeper_tpu.models.transformer import TransformerLMModule

    module = TransformerLMModule(
        vocab_size=11, num_layers=1, d_model=16, num_heads=2,
        mlp_ratio=2, attention="ring", max_seq_len=16,
        dtype=jnp.float32,
    )
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="attention"):
        module.init(jax.random.PRNGKey(0), toks, training=False)


@pytest.mark.slow
def test_remat_policies_exact_with_flash_custom_vjp():
    """jax.checkpoint remat composes with the flash kernels' custom_vjp
    exactly: one train step under every remat policy produces the same
    loss and updated params (the "dots" policy is the transformer sweet
    spot the step docstring names — this is the model that actually
    exercises it). Bit-exact on the CPU suite backend today; compared
    at the sibling test's bit-for-bit-close tolerance because a
    backward-replayed forward may schedule differently on other
    backends (tests/training/test_step.py convention)."""
    _, module, params, state = make_model()
    batch = lm_batch()
    results = {}
    for remat in ("none", "dots", "full"):
        ts = TrainState.create(
            apply_fn=module.apply,
            params=jax.tree.map(jnp.copy, params),
            model_state=state,
            tx=optax.adam(1e-3),
        )
        step = jax.jit(make_train_step(remat=remat))
        ts, m = step(ts, batch)
        results[remat] = (float(m["loss"]), jax.device_get(ts.params))

    ref_loss, ref_params = results["none"]
    for remat in ("dots", "full"):
        loss, p = results[remat]
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_fsdp_lm_shards_exact_and_compiles_clean(capfd):
    """The LM under FSDP: with the residual-stream activation pins the
    step compiles WITHOUT GSPMD's 'Involuntary full rematerialization'
    (observed on the unpinned transformer: the FSDP axis spread into
    attention-intermediate layouts the partitioner could only
    replicate-then-repartition), big params actually shard, and one
    step matches single-device."""
    from zookeeper_tpu.parallel import FsdpPartitioner

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    _, module, params, state = make_model()
    part = FsdpPartitioner()
    # Low threshold so the tiny test model's kernels DO shard.
    configure(part, {"min_weight_size": 1024}, name="p")
    part.setup()

    # POSITIVE CONTROL first (the dryrun canary lesson: prove the
    # detector fires before trusting its silence). The original control
    # — the UNPINNED module under the same FSDP layout — ROTTED: on the
    # current XLA version it compiles without the warning, so it can no
    # longer prove the detector sees anything. The trigger is
    # single-sourced in testing.run_spmd_remat_trigger (shared with the
    # dryrun canary so the two detectors stay in lockstep; model-free,
    # so future layout fixes can't defuse it).
    from zookeeper_tpu.testing import run_spmd_remat_trigger

    capfd.readouterr()
    run_spmd_remat_trigger(8)
    canary_err = capfd.readouterr().err
    assert "Involuntary full rematerialization" in canary_err, (
        "canary: the known remat trigger compiled without the warning "
        "reaching stderr — the detector is blind, the clean assertion "
        "below would prove nothing"
    )
    capfd.readouterr()  # Drop canary noise.
    ts2, _ = _sharded_parity_run(module, params, state, lm_batch(), part)
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err
    assert any(
        not leaf.sharding.is_fully_replicated
        for leaf in jax.tree.leaves(ts2.params)
    )


def test_auto_pin_rule():
    """Auto pin: strings and the bare within-chip callables pin;
    unknown callables (assumed mesh-composed SP) do not; explicit bool
    overrides either way."""
    from functools import partial

    from zookeeper_tpu.models.transformer import _auto_pin_activations
    from zookeeper_tpu.ops import (
        attention_reference,
        flash_attention,
        ring_flash_attention,
    )

    assert _auto_pin_activations("flash", None)
    assert _auto_pin_activations("dense", None)
    assert _auto_pin_activations(flash_attention, None)
    assert _auto_pin_activations(attention_reference, None)
    assert not _auto_pin_activations(partial(ring_flash_attention), None)
    assert not _auto_pin_activations(lambda q, k, v, causal: q, None)
    assert _auto_pin_activations(partial(ring_flash_attention), True)
    assert not _auto_pin_activations("flash", False)


def test_model_summary_works_for_token_models():
    """model_summary's dummy input must be an INT for rank-1
    (token-sequence) shapes — a float dummy is an invalid embedding
    index (previously a TypeError)."""
    from zookeeper_tpu.models import model_summary

    _, module, *_ = make_model()
    s = model_summary(module, (32,), compute_flops=True)
    text = str(s)
    assert "embed" in text and "block0" in text
    assert s.total_params > 0


def test_model_summary_rank1_float_features_via_input_dtype_hint():
    """The ``input_dtype`` hint — sourced from
    ``Preprocessing.input_dtype`` at the experiment call site — is
    honored verbatim; and with NO hint the default now keys off the
    MODEL FAMILY, not the input rank (ADVICE summary.py:50, closed):
    an Mlp has no ``vocab_size``, so its rank-1 flat-feature input
    traces with a float32 dummy."""
    from zookeeper_tpu.core import configure as _cfg
    from zookeeper_tpu.models import Mlp, model_summary

    m = Mlp()
    _cfg(m, {"hidden_units": (8,)}, name="m")
    module = m.build((16,), num_classes=3)
    s = model_summary(module, (16,), input_dtype="float32")
    assert s.total_params > 0
    # No hint: same summary via the family-keyed float32 default.
    s2 = model_summary(module, (16,))
    assert s2.total_params == s.total_params


def test_model_summary_default_dtype_keys_off_model_family():
    """The family heuristic directly (ADVICE summary.py:50): a module
    declaring ``vocab_size`` (the token-pipeline marker) gets an int32
    dummy — ``compute_flops`` traces the forward, so a float dummy
    would die in the embedding lookup — while a rank-1 float-feature
    MLP traces float32 and computes FLOPs from the same default."""
    from zookeeper_tpu.core import configure as _cfg
    from zookeeper_tpu.models import Mlp, model_summary

    _, lm_module, *_ = make_model()
    s = model_summary(lm_module, (32,), compute_flops=True)
    assert s.total_params > 0  # int32 dummy: embedding lookup traced

    m = Mlp()
    _cfg(m, {"hidden_units": (8,)}, name="m_family")
    mlp_module = m.build((16,), num_classes=3)
    s2 = model_summary(mlp_module, (16,), compute_flops=True)
    assert s2.total_params > 0
    assert s2.flops is None or s2.flops > 0


@pytest.mark.slow
def test_lm_through_full_training_experiment():
    """The WHOLE component stack for the LM: ArrayDataset token corpus
    -> PassThroughPreprocessing (with example_shape sizing the model)
    -> DataLoader -> TrainingExperiment.run() with validation. Loss
    falls and validation accuracy beats chance within two epochs."""
    from zookeeper_tpu.data import ArrayDataset
    from zookeeper_tpu.training import TrainingExperiment

    vocab, seq = 61, 32
    toks, nxt = corpus_windows(seq=seq, vocab=vocab, n=128)
    ds = ArrayDataset().with_data(
        {"tokens": toks, "next": nxt},
        {"tokens": toks[:32], "next": nxt[:32]},
    )

    exp = TrainingExperiment()
    configure(
        exp,
        {
            "loader.dataset": ds,
            "loader.preprocessing": "PassThroughPreprocessing",
            "loader.preprocessing.input_key": "tokens",
            "loader.preprocessing.target_key": "next",
            "loader.preprocessing.example_shape": (seq,),
            "model": "TransformerLM",
            "model.num_layers": 2,
            "model.d_model": 64,
            "model.num_heads": 2,
            "model.max_seq_len": 64,
            "batch_size": 32,
            "epochs": 2,
            "verbose": False,
            "num_classes": vocab,
        },
        name="experiment",
    )
    history = exp.run()
    assert history["train"][-1]["loss"] < history["train"][0]["loss"]
    assert history["validation"][-1]["accuracy"] > 0.10  # chance ~1/61


def test_lm_eval_perplexity_bits_per_token_and_greedy_decode(tmp_path):
    """The LM eval surface: train -> export -> EvalExperiment with
    track_lm_metrics derives perplexity (e^CE) and bits_per_token
    (CE / ln 2) from the weighted-mean cross-entropy — derived AFTER
    aggregation, so they describe the whole split, not a mean of
    per-batch exponentials. Plus the greedy-decode smoke: deterministic
    argmax continuation within vocab, and the positional-table cap
    fails loudly."""
    import math

    from zookeeper_tpu.models import greedy_decode
    from zookeeper_tpu.training import EvalExperiment, TrainingExperiment

    lm_conf = {
        "loader.dataset": "SyntheticTokens",
        "loader.dataset.vocab_size": 31,
        "loader.dataset.num_train_examples": 64,
        "loader.preprocessing": "TokenPreprocessing",
        "seq_len": 32,
        "model": "TransformerLM",
        "model.num_layers": 1,
        "model.d_model": 32,
        "model.num_heads": 2,
        "batch_size": 16,
        "verbose": False,
    }
    export = str(tmp_path / "model")
    exp = TrainingExperiment()
    configure(
        exp, {**lm_conf, "epochs": 1, "export_model_to": export},
        name="experiment",
    )
    exp.run()

    ev = EvalExperiment()
    configure(
        ev,
        {
            **{
                k: v
                for k, v in lm_conf.items()
                if not k.startswith(("epochs", "export"))
            },
            # TokenPreprocessing derives input_shape from seq_len; the
            # eval task has no seq_len Field, so scope it directly.
            "loader.preprocessing.seq_len": 32,
            "checkpoint": export,
            "track_lm_metrics": True,
        },
        name="eval",
    )
    metrics = ev.run()
    assert metrics["perplexity"] == pytest.approx(
        math.exp(metrics["loss"]), rel=1e-6
    )
    assert metrics["bits_per_token"] == pytest.approx(
        metrics["loss"] / math.log(2.0), rel=1e-6
    )
    # An untrained-ish model on a 31-token vocab: perplexity near
    # vocab-size scale, bits consistent with it.
    assert 1.0 < metrics["perplexity"] < 100.0

    # Greedy decode smoke on the same trained weights.
    _, module, params, state = make_model(
        {"num_layers": 1, "d_model": 32, "max_seq_len": 48}, seq=32, vocab=31
    )
    variables = {"params": params, **state}
    prompt = jnp.asarray(corpus_windows(seq=16, vocab=31, n=2)[0])
    out = greedy_decode(module, variables, prompt, steps=4)
    assert out.shape == (2, 20) and out.dtype == prompt.dtype
    np.testing.assert_array_equal(np.asarray(out[:, :16]), np.asarray(prompt))
    assert int(np.asarray(out).max()) < 31
    out2 = greedy_decode(module, variables, prompt, steps=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    with pytest.raises(ValueError, match="max_seq_len"):
        greedy_decode(module, variables, prompt, steps=64)


def test_passthrough_input_shape_requires_example_shape():
    """Asking PassThroughPreprocessing for input_shape without
    configuring example_shape fails with an actionable message, not
    NotImplementedError."""
    from zookeeper_tpu.data import PassThroughPreprocessing

    pre = PassThroughPreprocessing()
    configure(pre, {}, name="pre")
    with pytest.raises(ValueError, match="example_shape"):
        pre.input_shape
    pre2 = PassThroughPreprocessing()
    configure(pre2, {"example_shape": (32,)}, name="pre2")
    assert pre2.input_shape == (32,)


def test_synthetic_tokens_and_token_preprocessing_components():
    """The CLI-constructible token pipeline: SyntheticTokens windows one
    deterministic periodic corpus (num_classes inferred from vocab);
    TokenPreprocessing derives input_shape from its seq_len field (the
    scoped-inheritance hook the TrainLM task relies on)."""
    from zookeeper_tpu.data import SyntheticTokens, TokenPreprocessing

    ds = SyntheticTokens()
    configure(
        ds,
        {"seq_len": 16, "vocab_size": 23, "num_train_examples": 64},
        name="ds",
    )
    src = ds.train()
    ex = src[0]
    assert ex["tokens"].shape == (16,) and ex["next"].shape == (16,)
    # Next-token alignment: next[i] is the stream successor of tokens[i].
    np.testing.assert_array_equal(ex["tokens"][1:], ex["next"][:-1])
    assert ds.infer_num_classes() == 23
    assert int(ex["tokens"].max()) < 23
    # Determinism: a rebuilt source yields identical windows.
    np.testing.assert_array_equal(ds.train()[0]["tokens"], ex["tokens"])
    # A validation split exists (same periodic corpus BY DESIGN — this
    # dataset is a memorization task; val_acc measures fit, not
    # generalization).
    assert ds.validation() is not None

    pre = TokenPreprocessing()
    configure(pre, {"seq_len": 16}, name="pre")
    assert pre.input_shape == (16,)
    out = pre(ex, training=True)
    np.testing.assert_array_equal(out["input"], ex["tokens"])
    np.testing.assert_array_equal(out["target"], ex["next"])


def test_max_seq_len_sentinel_and_typos():
    """-1 auto-sizes the positional table to the built sequence; 0 or
    other negatives are config typos and raise."""
    m = TransformerLM()
    configure(m, {"num_layers": 1, "d_model": 32, "num_heads": 2}, name="m")
    assert m.max_seq_len == -1
    mod = m.build((48,), num_classes=11)
    assert mod.max_seq_len == 48

    for bad in (0, -2):
        m2 = TransformerLM()
        configure(m2, {"max_seq_len": bad}, name="m2")
        with pytest.raises(ValueError, match="max_seq_len"):
            m2.build((32,), num_classes=11)


def test_token_preprocessing_example_shape_precedence():
    """The inherited example_shape knob stays live: when explicitly set
    it overrides the seq_len-derived shape."""
    from zookeeper_tpu.data import TokenPreprocessing

    pre = TokenPreprocessing()
    configure(pre, {"seq_len": 16, "example_shape": (128,)}, name="pre")
    assert pre.input_shape == (128,)
