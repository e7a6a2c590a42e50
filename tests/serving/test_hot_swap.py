"""Checkpoint→serving streaming: atomic weight hot-swap into a warmed
engine (no recompiles), the CheckpointWatcher's finalized-steps-only
discovery, and the bit-identity of a live swap vs a cold load of the
same step (docs/DESIGN.md §12)."""

import numpy as np
import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.serving import CheckpointWatcher, InferenceEngine, ServingMetrics

from tests.serving.test_decode_engine import (
    build_lm,
    make_engine as make_paged_engine,
    make_scheduler,
)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


def build_model(hidden=(16,), features=6, classes=4, seed=0):
    from zookeeper_tpu.models.simple import Mlp

    model = Mlp()
    configure(model, {"hidden_units": tuple(hidden)}, name="model")
    module = model.build((features,), classes)
    params, model_state = model.initialize(module, (features,), seed=seed)
    return module, params, model_state


def make_engine(module, params, model_state, buckets=(4,), features=6):
    engine = InferenceEngine()
    configure(engine, {"batch_buckets": tuple(buckets)}, name="engine")
    engine.bind(module.apply, params, model_state, (features,))
    return engine


def save_step(ckpt_dir, module, params, model_state, step):
    import jax.numpy as jnp
    import optax

    from zookeeper_tpu.training import Checkpointer, TrainState

    ckpt = Checkpointer()
    configure(
        ckpt, {"directory": str(ckpt_dir), "synchronous": True}, name="ckpt"
    )
    state = TrainState.create(
        apply_fn=module.apply,
        params=params,
        model_state=model_state,
        tx=optax.sgd(0.1),
    ).replace(step=jnp.asarray(step))
    assert ckpt.save(state, step=step)
    ckpt.wait()
    ckpt.close()


def test_swap_weights_bit_identical_no_recompile():
    """A swap serves exactly what a cold bind of the same weights
    serves, and moves the compile counter by ZERO."""
    module, p1, ms = build_model(seed=0)
    _, p2, _ = build_model(seed=1)
    engine = make_engine(module, p1, ms)
    engine.warmup()
    warm = engine.compile_count
    x = np.random.default_rng(0).normal(size=(3, 6)).astype(np.float32)
    out1 = np.asarray(engine.infer(x))
    engine.swap_weights(p2, ms)
    out2 = np.asarray(engine.infer(x))
    assert engine.compile_count == warm
    cold = make_engine(module, p2, ms)
    cold.warmup()
    assert np.array_equal(out2, np.asarray(cold.infer(x)))
    assert not np.array_equal(out1, out2)  # the swap really took


def test_swap_weights_rejects_mismatched_trees():
    module, p1, ms = build_model(hidden=(16,))
    _, p_wide, _ = build_model(hidden=(32,))
    _, p_deep, _ = build_model(hidden=(16, 16))
    engine = make_engine(module, p1, ms)
    with pytest.raises(ValueError, match="shape/dtype mismatch"):
        engine.swap_weights(p_wide, ms)
    with pytest.raises(ValueError, match="does not match the bound"):
        engine.swap_weights(p_deep, ms)


def test_watch_checkpoints_live_swap_matches_cold_load(tmp_path):
    """The acceptance pin: a live watch_checkpoints swap serves
    BIT-identical outputs to a cold load_inference_model of the same
    step, with compile_count unchanged post-warmup — and the metrics
    gauge names which training step is live."""
    from zookeeper_tpu.training import load_inference_model

    module, p1, ms = build_model(seed=0)
    _, p2, _ = build_model(seed=1)
    _, p_init, _ = build_model(seed=2)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)

    engine = make_engine(module, p_init, ms)
    engine.warmup()
    warm = engine.compile_count
    metrics = ServingMetrics()
    configure(metrics, {}, name="metrics")
    watch = engine.watch_checkpoints(
        str(ckpt_dir), weights="raw", metrics=metrics, start=False
    )
    assert watch.poll_once() == 1
    assert watch.poll_once() is None  # nothing new

    x = np.random.default_rng(1).normal(size=(4, 6)).astype(np.float32)
    live = np.asarray(engine.infer(x))
    cp, cms = load_inference_model(str(ckpt_dir), weights="raw", step=1)
    cold = make_engine(module, cp, cms)
    cold.warmup()
    assert np.array_equal(live, np.asarray(cold.infer(x)))

    # The training run advances; the next poll swaps the newer step in.
    save_step(ckpt_dir, module, p2, ms, step=2)
    assert watch.poll_once() == 2
    assert watch.current_step == 2
    live2 = np.asarray(engine.infer(x))
    cp2, _ = load_inference_model(str(ckpt_dir), weights="raw", step=2)
    cold2 = make_engine(module, cp2, cms)
    cold2.warmup()
    assert np.array_equal(live2, np.asarray(cold2.infer(x)))

    assert engine.compile_count == warm  # ZERO recompiles across swaps
    totals = metrics.totals
    assert totals["weight_swaps"] == 2
    assert totals["serving_weights_step"] == 2
    assert "weight_swap_ms_mean" in metrics.snapshot()


def test_watcher_never_serves_unfinalized_steps(tmp_path):
    """A torn async write (unfinalized remnant — the
    kill_during_async_write disk state) must be INVISIBLE to the
    watcher: discovery only ever returns atomically-finalized steps."""
    from zookeeper_tpu.resilience import FaultPlan, faults
    from zookeeper_tpu.training import Checkpointer, finalized_steps

    module, p1, ms = build_model(seed=0)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)

    # Tear an async write of step 2 mid-write.
    import jax.numpy as jnp
    import optax

    from zookeeper_tpu.training import TrainState

    ckpt = Checkpointer()
    configure(
        ckpt,
        {"directory": str(ckpt_dir), "mode": "async"},
        name="ckpt_async",
    )
    state = TrainState.create(
        apply_fn=module.apply, params=p1, model_state=ms, tx=optax.sgd(0.1)
    ).replace(step=jnp.asarray(2))
    with faults.injected(FaultPlan(kill_during_async_write=2)):
        ckpt.save(state, step=2)
        ckpt.wait()
    ckpt.close()

    assert finalized_steps(str(ckpt_dir)) == [1]
    engine = make_engine(module, p1, ms)
    engine.warmup()
    watch = engine.watch_checkpoints(
        str(ckpt_dir), weights="raw", start=False
    )
    assert watch.poll_once() == 1  # never 2
    assert watch.poll_once() is None


def test_watcher_tolerates_step_vanishing_between_list_and_load(tmp_path):
    """Retention GC racing the poll: the newest step vanishing between
    discovery and load is skipped (warning, retry next poll), exactly
    like restore_state's walk."""
    import shutil

    module, p1, ms = build_model(seed=0)
    _, p2, _ = build_model(seed=1)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)
    save_step(ckpt_dir, module, p2, ms, step=2)

    engine = make_engine(module, p1, ms)
    engine.warmup()
    watch = engine.watch_checkpoints(
        str(ckpt_dir), weights="raw", start=False
    )

    from zookeeper_tpu.training import checkpoint as ckpt_mod

    orig = ckpt_mod.load_inference_model
    raced = {"done": False}

    def racing_load(path, **kwargs):
        if kwargs.get("step") == 2 and not raced["done"]:
            raced["done"] = True
            shutil.rmtree(str(ckpt_dir / "2"))  # GC wins the race
        return orig(path, **kwargs)

    import unittest.mock as mock

    with mock.patch.object(ckpt_mod, "load_inference_model", racing_load):
        assert watch.poll_once() is None  # skipped, not raised
    assert raced["done"]
    assert watch.poll_once() == 1  # next poll serves the survivor


def test_watcher_threaded_start_stop(tmp_path):
    """The production path: the daemon poller swaps a new step in
    without any explicit poll_once calls, and stop() is idempotent."""
    import time

    module, p1, ms = build_model(seed=0)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)
    engine = make_engine(module, p1, ms)
    engine.warmup()
    watch = engine.watch_checkpoints(
        str(ckpt_dir), weights="raw", poll_interval_s=0.01
    )
    try:
        deadline = time.perf_counter() + 30
        while watch.current_step != 1 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert watch.current_step == 1
    finally:
        watch.stop()
        watch.stop()  # idempotent


def test_watcher_rejects_bad_config():
    module, p1, ms = build_model()
    engine = make_engine(module, p1, ms)
    with pytest.raises(ValueError, match="unknown"):
        CheckpointWatcher(engine, "/tmp/nowhere", weights="fastest")
    with pytest.raises(ValueError, match="poll_interval_s"):
        CheckpointWatcher(engine, "/tmp/nowhere", poll_interval_s=0)


def test_watcher_survives_torn_finalized_step(tmp_path):
    """A FINALIZED-but-torn step (post-crash disk state, the
    corrupt_checkpoint_step shape) must not kill the watcher: the poll
    warns and retries, and a newer good step still swaps in."""
    from zookeeper_tpu.resilience import corrupt_checkpoint_dir

    module, p1, ms = build_model(seed=0)
    _, p2, _ = build_model(seed=1)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)
    save_step(ckpt_dir, module, p2, ms, step=2)
    assert corrupt_checkpoint_dir(str(ckpt_dir / "2")) > 0

    engine = make_engine(module, p1, ms)
    engine.warmup()
    watch = engine.watch_checkpoints(
        str(ckpt_dir), weights="raw", start=False
    )
    assert watch.poll_once() is None  # torn: warn + retry, never fatal
    assert not watch._stop.is_set()
    save_step(ckpt_dir, module, p2, ms, step=3)
    assert watch.poll_once() == 3  # the next good step streams in


def test_watch_start_surfaces_config_errors_at_call_site(tmp_path):
    """weights="ema" against an EMA-less run is a configuration bug:
    with start=True the eager first poll raises HERE, not silently on
    the daemon thread."""
    module, p1, ms = build_model(seed=0)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)
    engine = make_engine(module, p1, ms)
    engine.warmup()
    with pytest.raises(ValueError, match="no ema_params"):
        engine.watch_checkpoints(str(ckpt_dir), weights="ema")


def test_watcher_initial_step_skips_redundant_startup_swap(tmp_path):
    """initial_step seeds the watcher with the step the caller already
    bound: startup performs NO redundant reload/swap, and only a newer
    step triggers one (ServingConfig.build_service's path)."""
    module, p1, ms = build_model(seed=0)
    _, p2, _ = build_model(seed=1)
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)
    engine = make_engine(module, p1, ms)
    engine.warmup()
    metrics = ServingMetrics()
    configure(metrics, {}, name="metrics")
    watch = engine.watch_checkpoints(
        str(ckpt_dir),
        weights="raw",
        metrics=metrics,
        start=False,
        initial_step=1,
    )
    assert watch.poll_once() is None  # step 1 is already live
    totals = metrics.totals
    assert totals["weight_swaps"] == 0  # no swap counted at startup
    assert totals["serving_weights_step"] == 1  # but the gauge is live
    save_step(ckpt_dir, module, p2, ms, step=2)
    assert watch.poll_once() == 2
    assert metrics.totals["weight_swaps"] == 1


def test_watch_missing_directory_warns_but_keeps_polling(tmp_path, caplog):
    """A directory that does not exist yet (serving started before the
    training run's first save — legitimate) is a loud warning, not an
    error; once the first checkpoint lands, the next poll streams it."""
    import logging

    module, p1, ms = build_model(seed=0)
    engine = make_engine(module, p1, ms)
    engine.warmup()
    ckpt_dir = tmp_path / "not_yet"
    with caplog.at_level(logging.WARNING, "zookeeper_tpu.serving.engine"):
        watch = engine.watch_checkpoints(
            str(ckpt_dir), weights="raw", start=False
        )
    assert any("does not exist" in r.message for r in caplog.records)
    assert watch.poll_once() is None  # nothing there yet, no error
    save_step(ckpt_dir, module, p1, ms, step=1)
    assert watch.poll_once() == 1  # the first save streams in


def test_dead_watcher_is_observable(tmp_path):
    """A fatal config error on the daemon thread must be OBSERVABLE:
    alive flips False and ServingMetrics counts watcher_stopped, so a
    frozen serving_weights_step can never masquerade as up-to-date."""
    import time

    module, p1, ms = build_model(seed=0)
    _, p_deep, deep_ms = build_model(hidden=(16, 16))
    ckpt_dir = tmp_path / "ckpt"
    save_step(ckpt_dir, module, p1, ms, step=1)

    engine = make_engine(module, p1, ms)
    engine.warmup()
    metrics = ServingMetrics()
    configure(metrics, {}, name="metrics")
    watch = engine.watch_checkpoints(
        str(ckpt_dir),
        weights="raw",
        metrics=metrics,
        poll_interval_s=0.01,
        initial_step=1,
    )
    assert watch.alive
    # The training run restarts with a DIFFERENT architecture into the
    # same directory: the next poll's swap must fail fatally.
    save_step(ckpt_dir, module, p_deep, deep_ms, step=2)
    deadline = time.perf_counter() + 30
    while watch.alive and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert not watch.alive
    assert metrics.totals["watcher_stopped"] == 1
    assert watch.current_step == 1  # frozen, and marked as such
    watch.stop()


# -- the decode engine: a checkpoint swapped in is held as bind holds one --


def decode_lm(seed, d_model=32):
    """``(module, params, state)``: bfloat16 compute over float32
    parameters, the case in which the engine holds what it was not given."""
    return build_lm(
        d_model=d_model, max_seq_len=32, seed=seed, compute_dtype="bfloat16"
    )[:3]


def make_decode_engine(module, params, state):
    # make_paged_engine turns the prefix cache off: a direct swap_weights does
    # not drop it (the scheduler's staged swap does)
    engine = make_paged_engine(
        module, params, state, slots=1, seq_buckets=(8,), kv_capacity=32
    )
    engine.warmup()
    return engine


def generate(engine, prompt):
    return make_scheduler(engine, max_new_tokens=8).generate(prompt)


def test_decode_swap_of_a_float32_checkpoint_is_held_cast():
    """The trainer's float32 checkpoint passes ``check_swap`` against
    what was BOUND (float32 kernels, tables of ``d_model`` 32 columns),
    though the engine holds its matmul kernels in bfloat16 and its
    tables with rows of 128 lanes; it is placed as ``bind`` places one,
    and serves the tokens a fresh ``bind`` of it serves, with no compile."""
    import jax
    import jax.numpy as jnp

    module, p1, state = decode_lm(seed=0)
    _, p2, _ = decode_lm(seed=1)
    engine = make_decode_engine(module, p1, state)
    warm = engine.compile_count
    prompt = np.arange(1, 7, dtype=np.int32)
    before = generate(engine, prompt)
    engine.check_swap(p2, state)
    engine.swap_weights(p2, state)
    after = generate(engine, prompt)
    assert engine.compile_count == warm
    cold = make_decode_engine(module, p2, state)
    assert jax.tree.structure(engine._variables) == jax.tree.structure(
        cold._variables
    )
    for held, fresh in zip(
        jax.tree.leaves(engine._variables), jax.tree.leaves(cold._variables)
    ):
        assert (held.dtype, held.shape) == (fresh.dtype, fresh.shape)
        np.testing.assert_array_equal(np.asarray(held), np.asarray(fresh))
    held = engine._variables["params"]
    assert held["block0"]["up"]["kernel"].dtype == jnp.bfloat16
    assert p2["embed"].shape == (53, 32) and p2["pos"].shape == (32, 32)
    assert held["embed"].shape == (53, 128) and held["pos"].shape == (32, 128)
    np.testing.assert_array_equal(
        np.asarray(held["tied_head"]), np.asarray(p2["embed"])
    )
    np.testing.assert_array_equal(after, generate(cold, prompt))
    assert not np.array_equal(before, after)  # the swap really took


@pytest.mark.parametrize(
    "differs", ["shape", "dtype", "structure", "padded", "held"]
)
def test_decode_swap_is_checked_against_the_bound_tree(differs):
    """A candidate is held to the tree ``bind`` was given, not to the
    tree the engine holds: one in the HELD types (bfloat16 kernels) is a
    dtype mismatch like any other, one with the HELD tables' padded rows
    a shape mismatch like any other, and the held tree itself (a tied
    head's table twice) another structure."""
    module, params, state = decode_lm(seed=0)
    engine = make_decode_engine(module, params, state)
    held = engine._variables["params"]
    if differs == "shape":
        _, candidate, _ = decode_lm(seed=0, d_model=64)
        match = "shape/dtype mismatch"
    elif differs == "dtype":
        candidate = {k: v for k, v in held.items() if k != "tied_head"}
        match = r"bfloat16 where the engine serves \(32, 96\)/float32"
    elif differs == "padded":
        candidate = {**params, "embed": held["embed"], "pos": held["pos"]}
        match = (
            r"shape/dtype mismatch — \(53, 128\)/float32 where the engine "
            r"serves \(53, 32\)/float32; \(32, 128\)/float32 where"
        )
    elif differs == "held":
        candidate = held
        match = "does not match the bound"
    else:
        candidate = {k: v for k, v in params.items() if k != "pos"}
        match = "does not match the bound"
    with pytest.raises(ValueError, match=match):
        engine.check_swap(candidate, state)
    with pytest.raises(ValueError, match=match):
        engine.swap_weights(candidate, state)
