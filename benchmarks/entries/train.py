"""Entry kind ``train``: steady training through the program's own
``TrainingExperiment.run()``, configured as its ``cli()`` configures it.

The configuration's file names the example task and the keys it is
configured with; the traffic file gives the batch per chip, the size of the
synthetic image store and the sync cadence. Nothing here names a model.

The benchmark plugs three components of its own into the task, the way a
user plugs in a dataset or a metrics sink, and edits nothing:

- a dataset that makes its image store from the seed in bulk;
- a metrics writer, which the loop calls at its own sync points (every
  ``log_every`` steps and at each epoch's end, after the readback of the
  step's metrics): these calls are the clock. The window opens at the sync
  that ends the warm-up epoch and closes at the first sync at or after
  ``--seconds``; the writer then asks the task's preemption guard to stop
  the loop at the next step boundary;
- a partitioner whose compiled step is tapped for its first calls: on the
  first call the state's parameters are replaced by the benchmark's own
  (``zkbench/weights.py``), and for the first ``follow_steps`` calls the
  batch, the loss, the optimizer's first moment after step one and the
  parameters' change are kept. The same compiled step and the same state
  then run the window.

``train_throughput`` is items trained by the steps that completed between
the two syncs, over the seconds between them and the chips.

A ``--trace 1`` run has two slices. The profiler slows the host-to-device
transfers of this loop's 308 MB batches to about a second each and keeps
gigabytes of host memory per traced step (my chip runs, PR 23), so a traced
window says nothing about how fast the loop runs. The run therefore first
measures ``trace_lead_seconds`` untraced (the step rate and the program's
``data_wait`` spans come from there), then traces from one sync to the next
(the device's busy time per step and the time by kernel come from there:
they do not depend on the host), and ends.
"""

import gc
import math
import os
import time
from typing import Any, Dict, List

import numpy as np

from zkbench import compare, spans, tracereduce
from zkbench.cells import ROOT, load_module, merged
from zkbench.device import executable_of, memory_peak_bytes
from zkbench.weights import flat_paths, make_weights, seed32


class Probe:
    """Everything the run learns from inside the loop."""

    def __init__(self, ctx, mix: Dict, config: Dict, spe: int):
        self.ctx = ctx
        self.seed = ctx.seed
        self.seconds = float(ctx.seconds)
        self.trace = bool(ctx.trace)
        self.follow = int(mix.get("follow_steps", 3))
        self.warmup_steps = int(mix.get("warmup_epochs", 1)) * spe
        self.b1 = float(config["optimizer"]["b1"])
        self.guard = None  # the task's, set once it is configured
        # taps
        self.calls = 0
        self.batches: List[Any] = []
        self.losses: List[Any] = []
        self.grad_norm = None
        self.change_norm = None
        self.params_like = None
        self._params0 = None
        # clock
        self.syncs: List[Any] = []
        self.phase = "warmup"
        self.t0 = self.t1 = None
        self.step0 = self.step1 = None
        self.setup_s = None
        self.compiles_at_open = None
        self.mark_host_ns: Dict[str, int] = {}
        self.trace_dir = os.path.join(ctx.out_dir, "trace")
        self.nonfinite = 0
        self.lead_seconds = float(mix.get("trace_lead_seconds", 5.0))
        self.untraced = None  # (t0, step0, t1, step1, ns0, ns1) of a traced run
        self.stem_stats = None
        self.executable = None  # the jax executable behind the timed step

    # -- the tap on the compiled step ------------------------------------

    def tapped_call(self, compiled, state, batch):
        import jax
        import jax.numpy as jnp

        k = self.calls
        self.ctx.phase(f"step {k + 1} dispatching")
        if k == 0:
            shardings = jax.tree.map(lambda x: x.sharding, state.params)
            self.params_like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params
            )
            params = make_weights(state.params, self.seed, shardings)
            self._params0 = jax.tree.map(jnp.copy, params)
            state = state.replace(params=params)
        self.batches.append(
            (np.asarray(batch["input"]), np.asarray(batch["target"]))
        )
        new_state, metrics = compiled(state, batch)
        self.losses.append(metrics["loss"])
        if k == 0:
            self.executable = executable_of(compiled)
            self.grad_norm = _leaf_norms(_first_moment(new_state.opt_state))
            self.stem_stats = _copy_stats(new_state.model_state)
        if k == self.follow - 1:
            self.change_norm = _leaf_norms(
                jax.tree.map(
                    lambda a, b: a - b, new_state.params, self._params0
                )
            )
            self._params0 = None
        self.calls = k + 1
        return new_state, metrics

    # -- the clock: called by the loop at its sync points ----------------

    def on_sync(self, step: int, values: Dict[str, float]) -> None:
        now = time.perf_counter()
        self.syncs.append((now, int(step)))
        for key, value in values.items():
            if key.endswith("loss") and not math.isfinite(float(value)):
                self.nonfinite += 1
        if self.phase == "warmup" and step >= self.warmup_steps:
            self.ctx.phase("window opens")
            self.compiles_at_open = self.ctx.compile_clock.compiles
            self.t0, self.step0 = time.perf_counter(), int(step)
            self.ns0 = time.perf_counter_ns()
            self.setup_s = self.ctx.clock.since_start(self.t0)
            self.phase = "window"
        elif self.phase == "window":
            limit = self.lead_seconds if self.trace else self.seconds
            if now - self.t0 >= limit:
                self.t1, self.step1 = now, int(step)
                self.ctx.phase("window closed")
                if self.trace:
                    self.untraced = (
                        self.t0, self.step0, now, int(step),
                        self.ns0, time.perf_counter_ns(),
                    )
                    self._start_trace()
                    self.traced_from = int(step)
                    self.phase = "traced"
                else:
                    self.phase = "done"
                    self.guard.request_preemption()
        elif self.phase == "traced" and step > self.traced_from:
            self.traced_steps = int(step) - self.traced_from
            self._stop_trace()
            self.ctx.phase("profiler stopped")
            self.phase = "done"
            self.guard.request_preemption()

    def _start_trace(self):
        tracereduce.start_profiler(self.trace_dir)
        self.mark_host_ns["window_start"] = tracereduce.mark("window_start")
        self.ctx.phase("profiler started")

    def _stop_trace(self):
        self.mark_host_ns["window_end"] = tracereduce.mark("window_end")
        tracereduce.stop_profiler()


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer's state keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _first_moment(part)
            if found is not None:
                return found
    if hasattr(opt_state, "inner_state"):
        return _first_moment(opt_state.inner_state)
    return None


def _copy_stats(model_state):
    """Device copies of the running statistics the step just wrote (the
    next step donates the originals)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, dict(model_state))


def _leaf_norms(tree):
    """Per-leaf L2 norms, computed on the device; a dict of device
    scalars by path, read when the window has closed."""
    import jax
    import jax.numpy as jnp

    if tree is None:
        return None

    @jax.jit
    def norms(t):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t
        )

    return norms(tree)


class StepTap:
    """The compiled step, with its first calls followed."""

    def __init__(self, compiled, probe: Probe):
        self._compiled = compiled
        self._probe = probe

    def __getattr__(self, name):
        return getattr(self._compiled, name)

    def __call__(self, state, batch):
        probe = self._probe
        if probe.calls >= probe.follow:
            return self._compiled(state, batch)
        return probe.tapped_call(self._compiled, state, batch)


def _components(probe_box: Dict):
    """The benchmark's three plug-in components. Built lazily: importing
    the program imports jax."""
    from zookeeper_tpu import component
    from zookeeper_tpu.data import SyntheticImageNet
    from zookeeper_tpu.data.source import ArraySource
    from zookeeper_tpu.parallel import DataParallelPartitioner
    from zookeeper_tpu.training.metrics import MetricsWriter

    @component
    class SeededImageStore(SyntheticImageNet):
        """A class-dependent smooth pattern plus noise, like the program's
        ``SyntheticImageNet``, made in bulk from the seed: uint8 from the
        generator, no float pass over the store."""

        def _arrays(self, n: int, seed: int):
            rng = np.random.default_rng([seed32(seed), 11])
            h, w, c = self.image_height, self.image_width, self.image_channels
            labels = rng.integers(0, self.num_classes, size=(n,), dtype=np.int32)
            yy, xx = np.meshgrid(
                np.linspace(0, 1, h, dtype=np.float32),
                np.linspace(0, 1, w, dtype=np.float32), indexing="ij",
            )
            kinds = 16
            angles = np.linspace(0.0, np.pi, kinds, endpoint=False)
            patterns = np.stack([
                (63.5 + 63.5 * np.sin(
                    2 * np.pi * (2 + k % 3) * (np.cos(a) * xx + np.sin(a) * yy)
                )).astype(np.uint8)
                for k, a in enumerate(angles)
            ])  # [kinds, h, w] in 0..127
            images = rng.integers(0, 128, size=(n, h, w, c), dtype=np.uint8)
            images += patterns[labels % kinds][..., None]
            return {"image": images, "label": labels}

        def train(self):
            return ArraySource(self._arrays(self.num_train_examples, self.seed))

    @component
    class ClockWriter(MetricsWriter):
        def write_scalars(self, step, values):
            probe_box["probe"].on_sync(step, values)

    @component
    class TappedDataParallel(DataParallelPartitioner):
        def compile_step(self, step_fn, state, *, donate_state: bool = True):
            compiled = super().compile_step(
                step_fn, state, donate_state=donate_state
            )
            return StepTap(compiled, probe_box["probe"])

    return SeededImageStore, ClockWriter, TappedDataParallel


def run(ctx) -> Dict[str, Any]:
    import jax

    cell = ctx.cell
    config, mix = cell.config, cell.traffic
    if ctx.rehearse:
        config = merged(config, config.get("rehearsal"))
        mix = merged(mix, mix.get("rehearsal"))
    chips = cell.chips
    batch = int(mix["batch_per_chip"]) * chips
    store = int(mix["store_images"]) * chips
    spe = store // batch
    log_every = int(mix["log_every"])
    if spe < int(mix.get("follow_steps", 3)) or spe % log_every:
        raise ValueError(
            f"store of {store} images gives {spe} steps an epoch: it has "
            f"to hold the followed steps and be a multiple of {log_every}"
        )

    from zookeeper_tpu import configure
    from zookeeper_tpu.resilience.faults import Preempted

    box: Dict[str, Any] = {}
    Store, Writer, Partitioner = _components(box)
    probe = box["probe"] = Probe(ctx, mix, config, spe)

    example = load_module(
        os.path.join(ROOT, "examples", config["task"]["example"] + ".py"),
        config["task"]["example"],
    )
    task = getattr(example, config["task"]["class"])()
    epochs = int(mix.get("epochs", 100000))
    conf = dict(config["program"])
    conf.update({
        "batch_size": batch,
        "epochs": epochs,
        "seed": seed32(ctx.seed),
        "log_every": log_every,
        "verbose": False,
        "writer": Writer,
        "partitioner": Partitioner,
        "loader.dataset": Store,
        "loader.dataset.num_train_examples": store,
        "loader.dataset.num_validation_examples": 0,
    })
    configure(task, conf)
    ctx.phase("configured")
    probe.guard = task.guard
    if ctx.trace:
        spans.enable()

    try:
        task.run()
        raise RuntimeError(
            f"the task ran out of its {epochs} epochs before the window closed"
        )
    except Preempted:
        pass
    if probe.phase != "done":
        raise RuntimeError(f"the loop stopped in phase {probe.phase!r}")
    ctx.phase("loop ended")
    compiled_inside = ctx.compile_clock.compiles - probe.compiles_at_open
    peak, notes_memory = memory_peak_bytes(chips, probe.executable, ctx.rehearse)
    probe.executable = None
    records = spans.drain() if ctx.trace else []

    steps = probe.step1 - probe.step0
    window_s = probe.t1 - probe.t0
    losses = [float(x) for x in jax.device_get(probe.losses)]
    grad_norm = {
        k: float(v) / (1.0 - probe.b1)
        for k, v in flat_paths(jax.device_get(probe.grad_norm)).items()
    }
    change_norm = {
        k: float(v)
        for k, v in flat_paths(jax.device_get(probe.change_norm)).items()
    }
    stem_stats = {
        k: np.asarray(v)
        for k, v in flat_paths(jax.device_get(probe.stem_stats)).items()
    }
    batches, params_like = probe.batches, probe.params_like
    total_steps = spe * epochs

    # Free the program's state before the reference takes the chip.
    probe.batches, probe.losses = [], []
    del task, example
    gc.collect()
    ctx.phase("program freed")

    program = {
        "losses": losses, "grad_norm": grad_norm, "change_norm": change_norm,
        "stats": stem_stats,
    }
    limits = config.get("limits", {})
    values, notes, controls = check(
        cell, config, ctx, program, batches, params_like, total_steps, limits
    )
    values["batch_rows_bad"] = float(
        bad_rows(batches, int(config["model"]["num_classes"]))
    )
    correct, compared = compare.judge(values, limits)
    if compiled_inside:
        correct = False
        notes.append(f"{compiled_inside} compiles inside the window")
    if probe.nonfinite:
        correct = False

    outcome = {
        "correct": correct,
        "attempted": steps,
        "failed": probe.nonfinite,
        "end_to_end": {
            "train_throughput": steps * batch / window_s / chips,
            "setup_s": probe.setup_s,
        },
        "memory_peak_bytes": peak,
        "compared": compared,
        "controls": controls,
        "notes": notes + [notes_memory] + [
            f"window {window_s:.3f}s, {steps} steps of {batch} items, "
            f"{len(probe.syncs)} syncs, compiles inside the window: "
            f"{compiled_inside}"
        ],
        "counts": {"steps": steps, "items": steps * batch},
    }
    ctx.phase("checked")
    if ctx.trace:
        extract = tracereduce.extract_xplane(
            probe.trace_dir, host_fallback=ctx.rehearse
        )
        ctx.keep_extract(extract)
        trace = tracereduce.DeviceTrace(
            extract, chips=chips, mark_host_ns=probe.mark_host_ns
        )
        a_t0, a_step0, a_t1, a_step1, a_ns0, a_ns1 = probe.untraced
        outcome["layer_ctx"] = {
            "trace": trace,
            "spans": spans.within(
                records, a_ns0, probe.mark_host_ns["window_end"]
            ),
            # the untraced slice: where the program's host spans are read
            "window_host_ns": (a_ns0, a_ns1),
            "work": {
                "steps": probe.traced_steps,  # inside the traced window
                "steps_per_s_untraced": (a_step1 - a_step0) / (a_t1 - a_t0),
                "items_per_step": batch,
                "chips": chips,
                "rehearsal": ctx.rehearse,
                "model": config["model"],
            },
            "counters": {},
        }
    return outcome


def bad_rows(batches, num_classes: int) -> int:
    """Rows of the followed batches that the loader should not have made:
    a row equal to another (the followed steps run on rows that all
    differ), a value that is not finite, a label outside the classes. An
    exact count; its limit is 0. (What the augmentation does to the pixels
    has no reference of its own and is not compared: PERF.md section 7.)"""
    seen, bad = set(), 0
    for images, labels in batches:
        labels = np.asarray(labels)
        finite = np.isfinite(
            np.asarray(images, np.float32).reshape(len(images), -1)
        ).all(axis=1)
        for i in range(len(images)):
            key = hash(images[i].tobytes())
            fine = (
                bool(finite[i]) and key not in seen
                and 0 <= int(labels[i]) < num_classes
            )
            seen.add(key)
            bad += not fine
    return bad


def check(cell, config, ctx, program, batches, params_like, total_steps, limits):
    """The reference follows the same first steps from the same weights
    and batches; returns the numbers compared, notes and, with
    ``--with-control``, the verdict on the control and on each planted
    fault: the reference in float8, or on half the batch, put in the
    program's place and judged by the same limits."""
    import jax

    reference = cell.reference_module()
    arch = config["model"]
    opt = dict(config["optimizer"], total_steps=total_steps)
    smoothing = float(config["program"].get("label_smoothing", 0.0))

    def follow(**kwargs):
        params = make_weights(params_like, ctx.seed)
        with jax.default_matmul_precision("highest"):
            return reference.train(
                params, batches, arch, opt, smoothing, **kwargs
            )

    t = time.perf_counter()
    ref = follow()
    notes = [f"reference: {time.perf_counter() - t:.1f}s"]
    ctx.phase("reference followed")
    values = gaps(program, ref)
    controls = {}
    if ctx.with_control:
        for name, kwargs in (
            ("control_fp8", {"lowp": True}),
            ("fault_half_batch", {"rows": slice(0, len(batches[0][1]) // 2)}),
        ):
            t = time.perf_counter()
            other = gaps(follow(**kwargs), ref)
            verdict, rows = compare.judge(other, limits)
            controls[name] = {"correct": verdict, "compared": rows}
            notes.append(
                f"{name} ({time.perf_counter() - t:.1f}s): "
                + " ".join(f"{k}={v:.6g}" for k, v in other.items())
            )
    leaf_gaps = sorted(
        (
            (gap, n, ref["grad_norm"][n])
            for n, gap in _leaf_gaps(program["grad_norm"], ref["grad_norm"]).items()
        ),
        reverse=True,
    )
    notes.append(
        "largest gradient-norm gaps by leaf (gap, leaf, reference norm): "
        + "; ".join(f"{g:.3f} {n} {r:.3g}" for g, n, r in leaf_gaps[:6])
        + f" | leaves moved {len(compare.moved_leaves(ref['grad_norm']))}"
        + f" of {len(ref['grad_norm'])}"
    )
    notes.append(
        "losses program " + " ".join(f"{x:.6f}" for x in program["losses"])
        + " | reference " + " ".join(f"{x:.6f}" for x in ref["losses"])
    )
    return values, notes, controls


def _leaf_gaps(program: Dict, ref: Dict, leaves=None) -> Dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's
    over the reference's (a leaf the program lacks reads nan)."""
    return {
        n: abs(program.get(n, math.nan) - ref[n]) / max(ref[n], 1e-30)
        for n in (ref if leaves is None else leaves)
    }


def gaps(program: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared: each step's loss, the first gradient's norm
    by the worst leaf, the parameters' change by the worst leaf (leaves the
    reference's gradient leaves unmoved are left out) and by the median
    leaf."""
    out = {}
    for i, (p, r) in enumerate(zip(program["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = compare.relative_gap(p, r)
    out["grad_norm_gap"], _ = compare.worst_leaf_gap(
        program["grad_norm"], ref["grad_norm"]
    )
    moved = compare.moved_leaves(ref["grad_norm"])
    out["change_norm_gap"], _ = compare.worst_leaf_gap(
        program["change_norm"], ref["change_norm"], moved
    )
    grads = sorted(_leaf_gaps(program["grad_norm"], ref["grad_norm"]).values())
    out["grad_norm_median_gap"] = grads[len(grads) // 2]
    total_p = math.sqrt(sum(v * v for v in program["grad_norm"].values()))
    total_r = math.sqrt(sum(v * v for v in ref["grad_norm"].values()))
    out["grad_global_norm_gap"] = compare.relative_gap(total_p, total_r)
    per_leaf = sorted(
        _leaf_gaps(program["change_norm"], ref["change_norm"], moved).values()
    )
    out["change_norm_median_gap"] = per_leaf[len(per_leaf) // 2]
    out["stem_stats_gap"] = stem_stats_gap(program["stats"], ref["stats"])
    return out


def stem_stats_gap(program: Dict, ref: Dict) -> float:
    """The running statistics that step one wrote for the BatchNorms the
    reference reports (those ahead of the first binarization: what reaches
    them is a smooth function of the batch and the weights, so the gap
    reads the arithmetic's precision and not the binarized net's chaos).
    The largest relative difference norm over those vectors."""
    worst = 0.0
    for name, r in ref.items():
        p = program.get(name)
        if p is None:
            return math.inf
        r = np.asarray(r, np.float64)
        p = np.asarray(p, np.float64)
        worst = max(
            worst,
            float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30)),
        )
    return worst
