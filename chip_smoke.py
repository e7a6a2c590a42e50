#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the system's two main paths through the entry points a
user would call, at the full width of models the repo supports, then checks
every Pallas kernel against its reference:

1. **start** — print what jax sees; anything but a TPU ends the run with a
   non-zero exit code and no result line. There is no CPU mode.
2. **trainer** — ``examples/imagenet_experiment.py``'s ``TrainImageNet``
   task, configured and run the way ``cli()`` does it: QuickNet-Large,
   bf16 compute, int8 binary convs, 224x224x3, 1000 classes, batch 128 per
   chip, data-parallel over every local chip, synthetic data through the
   real augmenting ``DataLoader``; a few steps, then the validation pass.
3. **server** — ``LMServingConfig.build_service()`` at 4 layers / d_model
   512 / 8 heads of 64 / vocab 1024 / bf16 with the paged KV layout and
   ``decode_attention=auto``; requests through ``DecodeScheduler.submit``
   / ``drain``; no warmed program's optimised HLO may copy a whole leaf
   of the page pool (``DecodeEngine.pool_sized_copies``) or the whole
   token table (``table_sized_copies``; at this width its rows are whole
   lane tiles, ``tools/probe_table_copies.py`` asks it of a ragged one);
   the same
   requests served by the kernel flavor and by the reference flavor must
   give identical tokens (compared in float32 at the highest matmul
   precision).
4. **kernels** — ``__graft_entry__.verify_onchip()``: every binary compute
   path bit-exact, every Pallas kernel against its reference.

Any failed check or exception ends the run with a non-zero exit code. The
last line of standard output is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
the device as jax reports it. The line before it (``chip_smoke: report
{...}``) carries each phase's pass/fail and its wall, compile and run
seconds. The times are set-up facts (how long the system takes to start),
not performance metrics.

    python chip_smoke.py          # on a machine with a TPU
"""

import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Trainer: images per chip per step, and steps before validation.
PER_CHIP_BATCH = 128
TRAIN_STEPS = 6

#: Server: the widest transformer the repo pins (bench.LM_BENCH_CONFIG)
#: behind a KV capacity in the thousands and two prompt buckets.
LM_CONFIG = {
    "model.num_layers": 4,
    "model.d_model": 512,
    "model.num_heads": 8,
    "model.compute_dtype": "bfloat16",
    "vocab_size": 1024,
    "seq_len": 2048,
    "engine.slots": 8,
    "engine.seq_buckets": (64, 512),
}
#: Prompt lengths spanning both buckets; more prompts than slots, so
#: finished slots are refilled mid-traffic. The last prompt starts with
#: the whole of the first, which the radix prefix cache serves warm.
PROMPT_LENGTHS = (100, 40, 64, 5, 300, 512, 17, 450, 33, 200)
NEW_TOKENS = 32


class CompileClock:
    """Seconds the XLA/Mosaic compiler spent compiling (or the persistent
    cache spent answering), and how many compiles the cache answered,
    summed over the process — read before and after a phase. Tracing and
    lowering are host work and stay with a phase's run seconds."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def start():
    """Print what jax sees and refuse anything but a TPU."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']}",
        flush=True,
    )
    if device["platform"] != "tpu":
        print(
            "chip_smoke: jax found no TPU (platform="
            f"{device['platform']!r}). This check runs on the chip only "
            "and has no CPU mode.",
            file=sys.stderr,
            flush=True,
        )
        sys.exit(2)
    return device


def check(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def load_example(name):
    """Import ``examples/<name>.py`` (a script directory, not a
    package) the way running it would."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "examples", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def trainer_phase(
    per_chip_batch=PER_CHIP_BATCH, steps=TRAIN_STEPS, overrides=None,
    platform="tpu",
):
    """``TrainImageNet`` via ``configure`` + ``run`` (what ``cli()``
    does), then: loss finite, step counter advanced, validation ran,
    parameters and batch spread over every local chip."""
    import jax

    from zookeeper_tpu import configure, native
    from zookeeper_tpu.observability.ledger import default_ledger

    # The augmenting loader's rate depends on the host kernels; a quiet
    # Python fallback would later read as an idle chip.
    native_status = native.status()
    print(f"chip_smoke: native host kernels {native_status}", flush=True)
    check(native_status != "unavailable", "native host kernels unavailable")

    n = jax.device_count()
    task = load_example("imagenet_experiment").TrainImageNet()
    configure(
        task,
        {
            "model": "QuickNetLarge",
            "model.compute_dtype": "bfloat16",
            "model.binary_compute": "int8",
            "batch_size": per_chip_batch * n,
            "epochs": 1,
            "steps_per_epoch": steps,
            # The synthetic splits grow with the chip count (the defaults
            # on one chip): an epoch holds the steps, and validation has
            # whole global batches to score.
            "loader.dataset.num_train_examples": 16 * per_chip_batch * n,
            "loader.dataset.num_validation_examples": 2 * per_chip_batch * n,
            **(overrides or {}),
        },
    )
    history = task.run()

    train, validation = history["train"][-1], history["validation"][-1]
    check(math.isfinite(train["loss"]), f"train loss {train['loss']}")
    check(math.isfinite(validation["loss"]), f"val loss {validation['loss']}")
    state = task.final_state
    check(int(state.step) == steps, f"step counter {int(state.step)}")

    # Placement: data-parallel means every parameter lives on every
    # chip and the batch has one shard per chip.
    ids = {d.id for d in jax.devices()}
    for leaf in jax.tree.leaves(state.params):
        on = {s.device.id for s in leaf.addressable_shards}
        check(on == ids, f"a parameter sits on devices {sorted(on)}")
        check(
            {d.platform for d in leaf.sharding.device_set} == {platform},
            f"a parameter is not on the {platform}",
        )
    batches = task.loader.batches(
        "train", epoch=0, sharding=task.partitioner.batch_sharding()
    )
    batch = next(batches)
    batches.close()
    shards = batch["input"].addressable_shards
    check(
        {s.device.id for s in shards} == ids and len(shards) == n,
        f"the batch has {len(shards)} shards on "
        f"{sorted(s.device.id for s in shards)}",
    )
    check(
        all(s.data.shape[0] == per_chip_batch for s in shards),
        f"batch shards of {[s.data.shape[0] for s in shards]} rows",
    )

    step = default_ledger().latest("train_step")
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "native": native_status,
        "model": type(task.model).__name__,
        "global_batch": per_chip_batch * n,
        "steps": steps,
        "train_loss": round(train["loss"], 4),
        "val_loss": round(validation["loss"], 4),
        "param_devices": sorted(ids),
        "batch_shards": len(shards),
        # What the chip's compiler reports for the step (facts the
        # benchmark will need, not metrics).
        "train_step_cost_flops": step.flops,
        "train_step_cost_bytes": step.bytes_accessed,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }


def serve(flavor, prompts, new_tokens, overrides):
    """One service, one pass over ``prompts``; returns the tokens and
    the facts about what served them."""
    from zookeeper_tpu import configure
    from zookeeper_tpu.serving import LMServingConfig

    service = LMServingConfig()
    configure(
        service,
        {
            **LM_CONFIG,
            "engine.decode_attention": flavor,
            "requests": 0,
            "verbose": False,
            **(overrides or {}),
        },
    )
    engine, scheduler = service.build_service()
    try:
        warm = engine.compile_count
        streams = [
            scheduler.submit(p, max_new_tokens=new_tokens) for p in prompts
        ]
        scheduler.drain()
        tokens = [s.result() for s in streams]
        facts = {
            "flavor": engine.decode_attention_flavor,
            "kv_capacity": engine.capacity,
            "compiles": engine.compile_count,
            "recompiles_after_warmup": engine.compile_count - warm,
            "prefix_cache_hit_rate": round(
                engine.page_pool.prefix_hit_rate, 4
            ),
            # SingleDevicePartitioner: the server computes on one chip,
            # however many the host has — this one.
            "serving_devices": sorted(
                d.id for d in engine._cache[0]["k"].devices()
            ),
            "mosaic_call_in_decode_step": "tpu_custom_call"
            in engine._decode_compiled().as_text(),
            # Per warmed program, the instructions that re-lay-out a
            # whole leaf of the donated page pool (none may).
            "pool_sized_copies": engine.pool_sized_copies(),
            # The same of the token table (its rows are whole lane
            # tiles at this width: nothing to re-lay either way).
            "table_sized_copies": engine.table_sized_copies(),
            # What the compiler's cost analysis says of a step that
            # holds a Pallas call (a fact for the benchmark to come).
            "decode_step_cost_flops": engine._ledger_records[
                "decode_step"
            ].flops,
            "decode_step_cost_bytes": engine._ledger_records[
                "decode_step"
            ].bytes_accessed,
        }
    finally:
        service._teardown_service(suppress=True)
    return tokens, facts


def server_phase(
    prompt_lengths=PROMPT_LENGTHS, new_tokens=NEW_TOKENS, overrides=None,
    expect_flavor="pallas",
):
    """Serve at full width with ``decode_attention=auto``: the resolved
    flavor is the kernel, the decode step holds a Mosaic custom call,
    nothing compiles after warm-up. Then the repo's token contract: the
    kernel flavor and the reference flavor give identical tokens. That
    one comparison is made in float32 at the highest matmul precision:
    on fresh-init weights the top two logits are near-ties, and the
    TPU's default matmuls round their operands to bfloat16, which turns
    the kernel's last-ulp differences into flipped argmaxes (on the v5e
    in PR 21: 2 of 10 requests in bfloat16, 1 of 10 in float32 at the
    default precision), after which the streams diverge."""
    import jax
    import numpy as np

    overrides = dict(overrides or {})
    rng = np.random.default_rng(0)
    vocab = overrides.get("vocab_size", LM_CONFIG["vocab_size"])
    prompts = [
        rng.integers(1, vocab, size=n).astype(np.int32)
        for n in prompt_lengths
    ]
    prompts[-1][: len(prompts[0])] = prompts[0]

    def served(flavor, expect, conf):
        tokens, facts = serve(flavor, prompts, new_tokens, conf)
        print(f"chip_smoke: served with {facts}", flush=True)
        check(
            facts["flavor"] == expect,
            f"decode_attention={flavor} resolved to {facts['flavor']!r}",
        )
        check(
            facts["mosaic_call_in_decode_step"] == (expect == "pallas"),
            f"Mosaic custom call in the {expect} decode step: "
            f"{facts['mosaic_call_in_decode_step']}",
        )
        check(facts["recompiles_after_warmup"] == 0, "compiled after warm-up")
        # The reference flavor's decode step gathers every slot's pages
        # into a view as large as the pool: that is what it is.
        check(
            expect != "pallas" or not any(facts["pool_sized_copies"].values()),
            "programs that copy a whole leaf of the page pool: "
            f"{facts['pool_sized_copies']}",
        )
        check(
            not any(facts["table_sized_copies"].values()),
            "programs that copy the whole token table: "
            f"{facts['table_sized_copies']}",
        )
        for out in tokens:
            check(out.shape == (new_tokens,), f"{out.shape} tokens answered")
            check(((out >= 0) & (out < vocab)).all(), "token outside the vocab")
        return tokens, facts

    _, facts = served("auto", expect_flavor, overrides)
    exact = {**overrides, "model.compute_dtype": "float32"}
    with jax.default_matmul_precision("highest"):
        tokens, _ = served("auto", expect_flavor, exact)
        ref_tokens, _ = served("reference", "reference", exact)
    differing = [
        i for i, (a, b) in enumerate(zip(tokens, ref_tokens))
        if not np.array_equal(a, b)
    ]
    check(
        not differing,
        f"requests {differing} differ between the kernel and the "
        "reference flavor in float32: first differing positions "
        f"""{[int(np.argmax(tokens[i] != ref_tokens[i])) for i in differing]}""",
    )
    return {
        **facts,
        "requests": len(prompts),
        "generated_tokens": len(prompts) * new_tokens,
        "tokens_equal_reference_flavor": "float32, highest precision",
    }


def kernels_phase(**sizes):
    """Every binary compute path and every Pallas kernel against its
    reference (``__graft_entry__.verify_onchip``)."""
    import __graft_entry__ as graft

    return graft.verify_onchip(**sizes)


def main():
    t0 = time.perf_counter()
    device = start()

    from zookeeper_tpu.parallel.distributed import enable_compile_cache

    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(
        f"chip_smoke: compile cache {cache_dir} ({cached} entries)",
        flush=True,
    )
    clock = CompileClock()
    report = {
        "compile_cache": {"dir": cache_dir, "entries_at_start": cached},
        "phases": {},
    }
    ok = False
    try:
        for name, phase in (
            ("trainer", trainer_phase),
            ("server", server_phase),
            ("kernels", kernels_phase),
        ):
            print(f"chip_smoke: phase {name}", flush=True)
            wall, compiling, hits = (
                time.perf_counter(), clock.seconds, clock.cache_hits
            )
            facts = phase()
            wall = time.perf_counter() - wall
            compiling = clock.seconds - compiling
            report["phases"][name] = {
                "ok": True,
                "wall_s": round(wall, 1),
                "compile_s": round(compiling, 1),
                "run_s": round(wall - compiling, 1),
                "compile_cache_hits": clock.cache_hits - hits,
                **facts,
            }
        ok = True
    except Exception:
        # The one boundary: say which phase failed, print the result
        # line with ok=false, exit non-zero. Later phases do not run.
        traceback.print_exc()
        report["phases"][name] = {"ok": False}
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    # No rate, no utilization: this script makes no performance claim.
    report["claim"] = None
    print(f"chip_smoke: report {json.dumps(report)}", flush=True)
    # The result line: exactly these two keys, last on standard output.
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
