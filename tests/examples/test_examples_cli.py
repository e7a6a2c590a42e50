"""The example scripts ARE the reference's canonical capability demo
(SURVEY §2.3): pin that each drives end-to-end from its CLI, in a real
subprocess (fresh interpreter, arg parsing, task registry, exit code)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


def run_example(script, *args, timeout=240):
    env = dict(os.environ)
    pythonpath = REPO
    if env.get("PYTHONPATH"):
        pythonpath = f"{REPO}{os.pathsep}{env['PYTHONPATH']}"
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": pythonpath,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_mnist_train_export_eval_convert(tmp_path):
    export = str(tmp_path / "model")
    packed = str(tmp_path / "packed")
    out = run_example(
        "mnist_experiment.py", "TrainMnist",
        "model=BinaryNet", "model.features=(8,8)", "model.dense_units=(16,)",
        "epochs=1", "steps_per_epoch=2", "batch_size=16",
        "loader.dataset.num_train_examples=32",
        "loader.dataset.num_validation_examples=16",
        f"export_model_to='{export}'",
    )
    assert "epoch 1/1" in out

    out = run_example(
        "mnist_experiment.py", "EvaluateMnist",
        "model=BinaryNet", "model.features=(8,8)", "model.dense_units=(16,)",
        "batch_size=16",
        "loader.dataset.num_train_examples=32",
        "loader.dataset.num_validation_examples=16",
        f"checkpoint='{export}'",
    )
    assert "eval[validation]" in out

    out = run_example(
        "convert_packed.py", "ConvertPacked",
        "model=BinaryNet", "model.features=(8,8)", "model.dense_units=(16,)",
        f"checkpoint='{export}'", f"output='{packed}'",
    )
    assert "verified max |forward diff| = 0.0" in out


def test_imagenet_task_compiles_tiny():
    out = run_example(
        "imagenet_experiment.py", "TrainImageNet",
        "epochs=1", "steps_per_epoch=1", "batch_size=4", "validate=False",
        "loader.dataset.num_train_examples=8",
        "loader.dataset.num_validation_examples=4",
        "loader.preprocessing.height=32", "loader.preprocessing.width=32",
        "loader.num_workers=0",
        "model.blocks_per_section=(1,1)", "model.section_features=(8,16)",
        timeout=400,
    )
    assert "epoch 1/1" in out


def test_cifar_binarynet_task():
    out = run_example(
        "cifar_experiment.py", "TrainCifar",
        "epochs=1", "steps_per_epoch=2", "batch_size=16",
        "model.features=(8,8)", "model.dense_units=(16,)",
        "loader.dataset.num_train_examples=32",
        "loader.dataset.num_validation_examples=16",
        "track_flip_ratio=True",
    )
    assert "epoch 1/1" in out


def test_latency_bench_task():
    out = run_example(
        "latency_bench.py", "LatencyBench",
        "model=Mlp", "model.hidden_units=(16,)",
        "height=8", "width=8", "channels=1", "num_classes=4",
        "chain_length=4", "rounds=2", "batch_size=2",
    )
    import json

    result = json.loads(out.strip().splitlines()[-1])
    assert result["model"] == "Mlp"
    assert result["ms_per_inference"] >= 0.0
    assert result["params_mib"] >= 0.0


def test_digits_real_data_task():
    """The offline REAL-data example: genuine handwritten digits, no
    synthetic fallback, >=85% val accuracy in two epochs through the
    subprocess CLI."""
    pytest.importorskip("sklearn")
    out = run_example(
        "digits_experiment.py", "TrainDigits",
        "epochs=2", "model.features=(16,32)", "model.dense_units=(64,)",
    )
    assert "epoch 2/2" in out
    import re

    accs = re.findall(r"val_acc=([0-9.]+)", out)
    assert accs and float(accs[-1]) >= 0.85, out[-500:]


def test_lm_long_context_example():
    """The long-context LM demo drives the TransformerLM family end to
    end (build -> DP partitioner -> flash-attention train steps) and
    reports falling loss + a throughput line."""
    # 25 steps -> loss lines at steps 10, 20, 24: enough to OBSERVE the
    # fall, not just parse a line.
    out = run_example(
        "lm_long_context.py",
        "--steps", "25", "--seq", "64", "--vocab", "53", "--layers", "2",
        "--d-model", "64", "--heads", "2", "--batch", "4",
    )
    assert "TransformerLM: 2L d64 h2 s64" in out
    assert "tokens/s" in out
    losses = [
        float(line.split("loss=")[1].split()[0])
        for line in out.splitlines()
        if "loss=" in line
    ]
    assert len(losses) >= 2, out
    assert losses[-1] < losses[0], losses


def test_lm_task_cli():
    """The config-system-native LM flow: TrainLM from the task CLI with
    scoped seq_len inheritance wiring dataset windows, preprocessing
    input_shape, and (via the -1 default) the model's positional table
    from ONE knob."""
    out = run_example(
        "lm_experiment.py", "TrainLM",
        "epochs=3", "seq_len=32", "batch_size=16",
        "loader.dataset.num_train_examples=128",
        "loader.dataset.vocab_size=31",
        "model.num_layers=2", "model.d_model=64", "model.num_heads=2",
    )
    assert "TrainLM" in out
    accs = [
        float(line.split("val_acc=")[1].split()[0])
        for line in out.splitlines()
        if "val_acc=" in line
    ]
    assert len(accs) == 3
    assert accs[-1] > accs[0], accs
    assert accs[-1] > 0.5, accs  # memorizable corpus, chance ~1/31


def test_lm_task_cli_sequence_parallel():
    """The dp x sp recipe straight from the CLI (the last code-not-
    config seam, closed): partitioner=SequenceParallelPartitioner
    partitioner.sp=2 trains the LM on the subprocess's 2 virtual
    devices — partitioner-owned mesh, injected ring-flash attention,
    loss falling like the single-device run's."""
    out = run_example(
        "lm_experiment.py", "TrainLM",
        "partitioner=SequenceParallelPartitioner", "partitioner.sp=2",
        "epochs=2", "seq_len=32", "batch_size=16",
        "loader.dataset.num_train_examples=64",
        "loader.dataset.vocab_size=31",
        "model.num_layers=2", "model.d_model=32", "model.num_heads=2",
    )
    assert "SequenceParallelPartitioner" in out
    losses = [
        float(line.split("loss=")[1].split()[0])
        for line in out.splitlines()
        if line.startswith("epoch ")
    ]
    assert len(losses) == 2, out
    assert losses[-1] < losses[0], losses


def test_serve_classifier_end_to_end(tmp_path):
    """The full inference half of the north star from the CLI: train +
    export the digits model, then serve the validation split through the
    dynamic-batching engine — batched serving must score what training
    shipped, with zero recompiles after warmup."""
    pytest.importorskip("sklearn")
    export = str(tmp_path / "digits_model")
    out = run_example(
        "digits_experiment.py", "TrainDigits",
        "epochs=2", "model.features=(16,32)", "model.dense_units=(64,)",
        f"export_model_to='{export}'",
    )
    assert "epoch 2/2" in out
    import json
    import re

    accs = re.findall(r"val_acc=([0-9.]+)", out)
    assert accs, out[-500:]
    trained_acc = float(accs[-1])

    out = run_example(
        "serve_classifier.py", "ServeDigits",
        f"checkpoint='{export}'",
        "model.features=(16,32)", "model.dense_units=(64,)",
        "engine.batch_buckets=(1,8,32)",
    )
    result = json.loads(out.strip().splitlines()[-1])
    assert result["recompiles_after_warmup"] == 0
    assert result["compiles"] == 3
    # Serving the exported weights through the batcher reproduces the
    # trained model's quality (row-exact batching; the small tolerance
    # covers the training-side eval dropping the remainder batch while
    # serving scores every example).
    assert result["accuracy"] >= 0.85, result
    assert abs(result["accuracy"] - trained_acc) < 0.05, (result, trained_acc)
    assert result["examples"] == 359  # full validation split coverage
    assert result["latency_p50_ms"] > 0.0


def test_serve_lm_fresh_init_smoke():
    """The decode subsystem from its CLI: fresh-init weights, a real
    continuous-batching serve (requests > slots => slot refills), one
    JSON result line with the decode metrics family, zero recompiles
    after warmup."""
    import json

    out = run_example(
        "serve_lm.py", "ServeLM",
        "model.num_layers=2", "model.d_model=32", "model.num_heads=4",
        "model.attention=dense", "seq_len=64", "vocab_size=50",
        "engine.slots=2", "engine.seq_buckets=(8,)",
        "requests=5", "max_prompt=8", "new_tokens=4",
    )
    result = json.loads(out.strip().splitlines()[-1])
    assert result["recompiles_after_warmup"] == 0
    assert result["compiles"] == 2  # one prefill bucket pair + decode
    assert result["requests"] == 5
    assert result["generated_tokens"] == 5 * 4
    assert result["tokens_per_sec"] > 0
    assert result["ttft_p99_ms"] > 0
    assert result["token_p50_ms"] > 0


def test_train_then_serve_lm_end_to_end(tmp_path):
    """The token-streaming north-star loop from the CLI: TrainLM into a
    checkpointer directory, then ServeLM streams generations from the
    shipped weights through the paged-KV decode engine."""
    import json

    ckpt = str(tmp_path / "lm_ckpt")
    out = run_example(
        "lm_experiment.py", "TrainLM",
        "epochs=2", "seq_len=32", "batch_size=16",
        "loader.dataset.num_train_examples=128",
        "loader.dataset.vocab_size=31",
        "model.num_layers=2", "model.d_model=64", "model.num_heads=2",
        "model.attention=dense",
        f"checkpointer.directory='{ckpt}'",
    )
    assert "epoch 2/2" in out
    out = run_example(
        "serve_lm.py", "ServeLM",
        f"checkpoint='{ckpt}'",
        "model.num_layers=2", "model.d_model=64", "model.num_heads=2",
        "model.attention=dense", "seq_len=32", "vocab_size=31",
        "engine.slots=2", "engine.seq_buckets=(8,16)",
        "requests=4", "max_prompt=8", "new_tokens=6",
    )
    result = json.loads(out.strip().splitlines()[-1])
    assert result["recompiles_after_warmup"] == 0
    assert result["requests"] == 4
    assert result["generated_tokens"] == 4 * 6
    assert result["weights"] == "auto"


def test_serve_fleet_cli_smoke():
    """The fleet topology from its CLI (docs/DESIGN.md §23): ServeFleet
    spawns a real worker process, pins the session, and the turn-2
    request reports worker-side warm ``shared_tokens`` — the
    prefix-affinity contract visible from one JSON line."""
    import json

    out = run_example(
        "serve_fleet.py", "ServeFleet",
        "replicas=1", "sessions=1", "turns=2",
        "num_layers=1", "d_model=32", "num_heads=4",
        "shared_tokens=24", "tail_tokens=8", "new_tokens=4",
        "page_size=8", "slots=2", "verbose=False",
        timeout=420,
    )
    result = json.loads(out.strip().splitlines()[-1])
    assert result["policy"] == "affinity"
    assert result["requests"] == 2
    assert result["routed_total"] == 2
    # Turn 2 re-entered the pinned replica's radix cache: an affinity
    # hit with every turn-1 full page warm on the worker side.
    assert result["affinity_hits"] == 1
    assert result["warm_shared_tokens"] == [24]
    assert result["healthy_replicas"] == 1
    assert result["rerouted"] == 0
    assert result["generated_tokens"] == 2 * 4
    assert result["tokens_per_sec"] > 0
