"""``run.py`` as a program: it refuses to run without a TPU, prints no
result then, and resolves a cell, a configuration and a per-layer metric
that were added as new files only."""

import json
import os
import shutil
import subprocess
import sys


from zkbench import cells

ROOT = cells.ROOT
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = cells.load_benchmark()["workloads"][0]["name"]


def run(args, env_extra=None, cwd=ROOT, script=RUN, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{") and '"correct"' in line:
            return True
    return False


def test_no_tpu_no_result():
    done = run(
        ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert not has_result_line(done.stdout)
    assert "TPU" in done.stderr


def test_rehearsal_needs_the_cpu_asked_for_explicitly():
    done = run(["--workload", CELL, "--rehearse", "--seconds", "1"])
    assert done.returncode != 0 and not has_result_line(done.stdout)


def test_unknown_cell_no_result():
    done = run(["--workload", "no.such_cell"], {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0 and not has_result_line(done.stdout)


def copy_benchmark(tmp_path, with_program: bool):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    ignore = shutil.ignore_patterns("__pycache__", "*.pyc")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks", ignore=ignore)
    os.makedirs(root / "tests")
    shutil.copytree(os.path.join(ROOT, "tests", "benchmarks"), root / "tests" / "benchmarks", ignore=ignore)
    if with_program:
        for name in ("zookeeper_tpu", "examples"):
            os.symlink(os.path.join(ROOT, name), root / name)
    return root


def test_alone_in_a_directory_no_result(tmp_path):
    root = copy_benchmark(tmp_path, with_program=False)
    done = run(
        ["--workload", CELL, "--rehearse", "--seconds", "1"],
        {"JAX_PLATFORMS": "cpu"}, cwd=root,
        script=str(root / "benchmarks" / "run.py"),
    )
    assert done.returncode != 0 and not has_result_line(done.stdout)


def test_a_cell_a_config_and_a_metric_added_as_new_files_only(tmp_path):
    """What a later PR does: new files and new entries, no edit of a file
    that is there. ``run.py`` resolves all three and runs the new cell."""
    root = copy_benchmark(tmp_path, with_program=True)
    bench_dir = root / "benchmarks"
    before = {
        str(p.relative_to(root)): p.read_bytes()
        for p in bench_dir.rglob("*") if p.is_file()
    }
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    chat = next(w for w in benchmark["workloads"] if w["traffic"] == "chat_poisson")
    old_config = next(c for c in benchmark["configs"] if c["name"] == chat["config"])

    # a new configuration: its own file of sizes
    config = json.loads((root / old_config["file"]).read_text())
    config["name"] = "gpt2_xl_24l_wide_slots"
    config["rehearsal"]["program"]["engine.slots"] = 6
    new_config_file = "benchmarks/configs/gpt2_xl_24l_wide_slots.json"
    (root / new_config_file).write_text(json.dumps(config))
    benchmark["configs"].append(dict(old_config, name=config["name"], file=new_config_file))

    # a new traffic mix: a data file of parameters
    mix = json.loads((bench_dir / "traffic" / "chat_poisson.json").read_text())
    mix["rehearsal"]["arrivals"]["rate_per_s"] = 7.0
    (bench_dir / "traffic" / "chat_fast.json").write_text(json.dumps(mix))

    # a new per-layer metric: a spec and a small reader of its own
    (bench_dir / "layer_metrics" / "requests_seen.json").write_text(json.dumps({
        "layer": "serving scheduler", "unit": "requests", "moves": "itl_p95_ms",
        "source": "program_span", "what": "enqueue events in the traced window",
    }))
    (bench_dir / "layer_metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n"
        "    n = sum(1 for r in ctx['spans'] if r['name'] == 'decode_request_enqueue')\n"
        "    return n or None\n"
    )
    benchmark["workloads"].append({
        "name": "gpt2_xl_wide.chat_fast", "config": config["name"],
        "traffic": "chat_fast", "chips": 1, "why": "added by a test",
    })
    for metric in benchmark["end_to_end"] + benchmark["per_layer"]:
        if chat["name"] in metric.get("workloads", []):
            metric["workloads"].append("gpt2_xl_wide.chat_fast")
    benchmark["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_span", "layer": "serving scheduler",
        "moves": "itl_p95_ms", "workloads": ["gpt2_xl_wide.chat_fast"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))

    cell = cells.Cell("gpt2_xl_wide.chat_fast", str(root))
    assert cell.config["name"] == config["name"]
    assert cell.traffic["rehearsal"]["arrivals"]["rate_per_s"] == 7.0
    assert "requests_seen" in [m["name"] for m in cell.per_layer]
    spec, reader = cell.layer_metric("requests_seen")
    assert reader.read({"spans": [{"name": "decode_request_enqueue"}]}) == 1

    done = run(
        ["--workload", "gpt2_xl_wide.chat_fast", "--rehearse", "--seed",
         str(2**31 + 99), "--seconds", "2", "--trace", "1"],
        {"JAX_PLATFORMS": "cpu"}, cwd=root,
        script=str(root / "benchmarks" / "run.py"),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last and "device" not in last
    # 7 a second for 2 s are generated; a traced run ends with its slice
    assert 0 < last["attempted"] <= 14 and last["failed"] == 0
    assert "requests_seen" in last["layer_metrics_read"]

    # nothing that was there was edited
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, rel
