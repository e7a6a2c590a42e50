"""The traced rehearsals read the program's leaf spans (PR 24): each cell's
``--rehearse --trace 1`` run lists the new per-layer metrics it found."""

import json
import os
import subprocess
import sys

import pytest

from zkbench import cells

RUN = os.path.join(cells.ROOT, "benchmarks", "run.py")


def run(args, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, RUN, *args], cwd=cells.ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )


NEW_BY_TRAFFIC = {
    "train_b512": {
        "loader_assemble_ms", "loader_stage_ms", "host_mem_growth_mb_per_step",
    },
    "chat_poisson": {
        "sched_admit_p95_ms", "sched_host_self_ms.chat", "token_gap_p95_ms",
    },
    "summarize_closed": {"sched_host_self_ms.summarize"},
}


@pytest.mark.parametrize(
    "workload", cells.load_benchmark()["workloads"], ids=lambda w: w["name"]
)
def test_traced_rehearsal_lists_the_new_metrics(workload):
    """Each cell's ``--rehearse --trace 1`` run drives the program with
    the tracer on, and the readers PR 24 added find the spans and events
    they read (values are not printed: a CPU run gives no time)."""
    done = run(
        ["--workload", workload["name"], "--rehearse", "--seed",
         str(2**31 + 24), "--seconds", "2", "--trace", "1"],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert NEW_BY_TRAFFIC[workload["traffic"]] <= set(last["layer_metrics_read"])
