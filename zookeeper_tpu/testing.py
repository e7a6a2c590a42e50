"""Shared test/verification utilities.

Small helpers used by both the test suite and the driver-runnable
verification probes (``__graft_entry__.verify_onchip``) — single-sourced
here so the two cannot drift.
"""

from typing import Any, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def run_spmd_remat_trigger(n_devices: int = 8) -> None:
    """Compile-and-run a MINIMAL program known to make GSPMD log its
    "Involuntary full rematerialization" diagnostic — the positive
    control ("canary") for every SPMD-log-cleanliness certification
    (``__graft_entry__.dryrun_multichip`` and the FSDP suite).

    Single-sourced here because canary triggers ROT: two earlier,
    model-based triggers (the everything-shards QuickNet FSDP layout;
    the unpinned transformer under FSDP) stopped warning after model
    layout fixes / XLA upgrades, silently blinding whichever detector
    still used them. This trigger is the ``rules.auto_fsdp_rules``
    documented pathology with NO model code in the path: a depthwise
    conv with batch-sharded input and channel-sharded kernel, whose
    weight gradient demands a channel-sharded cotangent that GSPMD can
    reach from the batch-sharded layout only by full rematerialization.
    Empirically fires at (data >= 4, model = 2) meshes, i.e.
    ``n_devices >= 8``; if it ever stops firing, update it HERE and
    both certification legs stay in lockstep.

    NOTE: the diagnostic is an ERROR-level C++ stderr line that
    ``TF_CPP_MIN_LOG_LEVEL=3`` suppresses (a "bypasses level-3
    filtering" observation rotted with an XLA upgrade) — callers'
    environments must keep the level <= 2 for the capture to see it.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    groups = 8
    mesh = Mesh(
        np.array(jax.devices()[:n_devices]).reshape(n_devices // 2, 2),
        ("data", "model"),
    )
    x = jnp.ones((n_devices, 8, 8, groups), jnp.float32)
    k = jnp.ones((3, 3, 1, groups), jnp.float32)
    xs = NamedSharding(mesh, PartitionSpec("data"))
    ks = NamedSharding(mesh, PartitionSpec(None, None, None, "model"))

    def loss(x, k):
        y = jax.lax.conv_general_dilated(
            x, k, (2, 2), "SAME", feature_group_count=groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return (y * y).sum()

    jax.jit(jax.grad(loss, argnums=1), in_shardings=(xs, ks))(
        jax.device_put(x, xs), jax.device_put(k, ks)
    ).block_until_ready()


def randomize_bn_variables(
    params: Mapping[str, Any],
    batch_stats: Mapping[str, Any],
    rng: np.random.Generator,
) -> Tuple[dict, dict]:
    """Return (params, batch_stats) copies with every BatchNorm's affine
    and running stats randomized (recursively — some model families nest
    block scopes).

    Fresh-init BN is mean=0/var=1/scale=1/bias=0, which makes any check
    of BN-dependent transforms (e.g. fold-at-conversion exactness) a
    near-identity, near-vacuous comparison; jittering gives the check
    something non-trivial to verify. Ranges keep var positive and values
    O(1).
    """

    def jitter(tree, low, high):
        return jax.tree.map(
            lambda x: jnp.asarray(
                rng.uniform(low, high, np.shape(x)), jnp.float32
            ),
            tree,
        )

    def walk_params(node):
        out = {}
        for k, v in node.items():
            if k.startswith("BatchNorm"):
                out[k] = {
                    "scale": jitter(v["scale"], 0.5, 1.5),
                    "bias": jitter(v["bias"], -0.3, 0.3),
                }
            elif isinstance(v, Mapping):
                out[k] = walk_params(v)
            else:
                out[k] = v
        return out

    def walk_stats(node):
        out = {}
        for k, v in node.items():
            if k.startswith("BatchNorm"):
                out[k] = {
                    "mean": jitter(v["mean"], -0.5, 0.5),
                    "var": jitter(v["var"], 0.5, 2.0),
                }
            elif isinstance(v, Mapping):
                out[k] = walk_stats(v)
            else:
                out[k] = v
        return out

    return walk_params(dict(params)), walk_stats(dict(batch_stats))


def run_group_chaos_worker(
    process_id: int,
    num_processes: int,
    coordinator_address: str,
    out_path: str,
    workdir: str,
) -> None:
    """One host of the multi-process fault-tolerance chaos leg
    (docs/DESIGN.md §19). Spawned as a real OS process by
    ``__graft_entry__.dryrun_multiprocess`` and
    ``tests/resilience/test_multiprocess_chaos.py`` — N of these form a
    jax cluster and walk, with REAL process boundaries:

    1. the per-host sharded checkpoint protocol: a committed step
       round-trips bit-exactly (a genuinely cross-process-sharded leaf
       included), and a ``fail_host_finalize`` step — one host dies
       between shard write and finalize — is never restored by ANY
       host (commit record absent => invisible);
    2. coordinated group recovery: ``kill_process_at_step`` on host 1
       mid-epoch under ``unroll > 1`` drains and saves EVERY host at
       one agreed boundary, the group supervisors restart together,
       restore agrees on the step, and the final params are
       BIT-IDENTICAL to an uninterrupted run of the same config.

    Writes one JSON result document; the parent asserts on it.
    """
    import hashlib
    import json
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")
    from zookeeper_tpu.parallel import initialize_distributed

    initialize_distributed(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_index() == process_id
    assert jax.process_count() == num_processes

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.resilience import (
        FaultPlan,
        FileCoordinator,
        faults,
        run_with_recovery,
    )
    from zookeeper_tpu.training import (
        Checkpointer,
        TrainingExperiment,
        TrainState,
    )

    results = {"process_id": process_id, "ok": False}

    # -- leg 1: per-host sharded checkpoint protocol ----------------------
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    n_global = len(jax.devices())

    def tiny_state(value: float, step: int) -> TrainState:
        # One leaf genuinely sharded ACROSS the process boundary (each
        # host saves only its half, assembled from process-local rows
        # like the data pipeline's global batches) + host-local leaves.
        full = (
            np.arange(n_global * 4, dtype=np.float32).reshape(n_global, 4)
            * value
        )
        rows = n_global // num_processes
        w = jax.make_array_from_process_local_data(
            NamedSharding(mesh, PartitionSpec("data", None)),
            full[process_id * rows : (process_id + 1) * rows],
        )
        state = TrainState.create(
            apply_fn=lambda *a, **k: None,
            params={"w": w, "b": jnp.full((3,), value, jnp.float32)},
            model_state={},
            tx=optax.sgd(0.1),
        )
        return state.replace(step=jnp.asarray(step))

    ck = Checkpointer()
    configure(
        ck,
        {
            "directory": os.path.join(workdir, "ckpt_proto"),
            "sharded_per_host": True,
            "synchronous": True,
            "save_every_epochs": 0,
            "host_commit_timeout_s": 10.0,
        },
        name="ck_proto",
    )
    assert ck.save(tiny_state(1.0, 1), step=1)
    # Non-zero hosts return once THEIR half is durable; the commit
    # record is process 0's job and lands within its save call — poll
    # briefly so the assertion doesn't race it.
    import time as _time

    deadline = _time.monotonic() + 30
    while ck.latest_step() != 1 and _time.monotonic() < deadline:
        _time.sleep(0.05)
    results["sharded_latest_committed"] = ck.latest_step()
    with faults.injected(FaultPlan(fail_host_finalize=1)):
        torn_saved = ck.save(tiny_state(2.0, 2), step=2)
    # Host 1 dropped its finalize; host 0 timed out waiting — the step
    # has no commit record, so it must be invisible to EVERY host.
    results["torn_step_saved"] = bool(torn_saved)
    results["latest_after_torn"] = ck.latest_step()
    restored = ck.restore_state(tiny_state(0.0, 0))
    results["restored_step"] = int(jax.device_get(restored.step))
    shard_ok = True
    for shard in restored.params["w"].addressable_shards:
        want = (
            np.arange(n_global * 4, dtype=np.float32).reshape(n_global, 4)
        )[shard.index]
        shard_ok &= np.array_equal(np.asarray(shard.data), want)
    results["restored_shards_exact"] = bool(shard_ok)
    results["w_cross_process"] = not restored.params[
        "w"
    ].is_fully_addressable

    # -- leg 2: coordinated group recovery, bit-identical resume ---------
    def build_experiment(ckpt_dir):
        exp = TrainingExperiment()
        conf = {
            "loader.dataset": "SyntheticMnist",
            "loader.dataset.num_train_examples": 64,
            "loader.dataset.num_validation_examples": 0,
            "loader.preprocessing": "ImageClassificationPreprocessing",
            "loader.preprocessing.height": 28,
            "loader.preprocessing.width": 28,
            "loader.preprocessing.channels": 1,
            "model": "Mlp",
            "model.hidden_units": (8,),
            "partitioner": "DataParallelPartitioner",
            "batch_size": 16,
            # 4 steps/epoch x 4 epochs: the injected kill at step 3
            # drains the group at the deterministic stop boundary
            # (origin boundary 4 + the drain margin 8 = step 12), and
            # the restored group still has a real epoch to retrain —
            # the resume path is exercised, not just the restart.
            "epochs": 4,
            "unroll": 2,
            "validate": False,
            "verbose": False,
        }
        if ckpt_dir is not None:
            conf.update(
                {
                    "checkpointer.directory": ckpt_dir,
                    "checkpointer.sharded_per_host": True,
                    "checkpointer.synchronous": True,
                    "checkpointer.save_every_epochs": 0,
                    "checkpointer.host_commit_timeout_s": 30.0,
                }
            )
        configure(exp, conf, name=f"exp_{os.path.basename(str(ckpt_dir))}")
        return exp

    def params_digest(state) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(state.params):
            h.update(np.asarray(leaf.addressable_shards[0].data).tobytes())
        return h.hexdigest()

    oracle = build_experiment(None)
    assert oracle.partitioner.process_span() == num_processes
    oracle.run()
    oracle_digest = params_digest(oracle.final_state)
    results["oracle_digest"] = oracle_digest

    chaos = build_experiment(os.path.join(workdir, "ckpt_chaos"))
    coordinator = FileCoordinator(
        os.path.join(workdir, "group_coord"),
        process_id,
        num_processes,
        timeout_s=120.0,
    )
    with faults.injected(FaultPlan(kill_process_at_step={1: 3})):
        recovery = run_with_recovery(
            chaos,
            coordinator=coordinator,
            max_restarts=2,
            backoff_s=0.0,
            sleep=lambda s: None,
        )
    results["restarts"] = int(recovery.restarts)
    results["chaos_digest"] = params_digest(chaos.final_state)
    results["bit_identical"] = results["chaos_digest"] == oracle_digest
    results["group_restore_ms"] = (
        recovery.restore_ms[-1] if recovery.restore_ms else None
    )
    results["ok"] = True
    with open(out_path, "w") as f:
        json.dump(results, f)


def run_fleet_worker(
    worker_id: str,
    ready_path: str,
    workdir: str,
    config_json: str = "{}",
) -> None:
    """One replica of the fleet-serving topology (docs/DESIGN.md §23).
    Spawned as a real OS process by :func:`spawn_fleet_workers`: builds
    a paged-KV ``LMServingConfig`` (radix prefix cache ON — the warm
    path the router's affinity protects), serves ``POST /generate``
    over stdlib HTTP (JSON ``{tokens, max_new_tokens, rid, session}``
    in, ``{rid, tokens, ttft_ms, shared_tokens, ...}`` out — the
    scheduler ADOPTS the router-minted rid), and exposes the usual
    live ``/metrics`` + ``/statusz`` + ``/healthz`` on an ephemeral
    ObservabilityServer port. Writes a ready document (worker_id, pid,
    generate_port, metrics_port) atomically once serving.
    """
    import json
    import os
    import threading
    import time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import jax

    # CPU by construction: the spawning parent may hold the chip, and a
    # chip belongs to one process — a worker that reached for it would
    # fail or hang. Whatever a fleet of these measures is a CPU number.
    jax.config.update("jax_platforms", "cpu")

    from zookeeper_tpu.core import configure
    from zookeeper_tpu.parallel.distributed import enable_compile_cache
    from zookeeper_tpu.resilience import faults
    from zookeeper_tpu.serving import LMServingConfig

    enable_compile_cache()

    overrides = json.loads(config_json)
    # Chaos seam: a "faults" key in the worker config installs a
    # FaultPlan IN THIS PROCESS (plans are process-local — the router's
    # plan cannot reach across the OS boundary). Every worker receives
    # the same plan and fires only its own coordinate keys, the
    # kill_process_at_step discipline.
    fault_conf = overrides.pop("faults", None)
    if fault_conf:
        faults.install(faults.FaultPlan(**fault_conf))
    conf = {
        "model.num_layers": 2,
        "model.d_model": 64,
        "model.num_heads": 4,
        "model.max_seq_len": 128,
        "model.attention": "dense",
        "seq_len": 128,
        "vocab_size": 61,
        "seed": 0,
        "engine.page_size": 16,
        "engine.slots": 4,
        "engine.seq_buckets": (16, 128),
        "engine.prefill_buckets": (1,),
        "requests": 0,
        "verbose": False,
        "metrics_port": 0,
    }
    conf.update(overrides)
    svc = LMServingConfig()
    configure(svc, conf, name=f"fleet_worker_{worker_id}")
    engine, scheduler = svc.build_service()
    # One generation at a time per replica: the router's load terms
    # (outstanding + queue depth) stay meaningful and the CPU test
    # topology stays deterministic.
    gen_lock = threading.Lock()
    stop = threading.Event()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):  # silence per-request stderr
            pass

        def _send(self, code, doc):
            body = json.dumps(doc).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            if self.path == "/shutdown":
                self._send(200, {"ok": True})
                stop.set()
                return
            if self.path != "/generate":
                self._send(404, {"error": f"no route {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(n).decode())
                plan = faults.active()
                if plan is not None:
                    # Gray-failure injection (docs/DESIGN.md §24):
                    # stall the forward path, stay alive. /healthz on
                    # the ObservabilityServer keeps answering — only a
                    # latency-watching breaker can see this.
                    delay = plan.take_delay_forward(worker_id)
                    if delay:
                        time.sleep(delay / 1e3)
                with gen_lock:
                    stream = scheduler.submit(
                        np.asarray(req["tokens"], np.int32),
                        max_new_tokens=int(
                            req.get("max_new_tokens") or 16
                        ),
                        rid=req.get("rid"),
                    )
                    out = stream.result(timeout=300.0)
                self._send(
                    200,
                    {
                        "rid": stream.rid,
                        "worker_id": worker_id,
                        "tokens": [int(x) for x in out.tolist()],
                        "ttft_ms": stream.ttft_ms,
                        "shared_tokens": int(stream.shared_tokens),
                        "finish_reason": stream.finish_reason,
                    },
                )
            except Exception as e:  # surfaced to the router as 400
                self._send(
                    400, {"error": str(e), "type": type(e).__name__}
                )

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    serve_thread = threading.Thread(
        target=httpd.serve_forever, daemon=True
    )
    serve_thread.start()
    doc = {
        "worker_id": worker_id,
        "pid": os.getpid(),
        "generate_port": httpd.server_address[1],
        "metrics_port": svc.obs_server.port,
    }
    tmp = ready_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, ready_path)
    try:
        stop.wait()
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc._teardown_service(suppress=True)


def spawn_fleet_workers(
    workdir: str,
    num_workers: int = 2,
    config: dict = None,
    timeout_s: float = 300.0,
):
    """Spawn ``num_workers`` real OS processes running
    :func:`run_fleet_worker` ON THE CPU (``JAX_PLATFORMS=cpu`` in the
    child environment: the parent may hold the chip, and a chip belongs
    to one process) and wait for every ready file; returns
    the ready documents (feed them to
    ``zookeeper_tpu.serving.fleet.ReplicaHandle.from_worker``). Raises
    with the worker's log tail when any process dies before ready —
    shared by ``tests/serving/test_fleet.py``, the CI scrape smoke and
    the ``ZK_BENCH_FLEET`` bench leg so the three cannot drift."""
    import json
    import os
    import subprocess
    import sys
    import time

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    config_json = json.dumps(config or {})
    procs = []
    for w in range(num_workers):
        worker_id = f"w{w}"
        ready = os.path.join(workdir, f"ready_{worker_id}.json")
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": repo_root
                + (
                    os.pathsep + os.environ["PYTHONPATH"]
                    if os.environ.get("PYTHONPATH")
                    else ""
                ),
                "TPU_SKIP_MDS_QUERY": "1",
            }
        )
        code = (
            "import sys; from zookeeper_tpu.testing import "
            "run_fleet_worker; run_fleet_worker("
            "sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4])"
        )
        # Log to files, not pipes: a full pipe buffer would stall the
        # worker's HTTP loop (the group-chaos lesson).
        log_path = os.path.join(workdir, f"fleet_log_{worker_id}.txt")
        log_f = open(log_path, "wb")
        p = subprocess.Popen(
            [
                sys.executable,
                "-c",
                code,
                worker_id,
                ready,
                workdir,
                config_json,
            ],
            env=env,
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        log_f.close()
        procs.append((p, worker_id, ready, log_path))
    workers = []
    deadline = time.monotonic() + timeout_s
    try:
        for p, worker_id, ready, log_path in procs:
            while True:
                if os.path.exists(ready):
                    with open(ready) as f:
                        workers.append(json.load(f))
                    break
                if p.poll() is not None:
                    with open(log_path, errors="replace") as f:
                        log = f.read()
                    raise RuntimeError(
                        f"fleet worker {worker_id} died before ready "
                        f"(rc={p.returncode}):\n" + log[-4000:]
                    )
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"fleet worker {worker_id} not ready within "
                        f"{timeout_s:.0f}s; log: {log_path}"
                    )
                time.sleep(0.1)
    except BaseException:
        for p, *_ in procs:
            if p.poll() is None:
                p.kill()
        raise
    return workers


def stop_fleet_workers(workers, timeout_s: float = 30.0) -> None:
    """Graceful teardown for :func:`spawn_fleet_workers` output: POST
    ``/shutdown`` to every live worker, then SIGKILL stragglers.
    Already-dead workers (chaos legs kill them) are skipped silently.
    """
    import os
    import signal
    import time
    import urllib.error
    import urllib.request

    for w in workers:
        try:
            urllib.request.urlopen(
                urllib.request.Request(
                    "http://127.0.0.1:%d/shutdown" % w["generate_port"],
                    data=b"{}",
                ),
                timeout=5,
            )
        except (urllib.error.URLError, OSError):
            pass
    deadline = time.monotonic() + timeout_s
    for w in workers:
        pid = w.get("pid")
        if pid is None:
            continue
        # Reap (we are the parent): WNOHANG-poll until exit, then
        # SIGKILL stragglers. Chaos-killed workers are zombies until
        # this waitpid — reaping here keeps repeated spawns clean.
        while True:
            try:
                done, _ = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                break  # already reaped / not ours
            if done == pid:
                break
            if time.monotonic() > deadline:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
                try:
                    os.waitpid(pid, 0)
                except ChildProcessError:
                    pass
                break
            time.sleep(0.1)


def spawn_group_chaos_cluster(workdir: str, num_processes: int = 2):
    """Spawn ``num_processes`` OS processes running
    :func:`run_group_chaos_worker` as one jax CPU cluster
    (``JAX_PLATFORMS=cpu``, one virtual device each — CPU by
    construction, whatever the parent holds); wait for them
    and return the per-process result dicts. Raises with the worker's
    log tail when any process fails — shared by the pytest leg and
    ``__graft_entry__.dryrun_multiprocess`` so the two cannot drift."""
    import json
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    procs, out_paths = [], []
    for pid in range(num_processes):
        out = os.path.join(workdir, f"out_{pid}.json")
        out_paths.append(out)
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": repo_root
                + (
                    os.pathsep + os.environ["PYTHONPATH"]
                    if os.environ.get("PYTHONPATH")
                    else ""
                ),
                "TPU_SKIP_MDS_QUERY": "1",
            }
        )
        code = (
            "import sys; from zookeeper_tpu.testing import "
            "run_group_chaos_worker; run_group_chaos_worker("
            "int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], "
            "sys.argv[4], sys.argv[5])"
        )
        # Log to files, not pipes: a full pipe buffer on one worker
        # while the other waits in a collective would deadlock.
        log_path = os.path.join(workdir, f"log_{pid}.txt")
        with open(log_path, "wb") as log_f:
            procs.append(
                (
                    subprocess.Popen(
                        [
                            sys.executable,
                            "-c",
                            code,
                            str(pid),
                            str(num_processes),
                            coordinator,
                            out,
                            workdir,
                        ],
                        env=env,
                        stdout=log_f,
                        stderr=subprocess.STDOUT,
                    ),
                    log_path,
                )
            )
    try:
        for p, _ in procs:
            p.wait(timeout=600)
    finally:
        for p, _ in procs:
            if p.poll() is None:
                p.kill()
    for p, log_path in procs:
        with open(log_path, errors="replace") as f:
            log = f.read()
        if p.returncode != 0:
            raise RuntimeError(
                f"group chaos worker failed (rc={p.returncode}):\n"
                + log[-4000:]
            )
    results = []
    for path in out_paths:
        with open(path) as f:
            results.append(json.load(f))
    return results
