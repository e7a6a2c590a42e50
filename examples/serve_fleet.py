"""Fleet serving: a prefix-affinity router over real worker processes.

The multi-replica half of the serving story (docs/DESIGN.md §23): a
:class:`~zookeeper_tpu.serving.FleetRouter` fronts N OS processes, each
running the full paged-KV ``LMServingConfig`` decode engine behind
``POST /generate`` with live ``/metrics`` + ``/statusz`` + ``/healthz``.
The router mirrors every replica's radix prefix cache in a process-local
``PrefixIndex`` (the SAME chunk keying, via
``zookeeper_tpu.serving.decode.prefix_key``) and sends each request to
the replica whose cache already holds the longest prefix — so a
session's turn-2 history re-enters the warm §20 prefill path instead of
re-prefilling cold on whichever box round-robin picked.

The worker processes run ON THE CPU whatever device this process sees
(``spawn_fleet_workers`` sets ``JAX_PLATFORMS=cpu`` for them: a chip
belongs to one process, so children of a parent that may hold it cannot
have it). The result line's counts are exact; any time it reports is a
CPU time. The fleet on four chips is ROADMAP R4.

This task drives a deterministic multi-turn stream (S sessions x T
turns, each turn extending the last) through a freshly spawned fleet
and reports routing + warm-path outcomes as one JSON line::

    # 2 replicas, 3 sessions x 2 turns (defaults):
    python examples/serve_fleet.py ServeFleet

    # Tiny smoke geometry (what the CLI test runs):
    python examples/serve_fleet.py ServeFleet replicas=1 sessions=1 \\
        num_layers=1 d_model=32 shared_tokens=24 new_tokens=4

    # The no-affinity baseline for an A/B (expect affinity_hits=0 and
    # cold turn-2 warm_shared_tokens):
    python examples/serve_fleet.py ServeFleet policy=round_robin

    # A live router /metrics + /statusz endpoint (zk_fleet_* series,
    # "fleet" statusz section) while the stream runs:
    python examples/serve_fleet.py ServeFleet metrics_port=8080

The result line's contract: ``affinity_hits > 0`` and every
``warm_shared_tokens`` entry positive under ``policy=affinity`` with
``turns >= 2`` — the router kept sessions on their warm replica; the
same stream is token-deterministic regardless of policy (routing is a
latency policy, never a correctness input — the §23 identity the fleet
test suite and ``ZK_BENCH_FLEET=1`` bench leg assert end to end).
"""

import json
import shutil
import tempfile
import time

from zookeeper_tpu import cli, task
from zookeeper_tpu.core import Field
from zookeeper_tpu.serving import FleetRouter, ReplicaHandle
from zookeeper_tpu.testing import spawn_fleet_workers, stop_fleet_workers
from zookeeper_tpu.training.experiment import Experiment


@task
class ServeFleet(Experiment):
    """Route a deterministic multi-turn session stream through a
    freshly spawned multi-process fleet (docs/DESIGN.md §23)."""

    # Fleet topology + routing policy.
    replicas: int = Field(2)
    policy: str = Field("affinity")  # or "round_robin"
    # Workload shape: sessions x turns, turn t+1 = turn t + tail.
    sessions: int = Field(3)
    turns: int = Field(2)
    shared_tokens: int = Field(48)  # turn-1 prompt length
    tail_tokens: int = Field(8)  # appended per later turn
    new_tokens: int = Field(8)  # generation budget per turn
    # Worker model geometry (every replica runs this config).
    num_layers: int = Field(2)
    d_model: int = Field(64)
    num_heads: int = Field(4)
    vocab_size: int = Field(61)
    page_size: int = Field(16)
    slots: int = Field(4)
    seed: int = Field(0)
    # Router observability: -1 = off, 0 = ephemeral, >0 = fixed port.
    metrics_port: int = Field(-1)
    verbose: bool = Field(True)

    def run(self):
        import numpy as np

        if self.turns < 1 or self.sessions < 1 or self.replicas < 1:
            raise ValueError(
                "ServeFleet needs replicas/sessions/turns >= 1 "
                f"(got {self.replicas}/{self.sessions}/{self.turns})."
            )
        max_prompt = (
            self.shared_tokens + (self.turns - 1) * self.tail_tokens
        )
        seq_len = max(64, 2 * (max_prompt + self.new_tokens))
        conf = {
            "model.num_layers": self.num_layers,
            "model.d_model": self.d_model,
            "model.num_heads": self.num_heads,
            "model.max_seq_len": seq_len,
            "model.attention": "dense",
            "seq_len": seq_len,
            "vocab_size": self.vocab_size,
            "seed": self.seed,
            "engine.page_size": self.page_size,
            "engine.slots": self.slots,
            "engine.seq_buckets": (16, max_prompt),
            "engine.prefill_buckets": (1,),
            "requests": 0,
            "verbose": False,
        }
        # The deterministic stream: seeded, so reruns (and the
        # round-robin A/B) see token-identical prompts.
        rng = np.random.default_rng(self.seed + 11)
        session_ids = [f"s{i}" for i in range(self.sessions)]
        prompts = {}
        for sid in session_ids:
            base = rng.integers(
                1, self.vocab_size, size=self.shared_tokens
            ).tolist()
            turn_prompts = [list(base)]
            for _ in range(self.turns - 1):
                base = base + rng.integers(
                    1, self.vocab_size, size=self.tail_tokens
                ).tolist()
                turn_prompts.append(list(base))
            prompts[sid] = turn_prompts

        workdir = tempfile.mkdtemp(prefix="zk_serve_fleet_")
        workers = spawn_fleet_workers(
            workdir, num_workers=self.replicas, config=conf
        )
        router = None
        obs = None
        try:
            router = FleetRouter(
                [ReplicaHandle.from_worker(w) for w in workers],
                page_size=self.page_size,
                policy=self.policy,
            )
            if self.metrics_port >= 0:
                obs = router.start_observability(port=self.metrics_port)
                if self.verbose:
                    print(f"router observability: {obs.url}/metrics")
            warm_shared = []
            ttft_by_turn = {t: [] for t in range(self.turns)}
            generated = 0
            t0 = time.perf_counter()
            # Turn-major: every session's turn t lands before any
            # turn t+1 — the arrival order a live fleet would see.
            for turn in range(self.turns):
                for sid in session_ids:
                    resp = router.submit(
                        prompts[sid][turn],
                        session=(
                            sid if self.policy == "affinity" else None
                        ),
                        max_new_tokens=self.new_tokens,
                    )
                    ttft_by_turn[turn].append(float(resp.ttft_ms))
                    generated += int(resp.tokens.shape[0])
                    if turn > 0:
                        warm_shared.append(int(resp.shared_tokens))
                    if self.verbose:
                        print(
                            f"  {resp.rid} session={sid} turn={turn} "
                            f"-> {resp.worker_id} "
                            f"shared={resp.shared_tokens} "
                            f"ttft={resp.ttft_ms:.2f}ms"
                        )
            dt = time.perf_counter() - t0
            snap = router.metrics.snapshot()
            status = router.status()
            result = {
                "policy": self.policy,
                "replicas": self.replicas,
                "sessions": self.sessions,
                "turns": self.turns,
                "requests": self.sessions * self.turns,
                "generated_tokens": generated,
                "tokens_per_sec": round(generated / dt, 1),
                "routed_total": status["routed_total"],
                "affinity_hits": status["affinity_hits_total"],
                "rerouted": status["rerouted_total"],
                "healthy_replicas": status["healthy_replicas"],
                "warm_shared_tokens": warm_shared,
                "turn1_ttft_p50_ms": round(
                    float(np.percentile(ttft_by_turn[0], 50)), 3
                ),
                "route_ms_p50": snap.get("fleet_route_ms_p50"),
            }
            if self.turns > 1:
                warm = [
                    x
                    for t in range(1, self.turns)
                    for x in ttft_by_turn[t]
                ]
                result["warm_ttft_p50_ms"] = round(
                    float(np.percentile(warm, 50)), 3
                )
            print(json.dumps(result))
            return result
        finally:
            # router.close() stops the obs endpoint it started.
            if router is not None:
                router.close()
            stop_fleet_workers(workers)
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    cli()
