"""Fleet-serving certification (docs/DESIGN.md §23).

Three layers, cheapest first:

1. **Keying parity** — the router's pageless ``PrefixIndex`` and the
   real ``RadixPrefixCache`` share the walk code, and the parity test
   pins predicted match == actual match across randomized prompt
   families (shared prefixes, partial tails, interleaved inserts), so
   the router's warm predictions CANNOT drift from the cache they
   predict.
2. **Router semantics** — in-process stub transports pin the routing
   policy itself: session pinning, warm-prefix affinity, load
   fallback, round-robin, clean ``WorkerCrashedError`` + cold
   re-route on replica death, state-file restart recovery, rid
   propagation, ``zk_fleet_*`` / ``/statusz`` / ``/healthz``
   exposition, and the FaultPlan chaos knobs.
3. **The real thing** (``slow``) — a router over REAL worker
   processes (each a paged-KV ``LMServingConfig`` behind HTTP):
   fleet output certified token-identical to an in-process
   single-replica oracle, turn-2 warm prefill proved by the worker's
   own ``shared_tokens``, one rid traced router → worker, and the
   replica-kill chaos leg (mid-request SIGKILL → clean failure →
   survivor finishes the session cold, still token-identical).
"""

import json
import os
import urllib.request

import numpy as np
import pytest

from zookeeper_tpu.observability import trace
from zookeeper_tpu.resilience import FaultPlan, faults
from zookeeper_tpu.serving import (
    FleetRouter,
    FleetUnavailableError,
    ReplicaHandle,
    WorkerCrashedError,
)
from zookeeper_tpu.serving.decode.pages import RadixPrefixCache
from zookeeper_tpu.serving.decode.prefix_key import (
    PrefixIndex,
    common_prefix,
)

pytestmark = pytest.mark.serving


# -- layer 1: keying parity -------------------------------------------------


def make_cache(page_size):
    """A real RadixPrefixCache with inert page plumbing (parity tests
    exercise the WALKS, not the pool)."""
    return RadixPrefixCache(
        page_size, ref=lambda p: None, unref=lambda p: None,
        evictable=lambda p: True,
    )


def pages_for(n, ps):
    return (n + ps - 1) // ps


def test_common_prefix():
    assert common_prefix([1, 2, 3], [1, 2, 4]) == 2
    assert common_prefix([], [1]) == 0
    assert common_prefix([1, 2], [1, 2]) == 2


@pytest.mark.parametrize("page_size", [1, 3, 4])
def test_prefix_index_matches_radix_cache_exactly(page_size):
    """THE parity certification: after any interleaved sequence of
    inserts, the index's predicted match length equals the real
    cache's actual match length for every probe — full-chunk hits,
    partial tails, misses, and prompts diverging mid-chunk."""
    rng = np.random.default_rng(7)
    cache = make_cache(page_size)
    index = PrefixIndex(page_size)
    bases = [rng.integers(0, 13, size=n).tolist() for n in (24, 17, 9)]
    inserted = []
    next_page = [0]

    def insert_both(tokens):
        n_pages = pages_for(len(tokens), page_size)
        pages = list(range(next_page[0], next_page[0] + n_pages))
        next_page[0] += n_pages
        cache.insert(tokens, pages)
        index.observe(tokens)
        inserted.append(tokens)

    def probe(tokens):
        t_cache, _ = cache.lookup(tokens)
        assert index.match(tokens) == t_cache, (
            f"parity broke: index predicted {index.match(tokens)}, "
            f"cache matched {t_cache} for {tokens}"
        )

    for base in bases:
        # Grow the same conversation: each turn extends the last.
        for cut in (len(base) // 2, len(base)):
            insert_both(base[:cut])
        # Diverge mid-chunk off the shared prefix.
        insert_both(base[: len(base) // 2] + [50, 51, 52])
    probes = list(inserted)
    for base in bases:
        probes.append(base + [7, 8, 9])           # past the cached end
        probes.append(base[: max(1, len(base) - 2)])  # shorter
        probes.append([60] + base)                # cold miss
        probes.append(base[: page_size + 1])      # partial-tail probe
    for p in probes:
        probe(p)
    # And random probes for good measure.
    for _ in range(50):
        probe(rng.integers(0, 14, size=int(rng.integers(1, 30))).tolist())


def test_prefix_index_predict_caps_like_assign_prompt():
    """``predict`` mirrors ``PagePool.assign_prompt``: the final
    prompt token is never served warm (its logits must be computed),
    so a fully-cached prompt predicts len - 1 shared tokens."""
    idx = PrefixIndex(4)
    p = list(range(12))
    idx.observe(p)
    assert idx.match(p) == 12
    assert idx.predict(p) == 11
    assert idx.predict([]) == 0
    assert idx.predict([99]) == 0


def test_prefix_index_caps_nodes_and_resets():
    idx = PrefixIndex(2, max_nodes=4)
    idx.observe([1, 2, 3, 4])  # 2 nodes
    assert idx.nodes == 2 and idx.resets == 0
    idx.observe([5, 6, 7, 8, 9, 10])  # 3 more -> over cap -> reset
    assert idx.resets == 1
    assert idx.nodes == 0
    assert idx.match([1, 2, 3, 4]) == 0  # cold after reset


def test_prefix_index_rejects_bad_config():
    with pytest.raises(ValueError, match="page_size"):
        PrefixIndex(0)
    with pytest.raises(ValueError, match="max_nodes"):
        PrefixIndex(4, max_nodes=0)


# -- layer 2: router semantics over stub transports -------------------------


class StubFleet:
    """In-process stand-in for N workers: echoes tokens + [7], records
    every payload, and fails like a dead socket when killed."""

    def __init__(self, n):
        self.calls = []
        self.dead = set()
        self.replicas = [
            ReplicaHandle(f"w{i}", f"stub://w{i}/generate")
            for i in range(n)
        ]

    def transport(self, replica, payload, timeout_s):
        if replica.worker_id in self.dead:
            raise ConnectionError(f"{replica.worker_id} is dead")
        self.calls.append((replica.worker_id, payload))
        return {
            "rid": payload["rid"],
            "worker_id": replica.worker_id,
            "tokens": list(payload["tokens"]) + [7],
            "ttft_ms": 1.0,
            "shared_tokens": 0,
            "finish_reason": "length",
        }

    def health(self, replica, timeout_s):
        return replica.worker_id not in self.dead

    def kill(self, replica):
        self.dead.add(replica.worker_id)


def make_router(n=2, **kw):
    stub = StubFleet(n)
    router = FleetRouter(
        stub.replicas,
        page_size=4,
        transport=stub.transport,
        health_probe=stub.health,
        kill_replica=stub.kill,
        **kw,
    )
    return router, stub


def test_router_rejects_bad_config():
    stub = StubFleet(1)
    with pytest.raises(ValueError, match="policy"):
        FleetRouter(stub.replicas, page_size=4, policy="random")
    with pytest.raises(ValueError, match="at least one"):
        FleetRouter([], page_size=4)
    with pytest.raises(ValueError, match="duplicate"):
        FleetRouter(
            [ReplicaHandle("w0", "u"), ReplicaHandle("w0", "u")],
            page_size=4,
        )


def test_session_pins_and_turn2_is_affinity_hit():
    router, stub = make_router(2)
    p1 = list(range(16))
    r1 = router.submit(p1, session="s1")
    assert not r1.affinity_hit  # cold first turn routes by load
    assert router.session_pin("s1") == r1.worker_id
    # Turn 2 (history grew) rides the pin — the warm replica.
    r2 = router.submit(p1 + [40, 41], session="s1")
    assert r2.worker_id == r1.worker_id
    assert r2.affinity_hit
    assert r2.predicted_shared == 16  # the whole cached turn-1 prompt
    np.testing.assert_array_equal(r2.tokens, p1 + [40, 41, 7])


def test_unpinned_warm_prompt_routes_by_prefix_affinity():
    router, stub = make_router(2)
    base = list(range(16))
    first = router.submit(base)
    warm = router.submit(base[:8] + [55])  # shares 2 full chunks
    assert warm.worker_id == first.worker_id
    assert warm.affinity_hit
    assert warm.predicted_shared == 8


def test_cold_prompts_fall_back_by_load():
    router, stub = make_router(2)
    router.replicas[0].outstanding = 3  # w0 busy
    cold = router.submit([90, 91, 92])
    assert cold.worker_id == "w1"
    assert not cold.affinity_hit


def test_round_robin_policy_rotates():
    router, stub = make_router(2, policy="round_robin")
    seen = [router.submit([i, i + 1, i + 2]).worker_id for i in range(4)]
    assert seen == ["w0", "w1", "w0", "w1"]


def test_dead_replica_fails_clean_then_session_reroutes_cold():
    router, stub = make_router(2)
    p1 = list(range(16))
    r1 = router.submit(p1, session="s1")
    stub.kill(router._by_id[r1.worker_id])
    # In-flight against a dead worker: clean typed failure, replica
    # marked unhealthy, crash counted, rid in the router's RequestLog.
    with pytest.raises(WorkerCrashedError, match=r1.worker_id):
        router.submit(p1 + [40], session="s1", rid=4242)
    assert not router._by_id[r1.worker_id].healthy
    rec = router.request_log.find(4242)
    assert rec is not None and rec["outcome"] == "crashed"
    assert "WorkerCrashedError" in rec["detail"]
    # The resubmit re-routes COLD to the survivor and re-pins there.
    survivor = [r for r in router.replicas if r.healthy][0]
    r3 = router.submit(p1 + [40], session="s1")
    assert r3.worker_id == survivor.worker_id
    assert r3.rerouted
    assert router.session_pin("s1") == survivor.worker_id
    snap = router.metrics.snapshot()
    assert snap["fleet_worker_crashes_total"] == 1.0
    assert snap["fleet_rerouted_total"] == 1.0


def test_all_replicas_dead_raises_fleet_unavailable():
    router, stub = make_router(2)
    for r in router.replicas:
        stub.kill(r)
    router.check_health()
    with pytest.raises(FleetUnavailableError, match="no healthy"):
        router.submit([1, 2, 3])


def test_health_probe_marks_dead_and_cold_revival():
    router, stub = make_router(2)
    base = list(range(8))
    first = router.submit(base)
    warm_replica = router._by_id[first.worker_id]
    assert warm_replica.index.nodes > 0
    stub.kill(warm_replica)
    assert router.check_health() == {
        first.worker_id: False,
        ({"w0", "w1"} - {first.worker_id}).pop(): True,
    }
    assert not warm_replica.healthy
    assert warm_replica.index.nodes == 0  # its pages died with it
    # Revival (worker restarted): healthy again but COLD.
    stub.dead.clear()
    router.check_health()
    assert warm_replica.healthy
    assert warm_replica.index.nodes == 0


def test_state_path_restores_session_pins(tmp_path):
    state = str(tmp_path / "fleet_state.json")
    router, stub = make_router(2, state_path=state)
    r1 = router.submit(list(range(12)), session="s1")
    router.submit(list(range(6)), session="other")
    # A restarted router (same replicas, same state file) keeps the
    # pins — turn-2 of every session still lands on its warm replica.
    router2 = FleetRouter(
        stub.replicas,
        page_size=4,
        state_path=state,
        transport=stub.transport,
        health_probe=stub.health,
    )
    assert router2.session_pin("s1") == r1.worker_id
    r2 = router2.submit(list(range(12)) + [40], session="s1")
    assert r2.worker_id == r1.worker_id and r2.affinity_hit
    # Pins for replicas that no longer exist are dropped, not adopted.
    with open(state, "w") as f:
        json.dump({"sessions": {"ghost": "w9", "s1": r1.worker_id}}, f)
    router3 = FleetRouter(
        stub.replicas, page_size=4, state_path=state,
        transport=stub.transport,
    )
    assert router3.session_pin("ghost") is None
    assert router3.session_pin("s1") == r1.worker_id


def test_rid_propagates_and_router_logs_ok():
    router, stub = make_router(1)
    resp = router.submit([1, 2, 3], rid=991)
    assert resp.rid == 991
    assert stub.calls[-1][1]["rid"] == 991  # the worker ADOPTS it
    rec = router.request_log.find(991)
    assert rec is not None and rec["outcome"] == "ok"
    assert rec["role"] == "router"
    assert "replica=w0" in rec["detail"]


def test_fleet_route_emits_flow_traceable_event():
    prior = trace._TRACER
    trace.install(trace.Tracer(1024))
    try:
        router, stub = make_router(1)
        router.submit([1, 2, 3, 4], rid=5005)
        doc = trace.to_chrome_trace()
        routes = [
            e for e in doc["traceEvents"]
            if e.get("name") == "fleet_route"
        ]
        assert routes, "no fleet_route event in the trace"
        assert routes[0]["args"]["rid"] == 5005
        assert routes[0]["args"]["replica"] == "w0"
    finally:
        trace.install(prior)


def test_worker_error_body_raises_with_type():
    router, stub = make_router(1)

    def bad_transport(replica, payload, timeout_s):
        return {"error": "prompt too long", "type": "ValueError"}

    router._transport = bad_transport
    with pytest.raises(RuntimeError, match="ValueError: prompt too long"):
        router.submit([1, 2, 3], rid=17)
    rec = router.request_log.find(17)
    assert rec["outcome"] == "error"


def test_router_observability_endpoint(tmp_path):
    router, stub = make_router(2)
    router.submit(list(range(8)), session="s1")
    server = router.start_observability(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.read() == b"ok\n"
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            body = r.read().decode()
        for series in (
            "zk_fleet_routed_total",
            "zk_fleet_rerouted_total",
            "zk_fleet_worker_crashes_total",
            "zk_fleet_replica_healthy",
            "zk_fleet_replicas",
            "zk_fleet_sessions",
            "zk_fleet_route_ms",
        ):
            assert series in body, f"missing {series} in /metrics"
        assert 'replica="w0"' in body
        with urllib.request.urlopen(base + "/statusz", timeout=5) as r:
            doc = json.loads(r.read().decode())
        fleet = doc["fleet"]
        assert fleet["policy"] == "affinity"
        assert fleet["sessions"] == 1
        assert {x["worker_id"] for x in fleet["replicas"]} == {"w0", "w1"}
        assert doc["requests"]["service"] == "fleet"
    finally:
        router.close()
    assert router.obs_server is None


# -- FaultPlan chaos knobs --------------------------------------------------


@pytest.mark.chaos
def test_fault_plan_fleet_replica_kill_fires_once_at_nth_route():
    router, stub = make_router(2)
    with faults.injected(FaultPlan(fleet_replica_kill_at=2)):
        first = router.submit(list(range(8)), session="s1")  # 1st: safe
        with pytest.raises(WorkerCrashedError):
            router.submit(list(range(8)) + [40], session="s1")  # 2nd: kill
        # One-shot: the next submit routes to the survivor and works.
        r3 = router.submit(list(range(8)) + [40], session="s1")
    assert r3.worker_id != first.worker_id
    assert r3.rerouted
    assert stub.dead == {first.worker_id}


@pytest.mark.chaos
def test_fault_plan_router_restart_knob_is_one_shot():
    plan = FaultPlan(fleet_router_restart_at=2)
    with faults.injected(plan):
        assert not plan.take_fleet_router_restart()
        assert plan.take_fleet_router_restart()  # fires at the 2nd
        assert not plan.take_fleet_router_restart()  # one-shot


# -- layer 3: real multi-process certification (slow) -----------------------

# Tiny but REAL geometry shared by the workers (spawned processes) and
# the in-process oracle: same seed => identical fresh-init weights =>
# greedy decode is token-identical wherever a request lands.
FLEET_CONF = {
    "model.num_layers": 1,
    "model.d_model": 32,
    "model.num_heads": 4,
    "model.max_seq_len": 64,
    "model.attention": "dense",
    "seq_len": 64,
    "vocab_size": 61,
    "seed": 0,
    "engine.page_size": 8,
    "engine.slots": 2,
    "engine.seq_buckets": (16, 64),
    "engine.prefill_buckets": (1,),
    "requests": 0,
    "verbose": False,
}

NEW_TOKENS = 6


def fleet_prompts():
    """Deterministic 2-session, 2-turn conversation set: turn 2
    extends turn 1's prompt (the history-grows shape whose warm
    prefill the router's affinity protects)."""
    rng = np.random.default_rng(3)
    sessions = {}
    for sid in ("sA", "sB"):
        t1 = rng.integers(1, 60, size=20).tolist()
        t2 = t1 + rng.integers(1, 60, size=9).tolist()
        sessions[sid] = [t1, t2]
    return sessions


def oracle_outputs(sessions):
    """Single-replica in-process oracle: the same prompts through one
    paged-KV service (certified against the greedy oracle by
    test_paged_kv) — what every fleet routing MUST reproduce."""
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.serving import LMServingConfig

    svc = LMServingConfig()
    conf = dict(FLEET_CONF)
    conf["metrics_port"] = -1
    configure(svc, conf, name="fleet_oracle")
    _, scheduler = svc.build_service()
    try:
        out = {}
        for sid, turns in sessions.items():
            out[sid] = [
                scheduler.submit(
                    np.asarray(t, np.int32), max_new_tokens=NEW_TOKENS
                ).result(timeout=300.0).tolist()
                for t in turns
            ]
        return out
    finally:
        svc._teardown_service(suppress=True)


def spawn_fleet(tmp_path, n=2):
    from zookeeper_tpu.testing import spawn_fleet_workers

    return spawn_fleet_workers(str(tmp_path), num_workers=n,
                               config=FLEET_CONF)


@pytest.mark.slow
def test_fleet_token_identity_warm_turn2_and_rid_trace(tmp_path):
    """The §23 headline over REAL processes: (1) every fleet output is
    token-identical to the single-replica oracle; (2) turn 2 of every
    session lands on its pinned replica and the WORKER reports warm
    shared prompt tokens (the radix cache actually hit — TTFT rides
    the §20 warm path); (3) one router-minted rid is traceable in the
    router's RequestLog AND the worker's own /statusz request tail."""
    from zookeeper_tpu.testing import stop_fleet_workers

    sessions = fleet_prompts()
    want = oracle_outputs(sessions)
    workers = spawn_fleet(tmp_path)
    router = None
    try:
        router = FleetRouter(
            [ReplicaHandle.from_worker(w) for w in workers],
            page_size=FLEET_CONF["engine.page_size"],
        )
        got = {sid: [] for sid in sessions}
        turn2 = {}
        traced_rid = 314159
        for turn in range(2):
            for sid, turns in sessions.items():
                rid = (
                    traced_rid
                    if (turn, sid) == (0, "sA")
                    else None
                )
                resp = router.submit(
                    turns[turn], session=sid,
                    max_new_tokens=NEW_TOKENS, rid=rid,
                )
                got[sid].append(resp.tokens.tolist())
                if turn == 1:
                    turn2[sid] = resp
        assert got == want, "fleet output diverged from the oracle"
        for sid, resp in turn2.items():
            assert resp.worker_id == router.session_pin(sid)
            assert resp.affinity_hit
            # The WORKER's cache served turn-1's prompt warm: the
            # prediction was real, not just a routing bias.
            assert resp.shared_tokens >= len(sessions[sid][0]) - 1
            assert resp.predicted_shared <= resp.shared_tokens + \
                FLEET_CONF["engine.page_size"]
        # rid end-to-end: router log ...
        rec = router.request_log.find(traced_rid)
        assert rec is not None and rec["outcome"] == "ok"
        # ... and the worker the request landed on logged the SAME rid.
        first_a = router.request_log.find(traced_rid)["detail"]
        wid = first_a.split("replica=")[1].split()[0]
        w = next(x for x in workers if x["worker_id"] == wid)
        with urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz" % w["metrics_port"], timeout=10
        ) as r:
            doc = json.loads(r.read().decode())
        worker_rids = [
            e["rid"] for e in doc["requests"]["tail"]
        ]
        assert traced_rid in worker_rids
    finally:
        if router is not None:
            router.close()
        stop_fleet_workers(workers)


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_replica_kill_reroutes_and_router_restart_recovers(
    tmp_path,
):
    """Replica-kill chaos over REAL processes: the FaultPlan knob
    SIGKILLs the chosen replica mid-route, the in-flight request fails
    with WorkerCrashedError, the session finishes COLD on the survivor
    with token-identical output, and a restarted router (fresh object,
    same state file) still holds the session's pin."""
    from zookeeper_tpu.testing import stop_fleet_workers

    sessions = fleet_prompts()
    want = oracle_outputs(sessions)
    workers = spawn_fleet(tmp_path)
    state = str(tmp_path / "fleet_state.json")
    router = None
    try:
        replicas = [ReplicaHandle.from_worker(w) for w in workers]
        router = FleetRouter(
            replicas,
            page_size=FLEET_CONF["engine.page_size"],
            state_path=state,
        )
        t1, t2 = sessions["sA"]
        r1 = router.submit(t1, session="sA", max_new_tokens=NEW_TOKENS)
        assert r1.tokens.tolist() == want["sA"][0]
        with faults.injected(FaultPlan(fleet_replica_kill_at=1)):
            with pytest.raises(WorkerCrashedError):
                router.submit(
                    t2, session="sA", max_new_tokens=NEW_TOKENS
                )
        dead = router._by_id[r1.worker_id]
        assert not dead.healthy
        # The resubmit re-routes cold to the survivor — and the cold
        # path is still token-identical (affinity is a LATENCY
        # optimization, never a correctness dependency).
        r2 = router.submit(t2, session="sA", max_new_tokens=NEW_TOKENS)
        assert r2.rerouted
        assert r2.worker_id != r1.worker_id
        assert r2.shared_tokens == 0  # genuinely cold on the survivor
        assert r2.tokens.tolist() == want["sA"][1]
        snap = router.metrics.snapshot()
        assert snap["fleet_worker_crashes_total"] == 1.0
        assert snap["fleet_rerouted_total"] == 1.0
        # Router restart (the fleet_router_restart_at coordinate is
        # harness-consumed: the "restart" IS building the new router):
        plan = FaultPlan(fleet_router_restart_at=1)
        with faults.injected(plan):
            assert plan.take_fleet_router_restart()
            router.close()
            survivors = [r for r in replicas if r.healthy]
            router = FleetRouter(
                [
                    ReplicaHandle(
                        s.worker_id, s.generate_url, obs_url=s.obs_url,
                        pid=s.pid,
                    )
                    for s in survivors
                ],
                page_size=FLEET_CONF["engine.page_size"],
                state_path=state,
            )
        # The restarted router kept the pin and the session rides the
        # (now-warm again) survivor.
        assert router.session_pin("sA") == r2.worker_id
        r3 = router.submit(
            t2 + [5, 6], session="sA", max_new_tokens=NEW_TOKENS
        )
        assert r3.worker_id == r2.worker_id
        assert r3.shared_tokens > 0  # turn-2's prompt is cached now
    finally:
        if router is not None:
            router.close()
        stop_fleet_workers(workers)
