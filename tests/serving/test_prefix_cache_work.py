"""What the radix prefix cache's bookkeeping costs (docs/DESIGN.md §20):
eviction pops a kept heap of childless nodes instead of walking the
trie, an admission keys its prompt once, and ``insert_prefix`` resumes
where the admission's lookup stopped. Host-only: a ``PagePool`` and no
device."""

import numpy as np
import pytest

from zookeeper_tpu.serving.decode.pages import PagePool
from zookeeper_tpu.serving.decode.prefix_key import (
    PrefixIndex,
    common_prefix,
    walk_match,
)

pytestmark = pytest.mark.serving


def dump(pool):
    """The trie as nested ``{chunk: (page, {...})}``."""

    def node(n):
        return {c: (k.page, node(k)) for c, k in n.children.items()}

    return node(pool.prefix._root)


def trie_nodes(pool):
    out, stack = [], [pool.prefix._root]
    while stack:
        n = stack.pop()
        for child in n.children.values():
            out.append(child)
            stack.append(child)
    return out


def held_pages(pool):
    held = {}
    for slot in range(pool.slots):
        for page in pool.table[slot, : pool.counts[slot]].tolist():
            held[page] = held.get(page, 0) + 1
    return held


def freeable(pool):
    """Pages repeated eviction could free: nodes whose whole subtree
    only the cache holds."""

    def count(n):
        below = [count(k) for k in n.children.values()]
        whole = all(ok for ok, _ in below)
        total = sum(c for _, c in below)
        if n is not pool.prefix._root and whole and pool.refcount[n.page] == 1:
            return True, total + 1
        return False, total

    return count(pool.prefix._root)[1]


def check(pool):
    """The invariants every step must leave."""
    assert pool.leak_check() == 0
    nodes = trie_nodes(pool)
    assert pool.prefix.nodes == len(nodes)
    cached = {n.page for n in nodes}
    assert len(cached) == len(nodes)
    held = held_pages(pool)
    for page in range(pool.num_pages):
        want = held.get(page, 0) + (page in cached)
        assert pool.refcount[page] == want, (page, pool.refcount[page], want)
    assert set(pool._free).isdisjoint(held)


def admit(pool, slot, prompt):
    plan = pool.assign_prompt(slot, prompt)
    assert plan is not None
    pool.insert_prefix(slot, prompt)
    return plan


# -- (a) which pages eviction frees, and in what order --------------------


def hand_pool():
    """root -> (1,2)p0 -> {(3,4)p1 -> (5,)p2, (6,7)p3}; touched in the
    order p0 p1 p2 (the first insert), p0 (the second's lookup), p3
    (its insert, resumed below p0)."""
    pool = PagePool(num_pages=8, page_size=2, slots=2, max_pages_per_slot=4)
    admit(pool, 0, np.array([1, 2, 3, 4, 5]))
    assert pool.table[0, :3].tolist() == [0, 1, 2]
    admit(pool, 1, np.array([1, 2, 6, 7]))
    assert pool.table[1, :2].tolist() == [0, 3]
    pool.release_slot(0)
    pool.release_slot(1)
    check(pool)
    return pool


def test_eviction_frees_least_recently_used_leaves_and_exposed_parents():
    pool = hand_pool()
    stamps = {n.page: n.last_used for n in trie_nodes(pool)}
    assert stamps == {0: 4, 1: 2, 2: 3, 3: 5}
    assert pool.prefix.evict_lru(1) == 1
    assert pool._free[-1:] == [2]
    # p1 is childless now and older than p3: it goes first
    assert pool.prefix.evict_lru(2) == 2
    assert pool._free[-2:] == [1, 3]
    assert pool.prefix.evict_lru(5) == 1
    assert pool._free[-1:] == [0]
    assert pool.prefix.nodes == 0 and dump(pool) == {}
    check(pool)


def test_one_eviction_takes_an_exposed_parent_in_turn():
    """A parent an eviction exposes joins the leaves at its own
    ``last_used``: p1 (2) before p3 (5), where a sweep layer by layer
    would take p2, p3, then p1."""
    pool = hand_pool()
    assert pool.prefix.evict_lru(3) == 3
    assert pool._free[-3:] == [2, 1, 3]
    assert dump(pool) == {(1, 2): (0, {})}
    check(pool)


def test_eviction_skips_what_a_live_slot_holds():
    pool = hand_pool()
    admit(pool, 0, np.array([1, 2, 6, 7, 8]))  # holds p0 and p3
    assert pool.prefix.evict_lru(8) == 2  # p2, then p1
    assert sorted(n.page for n in trie_nodes(pool)) == [0, 3, 4]
    check(pool)
    pool.release_slot(0)
    assert pool.prefix.evict_lru(8) == 3
    check(pool)


# -- (b) a seeded random run, checked after every step --------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_operations_keep_the_invariants(seed):
    rng = np.random.default_rng(seed)
    ps, slots, max_pages = 4, 4, 8
    pool = PagePool(
        num_pages=20, page_size=ps, slots=slots, max_pages_per_slot=max_pages
    )
    bases = [rng.integers(0, 5, size=12).tolist() for _ in range(3)]
    lengths = {}  # slot -> rows its pages must cover
    pending = {}  # slot -> prompt assigned, not yet inserted
    for _ in range(600):
        op = rng.integers(0, 6)
        free = [s for s in range(slots) if s not in lengths]
        busy = sorted(lengths)
        if op == 0 and free:
            slot = int(rng.choice(free))
            base = bases[rng.integers(0, len(bases))]
            head = base[: int(rng.integers(0, len(base) + 1))]
            tail = rng.integers(0, 5, size=int(rng.integers(1, 10))).tolist()
            prompt = np.array(head + tail, np.int32)
            if pool.assign_prompt(slot, prompt) is not None:
                lengths[slot] = len(prompt)
                pending[slot] = prompt
        elif op == 1 and pending:
            slot = int(rng.choice(sorted(pending)))
            pool.insert_prefix(slot, pending.pop(slot))
        elif op == 2 and busy:
            slot = int(rng.choice(busy))
            rows = min(lengths[slot] + int(rng.integers(1, 6)), max_pages * ps)
            if pool.ensure_rows(slot, rows):
                lengths[slot] = rows
        elif op == 3 and busy:
            slot = int(rng.choice(busy))
            pool.release_slot(slot)
            del lengths[slot]
            pending.pop(slot, None)
        elif op == 4:
            want = int(rng.integers(0, 6))
            expect = min(want, freeable(pool))
            held = set(held_pages(pool))
            before = {n.page for n in trie_nodes(pool)}
            free_before = pool.free_pages
            assert pool.prefix.evict_lru(want) == expect
            evicted = before - {n.page for n in trie_nodes(pool)}
            assert len(evicted) == expect and evicted.isdisjoint(held)
            assert pool.free_pages == free_before + expect
        elif op == 5 and rng.random() < 0.1:
            pool.invalidate_prefix()
        check(pool)
    assert pool.prefix.evicted_pages > 0


def old_walk_match(root, tokens, ps):
    """The match walk before the first-token index: the partial tail
    scanned against every child."""
    node, visited, t = root, [], 0
    while t + ps <= len(tokens):
        child = node.children.get(tuple(tokens[t:t + ps]))
        if child is None:
            break
        visited.append(child)
        t += ps
        node = child
    rest = tokens[t:]
    if rest:
        best, bestq = None, 0
        for child in node.children.values():
            q = common_prefix(child.chunk, rest)
            if q > bestq:
                best, bestq = child, q
        if best is not None:
            visited.append(best)
            t += bestq
    return t, visited


@pytest.mark.parametrize("ps", [1, 3, 4])
def test_partial_tail_by_first_token_matches_the_scan_of_every_child(ps):
    rng = np.random.default_rng(ps)
    index = PrefixIndex(ps)
    for _ in range(60):
        index.observe(rng.integers(0, 4, size=int(rng.integers(1, 14))).tolist())
    for _ in range(300):
        probe = rng.integers(0, 4, size=int(rng.integers(1, 16))).tolist()
        t, visited = walk_match(index._root, probe, ps)
        t_old, visited_old = old_walk_match(index._root, probe, ps)
        assert t == t_old
        assert [id(n) for n in visited] == [id(n) for n in visited_old]


# -- (c) eviction examines about what it frees ----------------------------


def test_eviction_examines_about_two_entries_a_page_freed():
    ps = 4
    pool = PagePool(
        num_pages=3200, page_size=ps, slots=48, max_pages_per_slot=64
    )
    rng = np.random.default_rng(3)
    for i in range(60):  # 60 x 50 pages cached, the last 8 still held
        admit(pool, i % 48, rng.integers(0, 50000, size=200))
        if i < 52:
            pool.release_slot(i % 48)
    assert pool.prefix.nodes == 3000
    for k in (1, 7, 64, 500):
        examined = pool.prefix.evict_nodes_examined
        assert pool.prefix.evict_lru(k) == k
        assert pool.prefix.evict_nodes_examined - examined <= 2 * k
    check(pool)


def test_a_closed_loop_examines_about_what_it_evicts():
    """Unshared prompts through a full pool (the summarizing traffic's
    shape, scaled down): every admission evicts, each pop frees."""
    ps, slots = 4, 6
    pool = PagePool(
        num_pages=slots * 16, page_size=ps, slots=slots, max_pages_per_slot=16
    )
    rng = np.random.default_rng(5)
    lengths = [0] * slots
    for i in range(400):
        slot = i % slots
        if lengths[slot]:
            pool.release_slot(slot)
        prompt = rng.integers(0, 50000, size=int(rng.integers(30, 50)))
        admit(pool, slot, prompt)
        lengths[slot] = len(prompt)
        while lengths[slot] < len(prompt) + 12:
            lengths[slot] += 1
            assert pool.ensure_rows(slot, lengths[slot])
        check(pool)
    cache = pool.prefix
    assert cache.evicted_pages > 1000
    assert cache.evict_nodes_examined <= 2 * cache.evicted_pages
    status = pool.status()
    assert status["prefix_evict_nodes_examined"] == cache.evict_nodes_examined
    assert status["prefix_keyed_tokens"] == cache.keyed_tokens
    assert status["prefix_nodes"] == len(trie_nodes(pool))


# -- (d) a warm admission keys its prompt once ----------------------------


def warm_pool(prefix, body):
    ps = 64
    pool = PagePool(
        num_pages=1024, page_size=ps, slots=4, max_pages_per_slot=288
    )
    admit(pool, 0, np.concatenate([prefix, body]))
    pool.release_slot(0)
    return pool


def test_a_warm_admission_keys_once_and_inserts_only_its_suffix():
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 100000, size=16384).astype(np.int32)
    pool = warm_pool(prefix, rng.integers(0, 100000, size=300))
    prompt = np.concatenate([prefix, rng.integers(0, 100000, size=700)])
    cache = pool.prefix
    keyed = cache.keyed_tokens
    plan = pool.assign_prompt(1, prompt)
    assert plan["shared_tokens"] == 16384
    clock = cache._clock
    nodes = cache.nodes
    assert pool.insert_prefix(1, prompt) == 11  # 700 tokens: 10 pages + a tail
    assert cache.nodes == nodes + 11
    assert len([n for n in trie_nodes(pool) if n.last_used > clock]) == 11
    assert cache.keyed_tokens - keyed <= len(prompt) + 64
    check(pool)


@pytest.mark.parametrize("between", ["none", "evict", "clear"])
def test_the_resumed_insert_builds_the_trie_a_full_walk_does(between):
    """The same admissions with the insert handed its lookup's resume
    point (the prompt object the admission had) and without it (an
    equal copy): the same trie, whatever happened in between."""
    rng = np.random.default_rng(13)
    prefix = rng.integers(0, 100000, size=16384).astype(np.int32)
    body = rng.integers(0, 100000, size=64).astype(np.int32)
    trees = []
    for resumed in (True, False):
        pool = warm_pool(prefix, body)
        # The whole prompt again: the match is capped a token short, so
        # its last page is copied and the node the lookup stopped at is
        # the cache's alone.
        prompt = np.concatenate([prefix, body])
        plan = pool.assign_prompt(1, prompt)
        assert plan["shared_tokens"] == 16447 and plan["cow"] is not None
        if between == "evict":
            assert pool.prefix.evict_lru(1) == 1
        elif between == "clear":
            pool.invalidate_prefix()
        pool.insert_prefix(1, prompt if resumed else prompt.copy())
        check(pool)
        trees.append(dump(pool))
    assert trees[0] == trees[1]


# -- (e) the prompt's type does not matter --------------------------------


def test_a_list_int32_or_int64_prompt_builds_the_same_trie():
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 2**31 - 1, size=n) for n in (37, 50, 9)]
    prompts.append(np.concatenate([prompts[0][:20], prompts[2]]))
    trees = []
    for kind in (list, np.int32, np.int64):
        pool = PagePool(
            num_pages=64, page_size=8, slots=2, max_pages_per_slot=8
        )
        for i, p in enumerate(prompts):
            p = p.tolist() if kind is list else p.astype(kind)
            admit(pool, i % 2, p)
            pool.release_slot(i % 2)
        for n in trie_nodes(pool):
            assert all(type(x) is int for x in n.chunk)
        trees.append(dump(pool))
    assert trees[0] == trees[1] == trees[2]
