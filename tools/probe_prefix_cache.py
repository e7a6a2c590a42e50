"""The radix prefix cache's host bookkeeping alone, no device and no
scheduler: each prefix-cache cell's admission pattern replayed on a
``PagePool`` (docs/DESIGN.md §20) at the cell's own sizes, read from its
configuration and traffic files:

- ``summarize``: ``gpt2_xl_24l`` under ``summarize_closed`` (unshared
  prompts of 768-960 tokens through a pool that holds exactly its slots'
  pages, so once the cache is full every allocation evicts);
- ``prefix16k``: ``deepseek_v2_lite_6l`` under ``prefix16k_closed`` (a
  16,384-token shared prefix before every body).

The replay is a closed loop with the queue never empty: every iteration
grows each active slot by one row (``ensure_rows``) and counts one
output token; a slot whose answer is complete is released
(``release_slot``) and given the next request (``assign_prompt``, then
``insert_prefix``). It prints, a cell, milliseconds a request for each
``PagePool`` phase and the cache's counters (``evicted_pages``, and
where the tree has them ``evict_nodes_examined`` and ``keyed_tokens``,
beside the prompt tokens admitted).

    python tools/probe_prefix_cache.py                 # both cells, ~1 min
    PYTHONPATH=<other checkout> python tools/probe_prefix_cache.py

The second form replays another tree's ``PagePool`` (the tool's own
directory comes after whatever ``PYTHONPATH`` names). Times are the
host's: every line says so.
"""

import argparse
import json
import os
import platform
import sys
import time

# After whatever PYTHONPATH names, so that an older tree given there wins.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(ROOT)
sys.path.append(os.path.join(ROOT, "benchmarks"))

from zkbench.traffic import closed_loop  # noqa: E402

from zookeeper_tpu.serving.decode.pages import PagePool  # noqa: E402

#: name -> (configuration, traffic, requests replayed by default)
CELLS = {
    "summarize": ("gpt2_xl_24l", "summarize_closed", 600),
    "prefix16k": ("deepseek_v2_lite_6l", "prefix16k_closed", 400),
}
PHASES = ("assign_prompt", "insert_prefix", "ensure_rows", "release_slot")


def load(kind, name):
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def pool_for(program):
    """The ``PagePool`` the engine builds for ``program``'s settings."""
    page = int(program["engine.page_size"])
    slots = int(program["engine.slots"])
    max_pages = int(program["engine.kv_capacity"]) // page
    pages = int(program.get("engine.pool_pages", -1))
    widths = program.get("engine.extend_buckets") or program["engine.seq_buckets"]
    return PagePool(
        num_pages=slots * max_pages if pages == -1 else pages,
        page_size=page,
        slots=slots,
        max_pages_per_slot=max_pages,
        prefix_cache=bool(program["engine.prefix_cache"]),
        max_suffix=max(widths),
    )


def replay(cell, requests, seed):
    config, traffic, _ = CELLS[cell]
    program = load("configs", config)["program"]
    mix = load("traffic", traffic)
    pool = pool_for(program)
    clients = int(mix["arrivals"]["clients"])
    rounds = -(-requests // clients)
    per_client = closed_loop(mix, seed, rounds, int(program["vocab_size"]))
    queue = [r for batch in zip(*per_client) for r in batch][:requests]
    queue.reverse()
    spent = dict.fromkeys(PHASES, 0.0)
    active = {}  # slot -> [request, rows, tokens left]
    admitted = prompt_tokens = iterations = 0

    def timed(phase, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        spent[phase] += time.perf_counter() - t0
        return out

    while queue or active:
        for slot in range(pool.slots):
            if slot in active or not queue:
                continue
            req = queue.pop()
            if timed("assign_prompt", pool.assign_prompt, slot, req.prompt) is None:
                raise RuntimeError(f"{cell}: the pool cannot admit request {req.index}")
            timed("insert_prefix", pool.insert_prefix, slot, req.prompt)
            active[slot] = [req, len(req.prompt), int(req.max_new_tokens) - 1]
            admitted += 1
            prompt_tokens += len(req.prompt)
        iterations += 1
        for slot in list(active):
            state = active[slot]
            if state[2] <= 0:
                timed("release_slot", pool.release_slot, slot)
                del active[slot]
                continue
            state[1] += 1
            if not timed("ensure_rows", pool.ensure_rows, slot, state[1]):
                raise RuntimeError(f"{cell}: the pool ran out at slot {slot}")
            state[2] -= 1
    cache = pool.prefix
    ms = {k: v * 1e3 / admitted for k, v in spent.items()}
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "seed": seed,
        "requests": admitted,
        "iterations": iterations,
        "ms_per_request": {k: round(v, 4) for k, v in ms.items()},
        "total_ms_per_request": round(sum(ms.values()), 4),
        "total_ms_per_iteration": round(sum(spent.values()) * 1e3 / iterations, 4),
        "prompt_tokens": prompt_tokens,
        "evicted_pages": cache.evicted_pages,
        "evict_nodes_examined": getattr(cache, "evict_nodes_examined", None),
        "keyed_tokens": getattr(cache, "keyed_tokens", None),
        "nodes": cache.nodes,
        "leaked": pool.leak_check(),
        "ran_on": f"host CPU ({platform.processor() or platform.machine()}, "
        f"{os.cpu_count()} cores), no device",
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS), choices=list(CELLS))
    parser.add_argument("--requests", type=int, default=0,
                        help="requests a cell (0: the cell's default)")
    parser.add_argument("--seed", type=int, default=4200000017)
    args = parser.parse_args(argv)
    for cell in args.cells:
        n = args.requests or CELLS[cell][2]
        print(json.dumps(replay(cell, n, args.seed)), flush=True)


if __name__ == "__main__":
    main()
