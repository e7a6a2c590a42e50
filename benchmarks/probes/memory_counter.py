#!/usr/bin/env python3
"""Does the TPU runtime's ``peak_bytes_in_use`` hold a running program's
temporaries? A probe, not part of any run: ``zkbench/device.py`` adds the
timed step's temporaries to that counter on its answer (PERF.md section 2).

    python3 benchmarks/probes/memory_counter.py

It compiles one program whose temporaries jax's ``memory_analysis()`` puts
at some gigabytes, runs it, and prints the counter before and after. Then
it fills the chip with one array so that the program's arguments fit and
its temporaries do not, and runs it again. If the counter does not move by
the temporaries and the second run is refused for memory, the temporaries
take device memory that the counter leaves out. jax only; nothing of the
program or of the benchmark is imported.
"""

import json
import sys

import jax
import jax.numpy as jnp


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"memory_counter: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3

    def stats():
        return {k: int(v) for k, v in (dev.memory_stats() or {}).items()}

    n = 16384

    def chain(x, w):
        h1 = x @ w
        h2 = jnp.tanh(h1) @ w
        h3 = jnp.tanh(h2) @ w
        return jnp.sum(h3 * h1) + jnp.sum(h2)

    x = jnp.ones((2 * n, n), jnp.bfloat16)
    w = jnp.ones((n, n), jnp.bfloat16) * 1e-4
    compiled = jax.jit(chain).lower(x, w).compile()
    analysis = compiled.memory_analysis()
    temps = int(analysis.temp_size_in_bytes)
    before = stats()
    float(compiled(x, w))
    after = stats()
    report = {
        "temporaries_by_memory_analysis": temps,
        "arguments": int(analysis.argument_size_in_bytes),
        "counter_before": before.get("peak_bytes_in_use"),
        "counter_after": after.get("peak_bytes_in_use"),
        "counter_moved_by": after.get("peak_bytes_in_use", 0)
        - before.get("peak_bytes_in_use", 0),
        "bytes_limit": after.get("bytes_limit"),
        "stats_keys": sorted(after),
    }
    # Fill the chip: leave room for half of the temporaries only.
    free = after["bytes_limit"] - after["bytes_in_use"]
    filler = jnp.zeros(((free - temps // 2) // 4,), jnp.float32)
    filler.block_until_ready()
    report["filler_bytes"] = int(filler.nbytes)
    report["in_use_with_filler"] = stats().get("bytes_in_use")
    try:
        float(compiled(x, w))
        report["run_beside_filler"] = "ran"
    except Exception as e:  # jax raises its own error type for memory
        report["run_beside_filler"] = "refused: " + str(e).splitlines()[0][:200]
    del filler
    float(compiled(x, w))
    report["run_after_filler_freed"] = "ran"
    print("memory_counter: " + json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
