"""The training cell's batch assembly alone, no model: the native
augmentation (RandomResizedCrop 0.08-1, flip, zero-centre) of batches of
512 from a store of 16,384 uint8 images of 224 x 224 x 3
(``quicknet_large.train_b512``'s store and recipe), into a fresh output
buffer every batch and into recycled ones (``data.pipeline.
HostBatchRing``), two ways each:

- ``kernel``: ``native.gather_augment_normalize`` alone, back to back;
  ``recycled`` writes every call into the buffer the first call made;
- ``loader``: the loader's producer thread (``batch_iterator`` into
  ``prefetch_to_device``), staging each batch on the device, over
  consecutive epochs; the consumer takes each batch at once and holds the
  newest. Its own spans give assemble, stage and recycle-wait per batch.

For each: milliseconds per batch (median and quartiles) and the slope of
machine memory in use (``MemTotal - MemAvailable``) over the batches, in
MB a batch. Also the threads the native kernel starts a call.

    python tools/probe_loader_assemble.py   # on a host with a TPU, about 2 min

Every line names the device it ran on; no time comes from a CPU
(``--rehearse``: small shapes, for the control flow only).
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

# After whatever PYTHONPATH names, so that an older tree given there wins.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def in_use_bytes() -> int:
    with open("/proc/meminfo") as f:
        kb = {
            line.split(":")[0]: int(line.split()[1])
            for line in f
            if line.startswith(("MemTotal:", "MemAvailable:"))
        }
    return (kb["MemTotal"] - kb["MemAvailable"]) * 1024


def summary(ms, memory):
    """Median and quartiles of ``ms``; least-squares MB a batch of
    ``memory``."""
    q1, med, q3 = statistics.quantiles(ms, n=4) if len(ms) > 1 else ms * 3
    slope = float(np.polyfit(np.arange(len(memory)), memory, 1)[0]) / 1e6
    return {
        "batches": len(ms), "ms_median": med, "ms_q1": q1, "ms_q3": q3,
        "mem_growth_mb_per_batch": slope,
    }


def kernel_phase(native, store, spec, batch, n, recycled, seed):
    eh, ew, _ = spec["expected_shape"]
    order = np.random.default_rng(seed).permutation(len(store))
    out, ms, memory = None, [], []
    for i in range(n + 1):
        start = (i * batch) % (len(store) - batch + 1)
        idx = order[start:start + batch].astype(np.int64)
        t = time.perf_counter()
        got = native.gather_augment_normalize(
            store, idx, out_height=eh, out_width=ew, seed=seed, epoch=i,
            random_resized_crop=spec["random_resized_crop"],
            crop_scale_range=spec["crop_scale_range"],
            log_aspect_range=spec["log_aspect_range"],
            pad_pixels=spec["pad_pixels"], random_flip=spec["random_flip"],
            post_scale=spec["post_scale"], post_shift=spec["post_shift"],
            out=out if recycled else None,
        )
        if i:  # the first call makes the buffer a recycled run reuses
            ms.append(1e3 * (time.perf_counter() - t))
            memory.append(in_use_bytes())
        if recycled:
            out = got
        del got
    return summary(ms, memory)


def loader_phase(source, pre, batch, n, recycled, seed):
    from zookeeper_tpu.data.pipeline import (
        HostBatchRing, batch_iterator, prefetch_to_device,
    )
    from zookeeper_tpu.observability import trace

    ring = HostBatchRing(4) if recycled else None
    prior = trace.get_tracer()
    tracer = trace.Tracer(1_000_000)
    trace.install(tracer)
    memory, seen, epoch = [], 0, 0
    try:
        while seen < n:
            it = batch_iterator(
                source, pre, batch, training=True, shuffle=True, seed=seed,
                epoch=epoch, buffers=ring,
            )
            for _held in prefetch_to_device(it, size=2, ring=ring):
                memory.append(in_use_bytes())
                seen += 1
                if seen >= n:
                    break
            epoch += 1
    finally:
        trace.install(prior)
    records = [r for r in tracer.snapshot() if r["phase"] == "X"]

    def per_batch(name):
        return [r["dur_ns"] / 1e6 for r in records if r["name"] == name]

    assembled = [
        r for r in records
        if r["name"] == "loader_assemble" and "recycled" in r["attrs"]
    ]
    out = summary(
        [r["dur_ns"] / 1e6 for r in assembled], memory
    )
    stage = per_batch("loader_stage")
    waits = per_batch("loader_recycle_wait")
    out.update({
        "stage_ms_median": statistics.median(stage),
        "recycle_waits": len(waits),
        "recycle_wait_ms_total": sum(waits),
        "recycled_share": 100.0 * sum(
            r["attrs"]["recycled"] for r in assembled
        ) / len(assembled),
        "epochs": epoch,
    })
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batches", type=int, default=64)
    parser.add_argument("--seed", type=int, default=2**31 + 41)
    parser.add_argument("--rehearse", action="store_true")
    opts = parser.parse_args()

    import jax

    from zookeeper_tpu import native
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.data import ArraySource, ImageClassificationPreprocessing

    device = jax.devices()[0]
    if device.platform != "tpu" and not opts.rehearse:
        print("probe_loader_assemble: no TPU; a time comes only from the chip")
        return 2
    if not native.available():
        print("probe_loader_assemble: the native library did not build")
        return 1
    if opts.rehearse:
        images, side, out_side, batch = 64, 40, 32, 8
    else:
        images, side, out_side, batch = 16384, 224, 224, 512
    pre = ImageClassificationPreprocessing()
    configure(
        pre,
        {"height": out_side, "width": out_side, "channels": 3,
         "augment": True, "random_resized_crop": True},
        name="pre",
    )
    spec = pre.native_batch_spec(True)
    rng = np.random.default_rng([opts.seed % 2**32, 41])
    t = time.perf_counter()
    store = rng.integers(0, 256, size=(images, side, side, 3), dtype=np.uint8)
    labels = rng.integers(0, 1000, size=(images,), dtype=np.int32)
    made_s = time.perf_counter() - t
    source = ArraySource({"image": store, "label": labels})

    base = {
        "probe": "loader_assemble", "batch": batch, "store": list(store.shape),
        "native_threads": native.threads(), "cpu_count": os.cpu_count(),
        "affinity": len(os.sched_getaffinity(0)), "store_made_s": made_s,
        "device": {"platform": device.platform, "kind": device.device_kind},
    }
    lines = []
    for way in ("kernel", "loader"):
        for recycled in (False, True):
            if way == "kernel":
                got = kernel_phase(
                    native, store, spec, batch, opts.batches // 2, recycled,
                    opts.seed,
                )
            else:
                got = loader_phase(
                    source, pre, batch, opts.batches, recycled, opts.seed,
                )
            line = dict(
                base, way=way, buffer="recycled" if recycled else "fresh",
                **got,
            )
            print(json.dumps(line), flush=True)
            lines.append(line)
    print("| way | buffer | ms a batch (q1 / median / q3) | MB a batch |")
    print("| --- | --- | --- | --- |")
    for line in lines:
        print(
            f"| {line['way']} | {line['buffer']} | {line['ms_q1']:.1f} / "
            f"{line['ms_median']:.1f} / {line['ms_q3']:.1f} | "
            f"{line['mem_growth_mb_per_batch']:.1f} |"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
