"""The per-layer readers and the memory reading that the review of PR 23
asked to be put right: a device metric reads the trace, a host-clock metric
says so, nothing is clipped, and the timed step's temporaries come from
jax's own analysis of an executable or not at all."""

import numpy as np
import pytest

from zkbench import cells, device

BENCH = cells.load_benchmark()
TRAIN = next(
    w["name"] for w in BENCH["workloads"]
    if cells.Cell(w["name"]).config["entry"] == "train"
)
PEAKS = {"bf16_flops_per_s": 100.0, "int8_ops_per_s": 200.0, "hbm_bytes_per_s": 10.0}
SMALL_NET = {
    "blocks_per_section": [1, 2], "section_features": [4, 8],
    "stem_features": 2, "stem_groups": 2, "image": [8, 8, 3], "num_classes": 5,
}


class FakeTrace:
    def __init__(self, busy):
        self._busy = busy

    def busy_s(self):
        return self._busy


def reader_ctx(name, busy, steps, rate):
    cell = cells.Cell(TRAIN)
    spec, reader = cell.layer_metric(name)
    ctx = {
        "trace": FakeTrace(busy), "spec": spec, "peaks": PEAKS, "cell": cell,
        "work": {"steps": steps, "steps_per_s_untraced": rate,
                 "items_per_step": 2, "chips": 1, "model": SMALL_NET},
    }
    least = cell.shapes_module("quicknet").least_step_seconds(SMALL_NET, 2, PEAKS)
    return reader, ctx, least["compute_s"]


def test_train_step_mfu_reads_the_trace_and_not_the_host_rate():
    reader, ctx, least = reader_ctx("train_step_mfu", busy=4 * 3 * 250.0, steps=4, rate=1e-9)
    value = reader.read(ctx)
    # least time over busy time per step, whatever the host's rate was
    assert value == pytest.approx(100.0 * least / (3 * 250.0))
    ctx["work"]["steps_per_s_untraced"] = 123.0
    assert reader.read(ctx) == pytest.approx(value)
    ctx["trace"] = FakeTrace(0.0)
    assert reader.read(ctx) is None  # nothing to read: no 0 for a share of a peak


def test_train_wall_mfu_is_the_host_rate_and_refuses_slices_that_disagree():
    reader, ctx, least = reader_ctx("train_wall_mfu", busy=4 * 200.0, steps=4, rate=1 / 400.0)
    assert reader.read(ctx) == pytest.approx(100.0 * least / 400.0)
    # 200 s busy a step cannot go with 1 step every 100 s: no clip, an error
    ctx["work"]["steps_per_s_untraced"] = 1 / 100.0
    with pytest.raises(ValueError):
        reader.read(ctx)
    ctx["work"]["rehearsal"] = True  # host threads stand in for the device
    assert reader.read(ctx) == pytest.approx(100.0 * least / 100.0)
    ctx["work"]["rehearsal"] = False
    ctx["work"]["steps_per_s_untraced"] = 1 / 195.0  # device-bound: 200/195 = 1.026
    assert reader.read(ctx) == pytest.approx(100.0 * least / 195.0)
    ctx["work"]["steps_per_s_untraced"] = 0
    assert reader.read(ctx) is None


def test_mfu_sources_say_where_each_number_comes_from():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["train_step_mfu"]["source"] == "device_trace"
    assert by_name["train_wall_mfu"]["source"] == "host_clock"
    # no metric of a device trace is worked out from the host's step rate
    assert "device_idle_share.train" not in by_name


def test_step_temporaries_come_from_a_jax_executable_or_not_at_all():
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: (x @ x).sum()).lower(jnp.ones((8, 8))).compile()
    assert device.executable_of(compiled) is compiled

    class Wrapper:  # as the program's ledgered step keeps it
        _compiled = compiled

    assert device.executable_of(Wrapper()) is compiled
    assert device.temp_bytes(compiled) >= 0
    with pytest.raises(RuntimeError):
        device.executable_of(object())
    with pytest.raises(RuntimeError):
        device.executable_of(jax.jit(lambda x: x))  # not compiled yet: no analysis


def test_memory_peak_fails_loudly_without_a_counter_and_is_silent_in_a_rehearsal():
    peak, note = device.memory_peak_bytes(1, rehearse=True)
    assert peak is None and "rehearsal" in note
    with pytest.raises(RuntimeError):  # the CPU keeps no peak_bytes_in_use
        device.memory_peak_bytes(1)


def test_reference_reads_every_length_with_one_program():
    """The served tokens of requests of any length are read by one compiled
    program (a seed's own lengths compile nothing anew), the reference's
    own greedy tokens read a gap of 0 and an altered token reads its gap."""
    import jax
    import jax.numpy as jnp

    ref = cells.Cell(BENCH["workloads"][-1]["name"]).reference_module()
    model = {"n_layer": 2, "n_head": 2}
    d, vocab, positions = 8, 32, 16
    rng = np.random.default_rng(3)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    params = {"embed": w(vocab, d), "pos": w(positions, d), "RMSNorm_0": {"scale": w(d) + 1}}
    for i in range(2):
        params[f"block{i}"] = {
            "RMSNorm_0": {"scale": w(d) + 1}, "qkv": {"kernel": w(d, 3 * d)},
            "proj": {"kernel": w(d, d)}, "RMSNorm_1": {"scale": w(d) + 1},
            "up": {"kernel": w(d, 4 * d)}, "down": {"kernel": w(4 * d, d)},
        }
    forward = ref.make_forward(2)
    layers = ref.stack_layers(params, 2)

    def greedy(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            padded = np.zeros((positions,), np.int32)
            padded[: len(seq)] = seq
            logits = forward(params["embed"], params["pos"], params["RMSNorm_0"]["scale"], layers, jnp.asarray(padded))
            seq.append(int(np.argmax(np.asarray(logits)[len(seq) - 1])))
        return np.asarray(seq[len(prompt):], np.int32)

    sequences = []
    for n_prompt, n_new in ((3, 4), (5, 2), (7, 6)):
        prompt = rng.integers(0, vocab, size=n_prompt).astype(np.int32)
        sequences.append({"prompt": prompt, "served": greedy(prompt, n_new)})
    with jax.log_compiles(False):
        found = ref.served_token_gaps(params, model, sequences, positions)
    assert found["tokens_compared"] == 12 and found["widest_gap"] == 0.0
    assert found["tokens_not_reference_choice"] == 0

    altered = [dict(s) for s in sequences]
    altered[1] = dict(altered[1], served=(altered[1]["served"] + 1) % vocab)
    found = ref.served_token_gaps(params, model, altered, positions)
    assert found["widest_gap"] > 0 and found["tokens_not_reference_choice"] >= 1

    reader = ref.make_reader(2)
    for s in sequences:
        padded = np.zeros((positions,), np.int32)
        full = np.concatenate([s["prompt"], s["served"]])
        padded[: len(full)] = full
        reader(params["embed"], params["pos"], params["RMSNorm_0"]["scale"], layers,
               jnp.asarray(padded), jnp.asarray(np.roll(padded, -1)))
    assert reader._cache_size() == 1
