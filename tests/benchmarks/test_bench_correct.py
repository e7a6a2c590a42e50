"""``correct`` comes out false where it has to: the control (the plain
reference computed in float8 in the program's place) and each fault a cell
can have, planted underneath a run that is otherwise whole.

The runs are rehearsals: the real entry, reference, comparison and limits
(the configuration files' ``limits``, set from chip runs at the cells' own
sizes, PERF.md section 2), at the tiny sizes the data files give under
``rehearsal``, in float32 so that a sound run reads far under every limit.
"""

import json
import sys

import numpy as np
import pytest

from zkbench import cells, compare

sys.path.insert(0, cells.BENCH_DIR)
import run as bench_run  # noqa: E402

BENCH = cells.load_benchmark()
TRAIN = next(
    w["name"] for w in BENCH["workloads"]
    if cells.Cell(w["name"]).config["entry"] == "train"
)
SERVE = [
    w["name"] for w in BENCH["workloads"]
    if cells.Cell(w["name"]).config["entry"] == "serve"
]


@pytest.fixture(autouse=True)
def cpu_explicit(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # In-process runs must not turn jax's persistent compilation cache on
    # for the tests that share this worker.
    from zkbench import device

    monkeypatch.setattr(device, "enable_compile_cache", lambda: None)


def rehearse(capsys, workload, *extra):
    code = bench_run.main(
        ["--workload", workload, "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "0", "--rehearse", *extra]
    )
    captured = capsys.readouterr()
    out = captured.out
    assert code == 0, captured.err[-3000:]
    lines = out.strip().splitlines()
    return json.loads(lines[-1]), lines


def control_numbers(lines, name):
    line = next(l for l in lines if l.startswith(f"benchmark: {name} "))
    pairs = line.split("): ", 1)[1].split()
    return {k: float(v) for k, v in (p.split("=") for p in pairs)}


def test_train_sound_run_is_correct_and_control_and_half_batch_fail(capsys):
    result, lines = rehearse(capsys, TRAIN, "--with-control")
    assert result["correct"] is True, result["compared"]
    assert any(row["limit"] is not None for row in result["compared"].values())
    limits = cells.Cell(TRAIN).config["limits"]
    for name in ("control_fp8", "fault_half_batch"):
        # put in the program's place, the run's own judge says not correct
        assert result["controls"][name] is False
        assert any(l.startswith(f"benchmark: in the program's place, {name} is judged correct: false") for l in lines)
        numbers = control_numbers(lines, name)
        correct, _ = compare.judge(numbers, limits)
        assert not correct, (name, numbers, limits)
    assert result["compared"]["batch_rows_bad"] == {"value": 0.0, "limit": 0, "ok": True}


def break_train_step(monkeypatch, wrap):
    import zookeeper_tpu.training.experiment as experiment

    make = experiment.make_train_step

    def broken_make(*args, **kwargs):
        return wrap(make(*args, **kwargs))

    monkeypatch.setattr(experiment, "make_train_step", broken_make)


def test_train_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            new_state, metrics = step(state, batch)
            return state.replace(step=new_state.step), metrics
        return unchanged

    break_train_step(monkeypatch, wrap)
    result, _ = rehearse(capsys, TRAIN)
    assert result["correct"] is False
    assert result["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_step_that_leaves_out_half_the_batch_is_not_correct(capsys, monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["target"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    break_train_step(monkeypatch, wrap)
    result, _ = rehearse(capsys, TRAIN)
    assert result["correct"] is False


@pytest.mark.parametrize("workload", SERVE)
def test_serve_sound_run_is_correct_and_control_fails(capsys, workload):
    result, lines = rehearse(capsys, workload, "--with-control")
    assert result["correct"] is True, result["compared"]
    assert result["counts"]["tokens_compared"] > 0
    line = next(l for l in lines if "control_widest_gap" in l)
    control = float(line.split("'control_widest_gap': ")[1].split("}")[0].split(",")[0])
    limit = cells.Cell(workload).config["limits"]["served_logit_gap"]
    assert control > limit
    assert result["controls"] == {"control_fp8": False}


@pytest.mark.parametrize("workload", SERVE[:1])
def test_serve_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch, workload):
    from zookeeper_tpu.serving.decode.engine import DecodeEngine

    decode = DecodeEngine.decode
    vocab = cells.Cell(workload).config["rehearsal"]["model"]["vocab_size"]

    def altered(self, tokens, lengths):
        out = np.array(decode(self, tokens, lengths))
        return (out + 1) % vocab

    monkeypatch.setattr(DecodeEngine, "decode", altered)
    result, _ = rehearse(capsys, workload)
    assert result["correct"] is False
    assert result["compared"]["served_logit_gap"]["value"] > 0


def test_a_loader_that_repeats_rows_is_not_correct(capsys, monkeypatch):
    """The followed steps run on rows that all differ: a batch whose second
    half repeats its first counts every repeated row."""
    train = cells.Cell(TRAIN).entry_module()
    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 4, 4, 3)).astype(np.float32)
    labels = np.arange(8) % 5
    assert train.bad_rows([(images, labels)], 5) == 0
    repeated = np.concatenate([images[:4], images[:4]])
    assert train.bad_rows([(repeated, labels)], 5) == 4
    assert train.bad_rows([(images, labels), (images, labels)], 5) == 8
    broken = images.copy()
    broken[3, 0, 0, 0] = np.inf
    assert train.bad_rows([(broken, labels)], 5) == 1
    assert train.bad_rows([(images, labels + 3)], 5) == 4  # labels 5, 6, 7, 5
    correct, compared = compare.judge({"batch_rows_bad": 4.0}, {"batch_rows_bad": 0})
    assert not correct and not compared["batch_rows_bad"]["ok"]
