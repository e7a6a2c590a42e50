"""Token-streaming LM serving: the continuous-batching decode engine
behind a CLI task.

The deployment pairing for ``lm_experiment.py``: train there, stream
tokens here — the interactive half of the north star (train -> ship
weights -> paged-KV continuous-batching decode) in two commands::

    # 1) train a small LM and export/checkpoint it:
    python examples/lm_experiment.py TrainLM epochs=3 \\
        checkpointer.directory=/tmp/lm_ckpt

    # 2) stream generations through the decode engine and report
    #    tokens/s + TTFT percentiles (one JSON line):
    python examples/serve_lm.py ServeLM checkpoint=/tmp/lm_ckpt \\
        seq_len=64 vocab_size=61

    # Fresh-init smoke (no training run needed — compile/latency only):
    python examples/serve_lm.py ServeLM requests=16

    # More slots / longer generations / a live /metrics + /statusz
    # endpoint:
    python examples/serve_lm.py ServeLM engine.slots=16 new_tokens=64 \\
        metrics_port=8080

    # Decode-attention flavor (docs/DESIGN.md §17): auto = the
    # length-aware Pallas pool decode kernel on TPU, the reference
    # einsum elsewhere; force either for an A/B:
    python examples/serve_lm.py ServeLM engine.decode_attention=pallas

    # Speculative decoding (docs/DESIGN.md §18): a distilled-student
    # draft proposes k tokens per slot, one teacher verify dispatch
    # scores the whole window — token-identical to plain greedy, up
    # to k+1 tokens per teacher dispatch:
    python examples/serve_lm.py ServeLM checkpoint=/tmp/lm_ckpt \\
        speculative.enabled=True speculative.k=4 \\
        speculative.draft_checkpoint=/tmp/lm_student_ckpt \\
        speculative.draft_model.num_layers=1

    # The KV cache is a shared page pool + per-slot page tables
    # (docs/DESIGN.md §20): pooled capacity, warm-prefix reuse through
    # the radix prefix cache (CoW at the divergence point), optional
    # int8 rows; the result line reports kv_pool_fill and
    # prefix_cache_hit_rate. A smaller pool than the worst case, int8:
    python examples/serve_lm.py ServeLM engine.pool_pages=64 \\
        engine.kv_quant=int8   # int8 optional; fp stays token-exact

Every request rides the REAL serving path — bucketed prefill into a
KV slot, slot-refill continuous batching, per-token streaming — so the
reported numbers are the decode subsystem's, not a synthetic loop's
(docs/DESIGN.md §15).
"""

from zookeeper_tpu import cli, task
from zookeeper_tpu.serving import DisaggServingConfig, LMServingConfig


@task
class ServeLM(LMServingConfig):
    """Serve a causal LM through the continuous-batching decode engine
    (synthetic deterministic prompt stream; see LMServingConfig)."""


@task
class ServeLMDisagg(DisaggServingConfig):
    """Disaggregated prefill/decode serving (docs/DESIGN.md §22): the
    same request stream through a prefill role and a decode role on
    separate mesh slices, KV pages streamed between them. Also
    reachable as ``ServeLM --disagg``."""


if __name__ == "__main__":
    import sys

    if "--disagg" in sys.argv:
        # ``ServeLM --disagg`` serves the disaggregated topology: swap
        # the task in place so every other key=value applies unchanged
        # (engine.* stays the decode role; prefill_engine.* /
        # transfer.* / partitioner.*_devices are the disagg knobs).
        sys.argv.remove("--disagg")
        if "ServeLM" in sys.argv:
            sys.argv[sys.argv.index("ServeLM")] = "ServeLMDisagg"
        elif "ServeLMDisagg" not in sys.argv:
            sys.argv.insert(1, "ServeLMDisagg")
    cli()
