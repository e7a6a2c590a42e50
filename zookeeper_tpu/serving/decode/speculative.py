"""Speculative decoding: a draft model proposes, the teacher verifies
(docs/DESIGN.md §18).

The decode engine's throughput is bounded by one teacher ``decode_step``
dispatch per emitted token. Greedy speculative decoding amortizes that
to one ``verify_step`` dispatch per window: a small DRAFT model
autoregressively proposes ``k`` tokens per slot, one batched teacher
``decode_verify_paged`` scores all ``k + 1`` window positions in a
single dispatch (multi-token KV append through the page table), and the
scheduler keeps the longest prefix where the draft's proposals match
the teacher's greedy argmax — plus the teacher's own token at the first
mismatch, which the verify already computed for free. Greedy
speculation is LOSSLESS by construction: every emitted token is the
teacher's argmax given the committed prefix, so speculative output is
certified token-identical to plain greedy decode — a perfect fit for
this repo's bit-exactness test policy (the rejected suffix is rolled
back by simply not advancing ``lengths``; garbage rows beyond a slot's
length are already certified harmless by the §17 poisoned-row tests).

This component owns the DRAFT half: a second :class:`DecodeEngine`
mirroring the teacher's slot/bucket/capacity geometry (same
``page_pool_sharding`` seam, same partitioner, its own worst-case page
pool — every slot owns its full share for a stream's lifetime, so no
draft dispatch waits on a page — and AOT program family, ledgered
``draft_*`` with ``compile_count`` pinned zero post-warmup). The repo
uniquely already owns both model halves: ``training/distill.py`` produces aligned student/teacher pairs — point
``draft_checkpoint`` at the distilled student's export. The two-model
slot SCHEDULE lives in :class:`DecodeScheduler` (``_decode_spec``);
the config surface is ``LMServingConfig.speculative``.
"""

import logging
from typing import Any, Optional

from zookeeper_tpu.core import ComponentField, Field, component
from zookeeper_tpu.models.base import Model
from zookeeper_tpu.models.transformer import TransformerLM
from zookeeper_tpu.serving.decode.engine import DecodeEngine

logger = logging.getLogger(__name__)

__all__ = ["SpeculativeDecoding"]


@component
class SpeculativeDecoding:
    """Config + runtime binding for the draft/verify schedule.

    Fields are the ``LMServingConfig.speculative`` CLI surface
    (``speculative.enabled=True speculative.k=4
    speculative.draft_checkpoint=/ckpt/student``); :meth:`bind` attaches
    the runtime objects — a built draft module + weights and the
    TEACHER engine whose geometry the internal draft engine mirrors.
    """

    #: Master switch: False (default) = plain decode, the speculative
    #: machinery entirely dormant.
    enabled: bool = Field(False)
    #: Draft tokens proposed per window. Each window costs ``k`` draft
    #: dispatches + ONE teacher verify and emits between 1 and ``k + 1``
    #: tokens (acceptance-dependent), so the teacher dispatch rate drops
    #: by up to ``k + 1``x. Raise k when acceptance is high (draft
    #: closely agrees with the teacher), lower it when rejections waste
    #: draft work — docs/DESIGN.md §18 has the cost model.
    k: int = Field(4)
    #: Draft model geometry (built at the teacher's seq_len/vocab) —
    #: the distilled student's config, typically far smaller than the
    #: teacher. Used by ``LMServingConfig`` to build the draft module;
    #: programmatic callers pass a built module to :meth:`bind`.
    draft_model: Model = ComponentField(TransformerLM)
    #: ``save_model`` export / Checkpointer directory holding the draft
    #: weights (the distill pipeline's student export). None = fresh-
    #: init draft_model weights — program-shape smoke only (acceptance
    #: will be ~chance), flagged loudly at bind.
    draft_checkpoint: Optional[str] = Field(None)
    #: EMA-vs-raw selection for the draft checkpoint (same contract as
    #: the teacher's ``weights``).
    draft_weights: str = Field("auto")

    # -- binding ---------------------------------------------------------

    def bind(
        self,
        engine: DecodeEngine,
        draft_module: Any,
        draft_params: Any,
        draft_state: Any = None,
        *,
        partitioner: Any = None,
    ) -> "SpeculativeDecoding":
        """Attach the draft: builds + warms an internal
        :class:`DecodeEngine` over ``draft_module`` mirroring the
        TEACHER ``engine``'s slot/bucket/capacity geometry (so admission
        groups and slot ids map 1:1 and the draft pool shards through
        the same ``page_pool_sharding`` seam), and pre-compiles the
        verify widths — the teacher's ``k + 1`` window and the draft's
        width-2 catch-up/append program. Raises ``ValueError`` on
        config bugs (bad k, vocab mismatch, draft positional table too
        short for the prompt buckets) — the loud half of the
        "degrade loudly" contract lives in ``LMServingConfig``."""
        from zookeeper_tpu.core import configure

        engine._require_bound()
        if int(self.k) < 1:
            raise ValueError(f"speculative.k={self.k} must be >= 1.")
        teacher_vocab = getattr(engine._module, "vocab_size", None)
        draft_vocab = getattr(draft_module, "vocab_size", None)
        if (
            teacher_vocab is not None
            and draft_vocab is not None
            and int(teacher_vocab) != int(draft_vocab)
        ):
            raise ValueError(
                f"draft vocab_size {draft_vocab} != teacher vocab_size "
                f"{teacher_vocab}: draft proposals would be scored "
                "against a different token id space — speculation would "
                "be silently meaningless. Build the draft at the "
                "teacher's vocabulary."
            )
        for role, module in (
            ("teacher", engine._module), ("draft", draft_module)
        ):
            if any(getattr(module, "slot_state_spec", tuple)()):
                raise ValueError(
                    f"speculative decoding with a {role} that has "
                    "recurrent (state-space) state is not implemented: a "
                    "rejected draft token is rolled back by not advancing "
                    "`lengths`, and that cannot undo a recurrence "
                    "(ROADMAP.md, Reach)."
                )
        if any(getattr(draft_module, "window_layers", ())):
            raise ValueError(
                "a draft model with window layers is not implemented: a "
                "draft slot takes its whole share of ONE page group at "
                "its prefill (PagePool.adopt_slot). Draft with full "
                "layers only."
            )
        draft = DecodeEngine()
        configure(
            draft,
            {
                # Mirror the TEACHER geometry exactly: one admission
                # plan serves both caches, and the draft rides the same
                # mesh/sharding seam.
                "slots": int(engine.slots),
                "seq_buckets": tuple(engine._seq_buckets),
                "prefill_buckets": tuple(engine._prefill_buckets),
                "kv_capacity": int(engine.capacity),
                "page_size": int(engine.page_size),
                "decode_attention": str(engine.decode_attention),
                "ledger_prefix": "draft_",
                # A private cache nobody shares, provisioned for the
                # worst case (pool_pages=-1): the scheduler hands each
                # slot its full share at the draft prefill.
                "prefix_cache": False,
            },
            name="speculative_draft_engine",
        )
        draft.bind(
            draft_module,
            draft_params,
            draft_state,
            partitioner=(
                partitioner if partitioner is not None
                else engine._partitioner
            ),
        )
        # Warm the full draft grid + both verify widths so the first
        # speculative window never waits on XLA and compile_count pins
        # at zero growth under traffic for BOTH engines.
        draft.warmup()
        draft.warmup_verify(2)  # catch-up gap (<=1) + current token
        engine.warmup_verify(int(self.k) + 1)
        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "_draft_engine", draft)
        # Lifetime acceptance accounting (the /statusz + result-line
        # numbers; the metrics counters are the scrapeable twins).
        object.__setattr__(self, "_proposed_total", 0)
        object.__setattr__(self, "_accepted_total", 0)
        logger.info(
            "speculative decoding bound: k=%d, draft %s (%d layers), "
            "verify window %d",
            int(self.k),
            type(draft_module).__name__,
            int(getattr(draft_module, "num_layers", -1)),
            int(self.k) + 1,
        )
        return self

    def _require_bound(self) -> None:
        if getattr(self, "_draft_engine", None) is None:
            raise RuntimeError(
                "SpeculativeDecoding is not bound: call spec.bind("
                "teacher_engine, draft_module, draft_params) first."
            )

    # -- runtime surface (read by the scheduler) -------------------------

    @property
    def engine(self) -> DecodeEngine:
        """The teacher engine this binding mirrors."""
        self._require_bound()
        return self._engine

    @property
    def draft_engine(self) -> DecodeEngine:
        self._require_bound()
        return self._draft_engine

    @property
    def window(self) -> int:
        """Teacher verify width: ``k`` draft tokens + the current input
        token (all ``k + 1`` positions scored in one dispatch)."""
        return int(self.k) + 1

    def record_window(self, proposed: int, accepted: int) -> None:
        """Lifetime acceptance accounting (scheduler commit phase,
        called under the scheduler lock)."""
        object.__setattr__(
            self, "_proposed_total", self._proposed_total + int(proposed)
        )
        object.__setattr__(
            self, "_accepted_total", self._accepted_total + int(accepted)
        )

    @property
    def acceptance_rate(self) -> float:
        """Lifetime accepted-draft fraction (-1 before any window)."""
        proposed = getattr(self, "_proposed_total", 0)
        if not proposed:
            return -1.0
        return self._accepted_total / proposed

    def status(self) -> dict:
        """The ``/statusz`` ``speculative`` sub-section: k, live
        acceptance, and the draft engine's compile discipline."""
        self._require_bound()
        draft = self._draft_engine
        return {
            "enabled": True,
            "k": int(self.k),
            "acceptance_rate": round(self.acceptance_rate, 4),
            "proposed_tokens": int(self._proposed_total),
            "accepted_tokens": int(self._accepted_total),
            "draft_compiles": draft.compile_count,
            "draft_recompiles_detected": draft.recompiles_detected,
            "draft_decode_attention": draft.decode_attention_flavor,
        }
