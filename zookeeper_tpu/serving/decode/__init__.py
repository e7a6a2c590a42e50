"""Continuous-batching autoregressive LM decode (docs/DESIGN.md §15).

The token-streaming half of the serving stack — the ROADMAP's
"millions-of-users" interactive workload:

- :class:`DecodeEngine` — the compiled programs over the device page
  pool: a bucketed ``prefill`` (writes a request's KV pages, emits its
  first token) and ONE ``decode_step`` (one token per slot over the
  full slot array), AOT-warmed with the forward engine's
  zero-recompile discipline and ledgered in the ProgramLedger.
- :class:`DecodeScheduler` — slot-refill continuous batching: a
  finished sequence's slot is refilled from the queue without draining
  or recompiling; deadlines/shedding/crash-recovery reuse the PR 4
  machinery; ``generate()`` / :class:`DecodeStream` surface streaming
  results; ``request_swap`` applies weight hot-swaps at slot-array
  drain boundaries (one weight version per sequence).
- :class:`DecodeMetrics` — TTFT + per-token latency histograms, token
  counters, slot-occupancy and KV-page gauges (``zk_decode_*``).
- :class:`LMServingConfig` — the config-system citizen tying model +
  checkpoint + engine + scheduler into a CLI task
  (``examples/serve_lm.py``).
- :mod:`~zookeeper_tpu.serving.decode.pages` — the KV layout
  (docs/DESIGN.md §20): a SHARED device
  page pool with per-slot page tables as runtime operands
  (:class:`PagePool` — free-list/refcount allocator), a radix prefix
  cache over prompt prefixes with copy-on-write at the divergence
  point (:class:`RadixPrefixCache` — warm-prefix admissions skip
  prefill for shared pages), and optional int8 KV quantization with
  per-row scales dequantized inside the attention read.
- :class:`SpeculativeDecoding` — the draft/verify schedule
  (docs/DESIGN.md §18): a small draft model proposes ``k`` tokens per
  slot, one teacher ``decode_verify_paged`` dispatch scores the whole window
  (multi-token KV append + rollback-by-length), greedy acceptance
  keeps the longest prefix match — certified token-identical to plain
  greedy decode at up to ``k + 1`` tokens per teacher dispatch.
"""

from zookeeper_tpu.serving.decode.engine import DecodeEngine
from zookeeper_tpu.serving.decode.pages import (
    PagePool,
    RadixPrefixCache,
    allocate_page_pool,
    page_pool_bytes,
)
from zookeeper_tpu.serving.decode.metrics import DecodeMetrics
from zookeeper_tpu.serving.decode.scheduler import (
    DecodeScheduler,
    DecodeStream,
)
from zookeeper_tpu.serving.decode.service import LMServingConfig
from zookeeper_tpu.serving.decode.speculative import SpeculativeDecoding

__all__ = [
    "DecodeEngine",
    "DecodeMetrics",
    "DecodeScheduler",
    "DecodeStream",
    "LMServingConfig",
    "PagePool",
    "RadixPrefixCache",
    "SpeculativeDecoding",
    "allocate_page_pool",
    "page_pool_bytes",
]
