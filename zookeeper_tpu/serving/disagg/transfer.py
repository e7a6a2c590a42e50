"""KV page handoff between mesh slices (docs/DESIGN.md §22).

The unit of disaggregated serving is a completed prefill's device
state: the pool pages its prompt wrote. :class:`PageTransfer` moves
exactly those pages from the prefill engine's pool into freshly
adopted pages of the decode engine's pool:

1. **Export** (source, read-only): one compiled gather lifts the page
   ids into a contiguous ``transfer_width``-page block
   (``DecodeEngine.export_pages`` — the source pool is never donated;
   prefix-cache-shared pages may be mid-read by another lane).
2. **Move**: ``jax.device_put`` of the block onto the destination
   pool's shardings — a direct device-to-device copy when the runtime
   supports the route (same process, reachable slices). When it does
   not — or ``host_bounce=True`` forces the portable path — the block
   bounces through host memory under an explicit
   ``jax.transfer_guard("allow")`` scope, so a transfer-guarded
   process still fails LOUDLY on accidental device->host syncs
   elsewhere while this deliberate one stays legal.
3. **Import** (destination, donated): one compiled scatter lands the
   block at the adopted page ids (``DecodeEngine.import_pages`` —
   padding lanes carry the OOB sentinel and write nowhere).

Refcount custody is the CALLER's (the disagg scheduler): destination
pages are adopted BEFORE ``move`` and the source lane is released only
AFTER it returns — both pools hold ``leak_check() == 0`` at every
instant, including across an injected ``FaultPlan.fail_page_transfer``
(this module raises :class:`PageTransferError`; the scheduler unwinds
the adopted pages and fails only the victim stream).
"""

import time
from collections import deque
from typing import Optional, Sequence

import numpy as np

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.observability import trace as _trace

__all__ = ["PageTransfer", "PageTransferError"]


class PageTransferError(RuntimeError):
    """A page handoff failed (injected or real): the victim stream is
    failed cleanly, the destination pages are unwound, and BOTH pools
    stay leak-free — the scheduler's unwind contract."""


@component
class PageTransfer:
    """Mover of KV page blocks between two paged engines' pools (see
    module docstring). ``bind(src_engine, dst_engine)`` validates the
    geometry; ``move`` is the per-handoff call."""

    #: Force the portable host-bounce path even when a direct
    #: device-to-device put would work (A/B lever for the §22 transfer
    #: cost model; the direct path is attempted first by default).
    host_bounce: bool = Field(False)

    def bind(
        self, src_engine, dst_engine, metrics=None
    ) -> "PageTransfer":
        """Attach the two engines. Both must have
        the SAME transfer block geometry (page size and pages-per-block
        — one compiled shape serves every handoff in each direction)."""
        src_engine._require_bound()
        dst_engine._require_bound()
        if int(src_engine.page_size) != int(dst_engine.page_size):
            raise ValueError(
                f"page_size mismatch across roles: src="
                f"{src_engine.page_size} dst={dst_engine.page_size} — "
                "a transferred page would land misaligned."
            )
        if int(src_engine.transfer_width) != int(dst_engine.transfer_width):
            raise ValueError(
                f"transfer_width mismatch: src={src_engine.transfer_width}"
                f" dst={dst_engine.transfer_width} pages — align the "
                "roles' seq_buckets so one block shape serves both."
            )
        object.__setattr__(self, "_src", src_engine)
        object.__setattr__(self, "_dst", dst_engine)
        object.__setattr__(self, "_metrics", metrics)
        # Mutable accounting lives in containers (the component is
        # frozen): lifetime totals + a bounded latency window for the
        # p50 the result line / statusz report.
        object.__setattr__(
            self,
            "_stats",
            {"handoffs": 0, "pages": 0, "bytes": 0, "bounces": 0},
        )
        object.__setattr__(self, "_ms_window", deque(maxlen=512))
        return self

    def _require_bound(self) -> None:
        if getattr(self, "_src", None) is None:
            raise RuntimeError(
                "PageTransfer is not bound: call transfer.bind("
                "prefill_engine, decode_engine) first."
            )

    # -- the handoff -----------------------------------------------------

    def move(
        self,
        src_page_ids: Sequence[int],
        dst_page_ids: Sequence[int],
        rid: Optional[int] = None,
    ) -> float:
        """Move ``src_page_ids``'s pages into ``dst_page_ids`` (equal
        lengths; the destination ids come from
        ``PagePool.adopt_slot``). Returns the wall milliseconds.
        Raises :class:`PageTransferError` on an injected
        ``FaultPlan.fail_page_transfer`` BEFORE touching either device
        — the deterministic chaos seam."""
        from zookeeper_tpu.resilience import faults

        self._require_bound()
        if len(src_page_ids) != len(dst_page_ids):
            raise ValueError(
                f"page id lists must pair up: {len(src_page_ids)} src "
                f"vs {len(dst_page_ids)} dst."
            )
        plan = faults.active()
        if plan is not None and plan.take_fail_page_transfer():
            raise PageTransferError(
                "injected page-transfer failure "
                "(FaultPlan.fail_page_transfer): the handoff block "
                "never reached the decode pool."
            )
        n = len(src_page_ids)
        t0 = time.perf_counter()
        with _trace.span(
            "page_transfer",
            rid=rid,
            attrs={"pages": n} if _trace.enabled() else None,
        ):
            block = self._src.export_pages(src_page_ids)
            moved = self._place(block)
            self._dst.import_pages(moved, dst_page_ids)
        dt_ms = (time.perf_counter() - t0) * 1e3
        nbytes = self._block_bytes(block, n)
        stats = self._stats
        stats["handoffs"] += 1
        stats["pages"] += n
        stats["bytes"] += nbytes
        self._ms_window.append(dt_ms)
        if self._metrics is not None:
            self._metrics.record_transfer(n, nbytes, dt_ms)
        return dt_ms

    def _place(self, block):
        """Land the block on the destination pool's devices: direct
        device-to-device put when the runtime can route it, else the
        transfer-guarded host bounce. Sharding comes from the LIVE
        destination pool leaves — NamedSharding is shape-agnostic along
        the (replicated) pages axis, so the pool's own placement
        applies to the W-page block verbatim."""
        import jax

        dst_shardings = jax.tree.map(
            lambda leaf: leaf.sharding, self._dst._cache
        )
        if not self.host_bounce:
            try:
                return jax.tree.map(
                    lambda leaf, sh: jax.device_put(leaf, sh),
                    block,
                    dst_shardings,
                )
            except (
                ValueError,
                RuntimeError,
                NotImplementedError,
            ):
                # Route unavailable (e.g. a backend without direct
                # cross-slice puts): fall through to the bounce.
                pass
        self._stats["bounces"] += 1
        host = jax.tree.map(np.asarray, block)
        with jax.transfer_guard("allow"):
            return jax.tree.map(
                lambda leaf, sh: jax.device_put(leaf, sh),
                host,
                dst_shardings,
            )

    @staticmethod
    def _block_bytes(block, n_pages: int) -> int:
        """Real payload bytes of a handoff: every leaf's per-page bytes
        x the REAL page count (padding lanes carry garbage the import
        drops — they ride the wire but are not payload)."""
        import jax

        total = 0
        for leaf in jax.tree.leaves(block):
            w = int(np.shape(leaf)[0])
            total += (leaf.nbytes // max(1, w)) * n_pages
        return int(total)

    # -- accounting ------------------------------------------------------

    @property
    def handoffs(self) -> int:
        return self._stats["handoffs"] if hasattr(self, "_stats") else 0

    def transfer_ms_p50(self) -> float:
        """Median handoff wall time over the recent window (-1 before
        any handoff)."""
        window = getattr(self, "_ms_window", None)
        if not window:
            return -1.0
        return float(np.percentile(np.asarray(window), 50))

    def status(self) -> dict:
        """The ``/statusz`` ``transfer`` section."""
        self._require_bound()
        stats = self._stats
        return {
            "handoffs_total": int(stats["handoffs"]),
            "pages_total": int(stats["pages"]),
            "bytes_total": int(stats["bytes"]),
            "host_bounces": int(stats["bounces"]),
            "host_bounce_forced": bool(self.host_bounce),
            "transfer_width": int(self._src.transfer_width),
            "transfer_ms_p50": round(self.transfer_ms_p50(), 4),
        }
