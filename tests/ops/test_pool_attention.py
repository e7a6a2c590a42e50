"""Op-level certification of the page-pool attention family
(docs/DESIGN.md §20): the gathered-pool reference must be BIT-identical
to the ``cached_attention`` mathematics over contiguous rows on every
live row (the gather is pure indirection — same values, same einsums),
the page-fetching kernel rides the §17 tolerance contract against that
reference (fp32 within ``2e-6`` absolute for O(1)-scale inputs: the
online softmax's reassociation is the ONLY divergence; argmax exact),
over every cache state the scheduler can produce (lengths are runtime
data: empty, full, partial final page, ragged, page boundaries, garbage
past ``lengths``), and the int8 path's dequantize-inside-the-read
stays within the documented quantization bound with argmax stability.
All CPU (interpret-mode Pallas)."""

import numpy as np
import pytest

from zookeeper_tpu import ops

ATOL = 2e-6  # the §17 kernel's documented fp32 reassociation bound


def scattered_pool(kc, vc, page_size, num_pages, seed=0, poison=1e9):
    """Scatter slot-contiguous caches ``[b, cap, h, d]`` into a
    shuffled page pool whose UNUSED pages are poisoned at ±1e9 — every
    test therefore re-pins the free-page-garbage-harmless contract."""
    rng = np.random.default_rng(seed)
    b, cap, h, d = kc.shape
    m = cap // page_size
    assert num_pages >= b * m
    perm = rng.permutation(num_pages)[: b * m]
    table = perm.reshape(b, m).astype(np.int32)
    sign = rng.choice([-1.0, 1.0], size=(num_pages, page_size, h, d))
    k_pool = (sign * poison).astype(kc.dtype)
    v_pool = (-sign * poison).astype(vc.dtype)
    for s in range(b):
        for p in range(m):
            k_pool[table[s, p]] = kc[s, p * page_size:(p + 1) * page_size]
            v_pool[table[s, p]] = vc[s, p * page_size:(p + 1) * page_size]
    return k_pool, v_pool, table


def folded(pool, head_shards=1):
    """A page-shaped ``[num_pages, page_size, heads, head_dim]`` array
    in the pool's stored form (``ops.fold_kv_pool``)."""
    return np.asarray(ops.fold_kv_pool(pool, head_shards))


def page_shaped(pool, h, d):
    """The inverse of :func:`folded`."""
    return np.asarray(ops.unfold_kv_rows(np.swapaxes(pool, 1, 2), h, d))


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(3)
    b, cap, h, d, ps = 4, 32, 4, 16, 8
    kc = rng.normal(size=(b, cap, h, d)).astype(np.float32)
    vc = rng.normal(size=(b, cap, h, d)).astype(np.float32)
    q = rng.normal(size=(b, 1, h, d)).astype(np.float32)
    # The adversarial length sweep: empty, mid-page, page boundary,
    # last row.
    lengths = np.array([0, 13, 16, 31], np.int32)
    k_pool, v_pool, table = scattered_pool(kc, vc, ps, 24)
    return q, kc, vc, folded(k_pool), folded(v_pool), table, lengths, ps


def test_pool_reference_bit_identical_to_cached_attention(operands):
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    ref = np.asarray(ops.cached_attention(q, kc, vc, lengths))
    pool = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    # BIT-identical, with the unused pool pages poisoned at ±1e9: the
    # gather is indirection only, and masked rows (finite mask value,
    # softmax-underflow to exactly 0.0) cannot perturb one bit.
    np.testing.assert_array_equal(ref, pool)


def test_pool_verify_bit_identical_to_verify_cached(operands):
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    rng = np.random.default_rng(5)
    w = 5
    qv = rng.normal(size=(kc.shape[0], w, kc.shape[2], kc.shape[3]))
    qv = qv.astype(np.float32)
    lens = np.array([0, 7, 16, 27 - w], np.int32)
    ref = np.asarray(ops.verify_cached_attention(qv, kc, vc, lens))
    pool = np.asarray(
        ops.pool_verify_attention(qv, k_pool, v_pool, table, lens)
    )
    np.testing.assert_array_equal(ref, pool)


CAP = 32  # the fixture's rows a slot: 4 pages of 8


@pytest.mark.parametrize(
    "lengths",
    [
        # The fixture's own: empty, mid-page, page boundary, last row.
        [0, 13, 16, 31],
        # length=0: only row 0 (the just-written token) is attended —
        # the first decode step after a 1-token prefill.
        [0, 0, 0, 0],
        # length=capacity-1: every row live, the capacity edge the
        # scheduler truncates at.
        [CAP - 1] * 4,
        # Partial final page: 17 lands 2 rows into the third page.
        [17, 17, 17, 17],
        # Ragged: every slot bounds its own page walk differently.
        [0, CAP - 1, 17, 5],
        # Page boundaries themselves (last row of a page / first row of
        # the next one).
        [7, 8, 23, 24],
    ],
)
def test_pool_kernel_matches_reference_within_tolerance(operands, lengths):
    """Lengths are runtime data: one kernel serves every cache state
    the scheduler can produce."""
    q, kc, vc, k_pool, v_pool, table, _, ps = operands
    lengths = np.asarray(lengths, np.int32)
    ref = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    kern = np.asarray(
        ops.pool_paged_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    np.testing.assert_allclose(kern, ref, atol=ATOL, rtol=0)
    # Token-exactness proxy: per-(slot, head) argmax over head_dim.
    np.testing.assert_array_equal(kern.argmax(axis=-1), ref.argmax(axis=-1))


def test_pool_kernel_dead_table_entries_harmless(operands):
    """Unallocated (-1) table entries past each slot's live pages must
    not perturb either path: the kernel's index map never selects them
    (dead logical pages clamp to the last live page) and the reference
    masks them."""
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    t2 = table.copy()
    # Kill every page strictly past the live region per slot.
    for s, n in enumerate(lengths):
        live = int(n) // ps + 1
        t2[s, live:] = -1
    ref = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    got_ref = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, t2, lengths)
    )
    got_kern = np.asarray(
        ops.pool_paged_decode_attention(q, k_pool, v_pool, t2, lengths)
    )
    np.testing.assert_array_equal(ref, got_ref)
    np.testing.assert_allclose(got_kern, ref, atol=ATOL, rtol=0)


def test_pool_kernel_garbage_rows_inside_a_live_page_never_leak(operands):
    """The slot-refill validity invariant: rows past ``lengths`` inside
    a LIVE page hold a previous occupant's K/V (or prefill padding).
    The kernel on a garbage-poisoned pool must equal the reference on a
    ZEROED one — masked rows contribute exactly nothing, not merely
    approximately."""
    q, kc, vc, _, _, _, _, ps = operands
    lengths = np.array([5, 20, 0, CAP - 1], np.int32)
    live = np.arange(CAP)[None, :, None, None] <= lengths[:, None, None, None]
    # Huge finite garbage: if any masked row leaked it would dominate.
    k_dirty, v_dirty, table = scattered_pool(
        np.where(live, kc, 1e9), np.where(live, vc, -1e9), ps, 24
    )
    k_clean, v_clean, _ = scattered_pool(
        np.where(live, kc, 0.0), np.where(live, vc, 0.0), ps, 24
    )
    ref = np.asarray(
        ops.pool_decode_attention(
            q, folded(k_clean), folded(v_clean), table, lengths
        )
    )
    got = np.asarray(
        ops.pool_paged_decode_attention(
            q, folded(k_dirty), folded(v_dirty), table, lengths
        )
    )
    np.testing.assert_allclose(got, ref, atol=ATOL, rtol=0)


def test_pool_kernel_lengths_at_or_past_capacity_clamp_like_reference(
    operands,
):
    """The reference mask ``ki <= lengths`` attends every row when
    lengths >= capacity; the kernel's clamp must agree (the scheduler
    never sends such lengths, but an idle slot's ride-along must not be
    able to produce NaN)."""
    q, kc, vc, k_pool, v_pool, table, _, ps = operands
    lengths = np.array([CAP, CAP + 7, CAP - 1, 2 * CAP], np.int32)
    ref = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    got = np.asarray(
        ops.pool_paged_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    np.testing.assert_allclose(got, ref, atol=ATOL, rtol=0)
    assert not np.isnan(got).any()


def test_int8_pool_attention_documented_ulp_and_argmax(operands):
    """int8 rows + per-(row, head) scales, dequantized inside the
    read: output within the quantization bound of the fp pool path,
    and the per-head argmax over a logits-like projection stays
    stable — the op-level half of the §20 numerics contract."""
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    h, d = q.shape[2:]
    kq, ks = ops.quantize_kv_rows(page_shaped(k_pool, h, d))
    vq, vs = ops.quantize_kv_rows(page_shaped(v_pool, h, d))
    kq, vq = folded(kq), folded(vq)
    ks, vs = ops.fold_kv_scales(ks), ops.fold_kv_scales(vs)
    fp = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    q8 = np.asarray(
        ops.pool_decode_attention(
            q, np.asarray(kq), np.asarray(vq), table, lengths,
            k_scale=np.asarray(ks), v_scale=np.asarray(vs),
        )
    )
    # Symmetric int8 with per-row scales: relative step 1/254, and the
    # softmax-weighted sum keeps the error in the same class.
    np.testing.assert_allclose(q8, fp, atol=0.05, rtol=0)
    kern8 = np.asarray(
        ops.pool_paged_decode_attention(
            q, np.asarray(kq), np.asarray(vq), table, lengths,
            k_scale=np.asarray(ks), v_scale=np.asarray(vs),
        )
    )
    np.testing.assert_allclose(kern8, q8, atol=ATOL, rtol=0)


def test_quantize_kv_rows_roundtrip_bound():
    rng = np.random.default_rng(11)
    x = (rng.normal(size=(6, 4, 3, 16)) * rng.gamma(1, 4)).astype(
        np.float32
    )
    x[0, 0] = 0.0  # all-zero row: scale 1, exact round trip
    q, s = ops.quantize_kv_rows(x)
    back = np.asarray(ops.dequantize_kv_rows(np.asarray(q), np.asarray(s)))
    amax = np.abs(x).max(axis=-1, keepdims=True)
    # Half-step bound per element, relative to each row's own scale.
    bound = amax / ops.KV_INT8_QMAX * 0.5 + 1e-7
    assert np.all(np.abs(back - x) <= bound)
    np.testing.assert_array_equal(back[0, 0], 0.0)


@pytest.mark.parametrize(
    "case",
    [
        {},
        # An explicit softmax scale reaches the kernel's closure.
        {"scale": 0.25},
        # A table of ONE page a slot: the band's first block, the only
        # block and the finalisation all land on one work item.
        {"pages": 1},
    ],
    ids=["default", "explicit_scale", "single_block_capacity"],
)
def test_pool_kernel_bf16_matches_reference_argmax(operands, case):
    import jax.numpy as jnp

    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    kwargs = {"scale": case["scale"]} if "scale" in case else {}
    if "pages" in case:
        table = table[:, : case["pages"]]
        lengths = np.array([0, 3, ps - 1, 5], np.int32)
    qb = jnp.asarray(q, jnp.bfloat16)
    kb = jnp.asarray(np.nan_to_num(k_pool, posinf=0, neginf=0), jnp.bfloat16)
    vb = jnp.asarray(np.nan_to_num(v_pool, posinf=0, neginf=0), jnp.bfloat16)
    ref = np.asarray(
        ops.pool_decode_attention(qb, kb, vb, table, lengths, **kwargs),
        np.float32,
    )
    kern = np.asarray(
        ops.pool_paged_decode_attention(
            qb, kb, vb, table, lengths, **kwargs
        ),
        np.float32,
    )
    # bf16 output grid is coarse; the two paths must agree to the
    # output resolution and pick the same per-head max lane.
    np.testing.assert_allclose(kern, ref, atol=0.04, rtol=0)
    np.testing.assert_array_equal(
        kern.argmax(axis=-1), ref.argmax(axis=-1)
    )


# -- the kernel's own page fetch: a block of pages a work item -------------

PS, MAX_PAGES = 16, 80  # 1,280 rows a slot: two or three blocks


def block_pages(hkv, d, itemsize=4, window=None):
    """``N`` as the kernel derives it for these shapes."""
    return ops.pool_decode_block_pages(
        PS, ops.kv_row_width(hkv, d), itemsize, MAX_PAGES, window
    )


def block_case(lengths, h, hkv, d, seed=7):
    """q, a scattered pool (pages no table names NaN) and its table."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    kc = rng.normal(size=(b, MAX_PAGES * PS, hkv, d)).astype(np.float32)
    vc = rng.normal(size=(b, MAX_PAGES * PS, hkv, d)).astype(np.float32)
    q = rng.normal(size=(b, 1, h, d)).astype(np.float32)
    k_pool, v_pool, table = scattered_pool(
        kc, vc, PS, b * MAX_PAGES + 5, seed=seed, poison=np.nan
    )
    return q, k_pool, v_pool, table


def only_live_pages(k_pool, v_pool, table, lengths, window=None):
    """The same pool with every page NO slot's band can touch NaN, and
    those table entries ``-1`` (released, or never allocated): a page
    that must not be fetched poisons the result if it is."""
    table = table.copy()
    keep = np.zeros(len(k_pool), bool)
    for s, n in enumerate(lengths):
        first = 0 if window is None else max(n - window + 1, 0) // PS
        keep[table[s, first:n // PS + 1]] = True
        table[s, :first] = -1
        table[s, n // PS + 1:] = -1
    k_pool, v_pool = k_pool.copy(), v_pool.copy()
    k_pool[~keep] = np.nan
    v_pool[~keep] = np.nan
    return k_pool, v_pool, table


LANE, MATMUL = (4, 4, 64), (8, 2, 128)  # (heads, kv heads, head_dim)
N_ROWS = {shape: block_pages(*shape[1:]) * PS for shape in (LANE, MATMUL)}


@pytest.mark.parametrize(
    "shape,lengths",
    [
        # one row, and the block's last row, a block exactly, one more
        (LANE, lambda n: [0, n - 1, n, n + 1, 2 * n + 5]),
        (MATMUL, lambda n: [0, n - 1, n, n + 1, 2 * n + 5]),
        (LANE, lambda n: [0, 0, n + 200, 0]),  # every slot empty but one
        (MATMUL, lambda n: [0, 0, n + 200, 0]),
        (LANE, lambda n: [0, 0, 0, 0]),  # all slots empty
        (MATMUL, lambda n: [0, 0, 0, 0]),
    ],
    ids=[
        "lane-boundaries", "matmul-boundaries", "lane-one-live",
        "matmul-one-live", "lane-all-empty", "matmul-all-empty",
    ],
)
def test_pool_kernel_fetches_blocks_of_pages(shape, lengths):
    """Work items of ``N`` pages, double-buffered across items and
    slots, against the gathered reference: allocator-scattered tables,
    every page past a slot's length NaN and its table entry -1."""
    h, hkv, d = shape
    assert N_ROWS[shape] < MAX_PAGES * PS  # more than one block a slot
    lengths = np.array(lengths(N_ROWS[shape]), np.int32)
    q, k_pool, v_pool, table = block_case(lengths, h, hkv, d)
    ref = np.asarray(
        ops.pool_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths, kv_heads=hkv
        )
    )
    k_pool, v_pool, table = only_live_pages(k_pool, v_pool, table, lengths)
    kern = np.asarray(
        ops.pool_paged_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths, kv_heads=hkv
        )
    )
    np.testing.assert_allclose(kern, ref, atol=ATOL, rtol=0)
    np.testing.assert_array_equal(kern.argmax(-1), ref.argmax(-1))


def finishes_within(seconds, call):
    """``call()``'s result, or a failure if it has not returned in time:
    under the TPU interpreter a wait no copy satisfies blocks for ever,
    as it would on the chip."""
    import threading

    box = {}

    def run():
        try:
            box["result"] = call()
        except BaseException as e:  # handed to the test's own thread
            box["error"] = e

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    thread.join(seconds)
    if thread.is_alive():
        pytest.fail(
            f"the kernel has not ended after {seconds} s: it waits for a "
            "copy that was never started, or for more bytes than were copied"
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


@pytest.mark.parametrize("shape", [LANE, MATMUL], ids=["lane", "matmul"])
@pytest.mark.parametrize("window", [None, 300, 700])
def test_pool_kernel_copies_and_semaphores_under_the_tpu_interpreter(
    shape, window, capfd
):
    """Plain interpret mode copies at ``start`` and ignores ``wait``;
    jax's TPU interpreter models the copies' semaphores and watches for
    races between a copy and the arithmetic. Every copy the kernel
    waits for was started and every wait is for the bytes that were
    copied (else the wait never returns, here as on the chip: a window
    of 300 makes a block of 20 pages, which no 128-key piece divides,
    in buffers of 24), every copy started was waited for (the
    interpreter reports a semaphore left above zero when the kernel
    ends), none lands in the buffer being computed on, and the result is
    the reference's."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from zookeeper_tpu.ops.attention import _pool_paged_decode_call

    h, hkv, d = shape
    n = block_pages(hkv, d, window=window) * PS
    lengths = np.array([0, n - 1, n, 2 * n + 5, 1279, 40], np.int32)
    q, k_pool, v_pool, table = block_case(lengths, h, hkv, d)
    ref = np.asarray(
        ops.pool_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths,
            kv_heads=hkv, window=window,
        )
    )
    k_pool, v_pool, table = only_live_pages(
        k_pool, v_pool, table, lengths, window
    )
    kern = finishes_within(
        240,
        lambda: _pool_paged_decode_call.__wrapped__(
            q, folded(k_pool), folded(v_pool), table, lengths, None, None,
            scale=d ** -0.5, kv_heads=hkv, window=window,
            interpret=pltpu.InterpretParams(detect_races=True),
        ),
    )
    kern = np.asarray(kern)
    assert "non-zero count" not in capfd.readouterr().out
    assert not interpret_pallas_call.races.races_found
    np.testing.assert_allclose(kern, ref, atol=ATOL, rtol=0)


def test_pool_kernel_repeated_table_entries():
    """Two slots whose tables name the same pages (a shared prefix),
    and a slot that names one page twice: a page is fetched wherever a
    table says it lies, as often as it is named."""
    h, hkv, d = LANE
    n = N_ROWS[LANE]
    lengths = np.array([n + 40, n + 7, 3 * PS], np.int32)
    q, k_pool, v_pool, table = block_case(lengths, h, hkv, d)
    table = table.copy()
    table[1, : n // PS] = table[0, : n // PS]
    table[2, 1] = table[2, 0]
    ref = np.asarray(
        ops.pool_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths
        )
    )
    k_pool, v_pool, table = only_live_pages(k_pool, v_pool, table, lengths)
    kern = np.asarray(
        ops.pool_paged_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths
        )
    )
    np.testing.assert_allclose(kern, ref, atol=ATOL, rtol=0)


def test_int8_pool_kernel_fetches_blocks_of_pages():
    """int8 rows ride the block fetch, their scale pages beside them:
    lengths on both sides of a block's end."""
    h, d = 8, 64
    n = block_pages(h, d, itemsize=1) * PS
    assert n < MAX_PAGES * PS
    lengths = np.array([0, n - 1, n, n + 1, n + 200], np.int32)
    q, k_pool, v_pool, table = block_case(lengths, h, h, d)
    k_pool, v_pool = np.nan_to_num(k_pool), np.nan_to_num(v_pool)
    kq, ks = ops.quantize_kv_rows(k_pool)
    vq, vs = ops.quantize_kv_rows(v_pool)
    operands = (
        q, folded(kq), folded(vq), table, lengths,
    )
    scales = dict(
        k_scale=np.asarray(ops.fold_kv_scales(ks)),
        v_scale=np.asarray(ops.fold_kv_scales(vs)),
    )
    ref = np.asarray(ops.pool_decode_attention(*operands, **scales))
    kern = np.asarray(ops.pool_paged_decode_attention(*operands, **scales))
    np.testing.assert_allclose(kern, ref, atol=2 * ATOL, rtol=0)
    np.testing.assert_array_equal(kern.argmax(-1), ref.argmax(-1))


@pytest.mark.parametrize("window", [None, 20, 700])
def test_host_work_items_equal_the_kernels_grid(window):
    """The host function behind the ``decode_kv_blocks`` event counts
    what the kernel's grid holds on the device."""
    import jax

    from zookeeper_tpu.ops.attention import _pool_work_items

    rng = np.random.default_rng(2)
    lengths = np.concatenate(
        [[0, PS - 1, PS, MAX_PAGES * PS - 1], rng.integers(0, 1280, 28)]
    ).astype(np.int32)
    for n in (1, 8, 32):
        steps = -(-MAX_PAGES // n)
        slot, step, total = jax.jit(
            _pool_work_items, static_argnums=(1, 2, 3, 4)
        )(lengths, PS, window, n, steps)
        items, live, capacity = ops.pool_decode_work(
            lengths, page_size=PS, max_pages=MAX_PAGES, block_pages=n,
            window=window,
        )
        assert int(total[0]) == items
        assert capacity == items * n and items <= live <= capacity
        # every slot's items, in slot order, steps counted from 0
        slot, step = np.asarray(slot)[:items], np.asarray(step)[:items]
        assert (np.diff(slot) >= 0).all() and set(slot) == set(range(32))
        assert (step[np.r_[True, np.diff(slot) > 0]] == 0).all()
        first = 0 if window is None else np.maximum(lengths - window + 1, 0) // PS
        np.testing.assert_array_equal(
            live, int((lengths // PS - first + 1).sum())
        )


def test_sharded_pool_kernel_two_head_shards():
    """The mesh twin on a 2-shard mesh: each device fetches its own
    head shard of every page; blocks past one work item."""
    import jax
    from jax.sharding import Mesh

    h, hkv, d = 8, 8, 64
    n = block_pages(hkv // 2, d) * PS
    assert n < MAX_PAGES * PS
    lengths = np.array([0, n - 1, n + 1, n + 300], np.int32)
    q, k_pool, v_pool, table = block_case(lengths, h, hkv, d)
    single = np.asarray(
        ops.pool_paged_decode_attention(
            q, folded(k_pool), folded(v_pool), table, lengths
        )
    )
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    with mesh:
        sharded = np.asarray(
            ops.sharded_pool_paged_decode_attention(
                q, folded(k_pool, 2), folded(v_pool, 2), table, lengths,
                mesh=mesh, data_axes=("data",), model_axis="model",
            )
        )
    assert np.isfinite(single).all()
    np.testing.assert_allclose(sharded, single, atol=ATOL, rtol=0)


@pytest.mark.parametrize(
    "case, match",
    [
        ("q_rank", "slots, 1, heads"),
        ("pools_differ", "must be identical"),
        ("pool_heads", "does not match q"),
        ("head_dim", "off the pool kernel's geometry"),
        ("table_rows", "page_table"),
        ("one_scale", "together"),
        ("window", "window=0"),
    ],
)
def test_pool_attention_validation_errors(operands, case, match):
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    kwargs = {}
    if case == "q_rank":
        q = q[:, 0]
    elif case == "pools_differ":
        v_pool = v_pool[:, :, :4]
    elif case == "pool_heads":
        # 4 query heads are no multiple of 3 key/value heads.
        kwargs["kv_heads"] = 3
    elif case == "head_dim":
        # 20 is on the sublane quantum's wrong side and divides no 128.
        q = np.zeros((4, 1, 4, 20), np.float32)
        k_pool = v_pool = np.zeros((24, 1, ps, 128), np.float32)
    elif case == "table_rows":
        table = table[:2]
    elif case == "one_scale":
        kwargs["k_scale"] = np.ones(k_pool.shape[:3] + (4,), np.float32)
    elif case == "window":
        kwargs["window"] = 0
    with pytest.raises(ValueError, match=match):
        ops.pool_paged_decode_attention(
            q, k_pool, v_pool, table, lengths, **kwargs
        )


def test_supported_predicate():
    """One geometry rule, the pool kernel's: a head's lanes are summed
    inside one 128-lane register, so head_dim divides 128 (and sits on
    the fp32 sublane quantum); off it the engine degrades to the
    reference einsum (``DecodeEngine``)."""
    assert ops.decode_attention_supported(4, 64)
    assert ops.decode_attention_supported(1, 8)
    assert ops.decode_attention_supported(4, 128)
    assert not ops.decode_attention_supported(4, 20)
    assert not ops.decode_attention_supported(4, 7)
    assert not ops.decode_attention_supported(4, 24)  # 8 | 24, 24 ∤ 128
    assert not ops.decode_attention_supported(0, 64)


@pytest.mark.slow
def test_sharded_pool_kernel_on_mesh(operands):
    """The shard_map composition on the 8-virtual-device mesh: slots/
    table/lengths over the data axes, pool heads over the model axis,
    zero collectives — output equal to the single-device kernel."""
    import jax
    from jax.sharding import Mesh

    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    devices = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("data", "model"))
    single = np.asarray(
        ops.pool_paged_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    h, d = q.shape[2:]
    with mesh:
        sharded = np.asarray(
            ops.sharded_pool_paged_decode_attention(
                q,
                folded(page_shaped(k_pool, h, d), head_shards=2),
                folded(page_shaped(v_pool, h, d), head_shards=2),
                table, lengths,
                mesh=mesh, data_axes=("data",), model_axis="model",
            )
        )
        replicated = np.asarray(
            ops.sharded_pool_paged_decode_attention(
                q, k_pool, v_pool, table, lengths,
                mesh=mesh, replicated=True,
            )
        )
    np.testing.assert_allclose(sharded, single, atol=ATOL, rtol=0)
    np.testing.assert_allclose(replicated, single, atol=ATOL, rtol=0)


# -- the kernel's size: what a program pays to trace and lower it ----------


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr):
    """Equations of a jaxpr, those of every jaxpr inside it included."""
    return sum(
        1 + sum(_equations(inner) for inner in _sub_jaxprs(eqn))
        for eqn in jaxpr.eqns
    )


def _named(jaxpr, primitive, found=None):
    """Every equation of ``primitive`` in a jaxpr, at any depth."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for inner in _sub_jaxprs(eqn):
            _named(inner, primitive, found)
    return found


#: Equations of the kernel's jaxpr at ``gpt2_xl_24l``'s shapes on the tree
#: before the kernel fetched its own pages (PR 26: one page a grid step,
#: its 13-column loop traced once). PR 27's kernel, refused for what its
#: size cost ``setup_s``, counted 3,192: every equation is traced (about
#: 1.2 ms each inside the serving program on the chip's host) and lowered
#: by every process that builds a decode program.
PARENT_KERNEL_EQUATIONS = 770


def test_kernel_body_does_not_grow_with_the_block():
    """At the serving cells' shapes (48 slots, 64 pages of 16, 25 heads
    of 64: a block of 16 pages, two 128-key sub-blocks, 13 lane columns)
    the traced kernel holds one copy of the lane path's column a row
    height (the 128 keys of a whole sub-block, and a page for what is
    left over), inside loops over the block's rows and the row's
    columns: no more than 1.5 times the equations of the kernel that
    read one page a grid step (it holds fewer)."""
    import jax
    import jax.numpy as jnp

    slots, heads, d, max_pages, ps = 48, 25, 64, 64, 16
    width = ops.kv_row_width(heads, d)
    assert ops.pool_decode_block_pages(ps, width, 2, max_pages) == 16
    pool = jax.ShapeDtypeStruct((slots * max_pages, 1, ps, width), jnp.bfloat16)

    def attend(q, k, v, table, lengths):
        return ops.pool_paged_decode_attention(
            q, k, v, table, lengths, interpret=False
        )

    program = jax.make_jaxpr(attend)(
        jax.ShapeDtypeStruct((slots, 1, heads, d), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((slots, max_pages), np.int32),
        jax.ShapeDtypeStruct((slots,), np.int32),
    )
    (call,) = _named(program.jaxpr, "pallas_call")
    kernel = call.params["jaxpr"]
    assert _equations(kernel) <= 1.5 * PARENT_KERNEL_EQUATIONS
    # A column's masked lane reductions (one a head: two heads of 64
    # lanes) stand in the kernel once a row height, 128 keys and a page,
    # not once a column of the row nor once a sub-block of the block.
    lane_sums = [
        eqn.outvars[0].aval.shape
        for eqn in _named(kernel, "reduce_sum") if eqn.params["axes"] == (1,)
    ]
    assert sorted(lane_sums) == [(16,), (16,), (128,), (128,)]


def test_program_of_24_layers_traces_the_kernel_once(monkeypatch):
    """``_pool_paged_decode_call`` is a ``jax.jit`` of its own: a program
    that attends once a layer traces the kernel once and holds the same
    jaxpr 24 times, so lowering builds it once too."""
    import jax
    from jax.experimental import pallas as pl

    traced = []
    pallas_call = pl.pallas_call

    def counting(kernel, *args, **kwargs):
        traced.append(kernel)
        return pallas_call(kernel, *args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", counting)
    lengths = np.array([0, 70, 300, 1100], np.int32)
    q, k_pool, v_pool, table = block_case(lengths, *LANE)
    k_pool, v_pool = folded(np.nan_to_num(k_pool)), folded(np.nan_to_num(v_pool))

    def layers(q):
        for _ in range(24):
            q = ops.pool_paged_decode_attention(q, k_pool, v_pool, table, lengths)
        return q

    jax.clear_caches()
    program = jax.make_jaxpr(layers)(q)
    assert len(traced) == 1
    calls = [
        eqn for eqn in _named(program.jaxpr, "jit") + _named(program.jaxpr, "pjit")
        if eqn.params["name"] == "_pool_paged_decode_call"
    ]
    assert len(calls) == 24
    assert len({id(eqn.params["jaxpr"]) for eqn in calls}) == 1


def test_probe_rehearses_both_paths_on_the_cpu():
    """``tools/probe_pool_decode.py --rehearse`` walks the probe's whole
    control flow at a tiny size (kernel, copies alone, arithmetic alone,
    a second block size): every line names the CPU, carries no time, and
    the kernel's result is finite."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    done = subprocess.run(
        [
            sys.executable, os.path.join(root, "tools", "probe_pool_decode.py"),
            "--rehearse", "--halves", "--block-bytes", "65536",
        ],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    assert {(x["shape"], x["variant"]) for x in lines} == {
        (shape, variant)
        for shape in ("rehearsal.lane", "rehearsal.matmul")
        for variant in ("kernel", "copies_only", "arithmetic_only")
    }
    assert len({x["block_pages"] for x in lines if "lane" in x["shape"]}) == 2
    for line in lines:
        assert line["device"]["platform"] == "cpu"
        assert line["ms_per_call"] is None and line["trace_s"] > 0
        assert line.get("finite", True)
