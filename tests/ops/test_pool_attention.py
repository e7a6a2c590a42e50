"""Op-level certification of the page-pool attention family
(docs/DESIGN.md §20): the gathered-pool reference must be BIT-identical
to the slot-contiguous ``cached_attention`` oracle on every live row
(the gather is pure indirection — same values, same einsums), the
page-table scalar-prefetch kernel rides the §17 tolerance contract
against that reference, and the int8 path's dequantize-inside-the-read
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


def test_pool_kernel_matches_reference_within_tolerance(operands):
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    ref = np.asarray(
        ops.pool_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    kern = np.asarray(
        ops.pool_paged_decode_attention(q, k_pool, v_pool, table, lengths)
    )
    np.testing.assert_allclose(kern, ref, atol=ATOL, rtol=0)


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


def test_pool_kernel_bf16_matches_reference_argmax(operands):
    import jax.numpy as jnp

    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    qb = jnp.asarray(q, jnp.bfloat16)
    kb = jnp.asarray(np.nan_to_num(k_pool, posinf=0, neginf=0), jnp.bfloat16)
    vb = jnp.asarray(np.nan_to_num(v_pool, posinf=0, neginf=0), jnp.bfloat16)
    ref = np.asarray(
        ops.pool_decode_attention(qb, kb, vb, table, lengths),
        np.float32,
    )
    kern = np.asarray(
        ops.pool_paged_decode_attention(qb, kb, vb, table, lengths),
        np.float32,
    )
    # bf16 output grid is coarse; the two paths must agree to the
    # output resolution and pick the same per-head max lane.
    np.testing.assert_allclose(kern, ref, atol=0.04, rtol=0)
    np.testing.assert_array_equal(
        kern.argmax(axis=-1), ref.argmax(axis=-1)
    )


def test_pool_attention_validation_errors(operands):
    q, kc, vc, k_pool, v_pool, table, lengths, ps = operands
    with pytest.raises(ValueError, match="slots, 1, heads"):
        ops.pool_paged_decode_attention(
            q[:, 0], k_pool, v_pool, table, lengths
        )
    with pytest.raises(ValueError, match="page_table"):
        ops.pool_paged_decode_attention(
            q, k_pool, v_pool, table[:2], lengths
        )
    with pytest.raises(ValueError, match="together"):
        ops.pool_paged_decode_attention(
            q, k_pool, v_pool, table, lengths,
            k_scale=np.ones(k_pool.shape[:3] + (4,), np.float32),
        )


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
