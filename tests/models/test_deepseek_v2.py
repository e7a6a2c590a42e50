"""The DeepSeek-V2-shaped model at a tiny size on the CPU against the plain
reference (``benchmarks/reference/deepseek_v2.py``, the expanded form):
the full pass, the absorbed decode against the expanded attention, a
prefill then decode through a latent page pool and a prefix's rows then
an extend, against the full pass; the two latent kernels against their
oracles; DeepSeek's YaRN and the router's switch against numbers worked
out here."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import deepseek_v2_tiny as tiny  # noqa: E402

from zookeeper_tpu.models.transformer import (  # noqa: E402
    _pool_write_pages,
    rope_inv_freq,
    yarn_score_factor,
)
from zookeeper_tpu.ops import kv_row_width  # noqa: E402
from zookeeper_tpu.ops import mla  # noqa: E402
from zookeeper_tpu.ops.moe import route_top_k  # noqa: E402
from zookeeper_tpu.serving.decode.pages import allocate_page_pool  # noqa: E402

reference = tiny.load_reference()
PAGE, PAGES = 8, 12  # a sequence's pages: 96 positions


@pytest.fixture(scope="module")
def built():
    return tiny.build()


def reference_logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, tiny.MODEL, jnp.asarray(tokens)))


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_full_pass_matches_the_reference(built, attention):
    """float32 on both sides, the program's rotation table and YaRN
    factors its own and the reference's worked out from the config: the
    sums differ in order only (the flash kernel's blocks, the router's
    sorted rows), hence 1e-4."""
    module, params = built
    module = module.clone(attention=attention)
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (2, tiny.POSITIONS), 0, tiny.VOCAB
    )
    got = module.apply({"params": params}, tokens)
    want = np.stack([reference_logits(params, t) for t in tokens])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert float(np.std(want)) > 0.5


@pytest.mark.parametrize(
    "fault",
    [
        {"lowp": True}, {"mscale_off": True}, {"yarn_on_rotation": True},
        {"k_pe_unrotated": True}, {"kv_norm_off": True},
        {"topk_renormalised": True}, {"shared_dropped": True},
        {"prefix_skipped": (60, 40)},
    ],
    ids=lambda f: next(iter(f)),
)
def test_the_reference_control_and_planted_faults_move_the_pass(built, fault):
    """Each changes the logits by far more than the model differs from the
    reference; skipping the prefix changes no position before the first
    that skips it."""
    _, params = built
    tokens = jax.random.randint(jax.random.PRNGKey(6), (64,), 0, tiny.VOCAB)
    with jax.default_matmul_precision("highest"):
        sound = reference.forward(params, tiny.MODEL, tokens)
        faulty = reference.forward(params, tiny.MODEL, tokens, **fault)
    assert float(jnp.max(jnp.abs(sound - faulty))) > 100 * 1e-4
    if "prefix_skipped" in fault:
        np.testing.assert_array_equal(sound[:60], faulty[:60])


def test_yarn_inverse_frequencies_and_score_factor_worked_out_here():
    """DeepSeek-V2-Lite's rope_scaling by hand: d(b) = 64 ln(4096 / (2 pi
    b)) / (2 ln 10^4) puts low = floor(d(32)) = 10 and high = ceil(d(1)) =
    23; below 10 the plain table, above 23 a fortieth of it, a linear ramp
    between. cos and sin take m(0.707) / m(0.707) = 1 and the score
    (0.1 x 0.707 x ln 40 + 1)^2 = 1.260804^2 = 1.58963."""
    yarn = (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    inv, on_rotation = rope_inv_freq(64, 10000.0, yarn)
    inv = np.asarray(inv, np.float64)
    assert on_rotation == 1.0
    assert yarn_score_factor(yarn) == pytest.approx(1.589627, abs=2e-6)
    assert yarn_score_factor(yarn[:4]) == 1.0
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    ramp = 6.0 / 13.0  # i = 16
    assert inv[16] == pytest.approx(0.01 * (1 - ramp + ramp / 40.0), rel=1e-6)
    # mellum2's convention, four numbers: cos and sin take 0.1 ln 40 + 1
    assert rope_inv_freq(64, 10000.0, yarn[:4])[1] == pytest.approx(
        0.1 * math.log(40.0) + 1.0
    )
    # the reference's own reckoning agrees
    ref_inv, ref_rot, ref_scale = reference.yarn(
        {**tiny.MODEL, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128}
    )
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_rot == 1.0
    assert ref_scale == pytest.approx(192 ** -0.5 * 1.589627, rel=1e-6)


@pytest.mark.parametrize("renormalize", [True, False])
def test_route_top_k_renormalises_only_when_asked(renormalize):
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    weights, experts = route_top_k(x, router, 3, renormalize=renormalize)
    probs = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(router), axis=-1))
    chosen = np.take_along_axis(probs, np.asarray(experts), axis=-1)
    want = chosen / chosen.sum(-1, keepdims=True) if renormalize else chosen
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_array_equal(
        np.sort(np.asarray(experts), -1), np.sort(np.argsort(-probs, -1)[:, :3], -1)
    )


def _pool_with_prompt(module, params, prompt, slots=2):
    """A latent pool with ``prompt``'s rows written by the cold prefill's
    page write at slot 0's pages (slot ``i`` holds pages ``i * PAGES ..``),
    and the prefill's own logits."""
    pool = allocate_page_pool(
        3, slots * PAGES, PAGE, 1, 1, jnp.float32, latent=module.mla.row
    )
    table = jnp.arange(slots * PAGES, dtype=jnp.int32).reshape(slots, PAGES)
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    logits, rows = module.apply(
        {"params": params}, tokens, lengths, method="prefill"
    )
    pages = jnp.where(
        jnp.arange(PAGES)[None, :] * PAGE < len(prompt), table[:1], slots * PAGES
    )[:, : -(-len(prompt) // PAGE)]
    pool = tuple(
        _pool_write_pages(layer, {"c": state[0]}, pages)
        for layer, state in zip(pool, rows)
    )
    return pool, table, logits


@pytest.mark.parametrize("flavor", ["reference", "pallas"])
def test_prefill_then_decode_through_the_latent_pool(built, flavor):
    """The cold prefill writes one latent row a token a layer (no K/V a
    head: the pool's leaf is ``c`` alone), and decode steps in the absorbed
    form, teacher-forced with the prompt's own next tokens, give the full
    pass's logits at each position (1e-4: float32, sums in another
    order)."""
    module, params = built
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(7), (60,), 0, tiny.VOCAB)
    )
    want = reference_logits(params, tokens)
    prompt = 37
    pool, table, logits = _pool_with_prompt(module, params, tokens[:prompt])
    assert [sorted(layer) for layer in pool] == [["c"]] * 3
    assert pool[0]["c"].shape[-1] == kv_row_width(1, 40) == 128
    np.testing.assert_allclose(logits[0], want[prompt - 1], atol=1e-4, rtol=1e-4)
    attn = (
        mla.mla_pool_decode_attention if flavor == "reference"
        else mla.mla_cascade_decode_attention
    )
    for pos in range(prompt, 46):
        step = jnp.asarray([tokens[pos], 0], jnp.int32)
        lengths = jnp.asarray([pos, 0], jnp.int32)
        logits, pool = module.apply(
            {"params": params}, step, lengths, pool, table,
            method="decode_step_paged", attention_override=attn,
        )
        np.testing.assert_allclose(logits[0], want[pos], atol=1e-4, rtol=1e-4)


def test_a_prefix_then_an_extend_is_the_cold_prefill(built):
    """A cached prefix of 29 rows (off the page) and an extend of 18
    tokens through the pool in the expanded form: its logits at every
    window position are the full pass's."""
    module, params = built
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(8), (47,), 0, tiny.VOCAB)
    )
    want = reference_logits(params, tokens)
    for attention in ("dense", "flash"):
        m = module.clone(attention=attention)
        pool, table, _ = _pool_with_prompt(m, params, tokens[:29])
        window = jnp.zeros((2, 24), jnp.int32).at[0, :18].set(tokens[29:])
        logits, _ = m.apply(
            {"params": params}, window, jnp.asarray([29, 0], jnp.int32), pool,
            table, valid=jnp.asarray([18, 0], jnp.int32),
            method="decode_verify_paged",
        )
        np.testing.assert_allclose(
            logits[0, :18], want[29:], atol=1e-4, rtol=1e-4
        )


def test_absorbed_decode_is_the_expanded_attention():
    """One step's attention two ways over the same cached rows: absorbed
    (the query into the latent, the latent average out through W_uv) and
    expanded (every head's key and value from the latent): 1e-5."""
    rng = np.random.default_rng(0)
    heads, nope, rope, v, rank, n = 4, 16, 8, 16, 32, 21
    w = jnp.asarray(rng.normal(size=(rank, heads, nope + v)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(n, rank + rope)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(heads, nope)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(heads, rope)), jnp.float32)
    scale = 0.2
    k = jnp.concatenate(
        [jnp.einsum("nr,rhd->nhd", rows[:, :rank], w[..., :nope]),
         jnp.broadcast_to(rows[:, None, rank:], (n, heads, rope))], -1,
    )
    vals = jnp.einsum("nr,rhd->nhd", rows[:, :rank], w[..., nope:])
    q = jnp.concatenate([q_nope, q_pe], -1)
    p = jax.nn.softmax(jnp.einsum("hd,nhd->hn", q, k) * scale, -1)
    expanded = jnp.einsum("hn,nhd->hd", p, vals)
    pool = jnp.zeros((4, 1, 8, 128)).at[:, 0, :, :40].set(
        jnp.pad(rows, ((0, 32 - n), (0, 0))).reshape(4, 8, 40)
    )
    q_lat = jnp.concatenate([jnp.einsum("hd,rhd->hr", q_nope, w[..., :nope]), q_pe], -1)
    o_lat = mla.mla_pool_decode_attention(
        q_lat[None], pool, jnp.arange(4, dtype=jnp.int32)[None],
        jnp.asarray([n - 1], jnp.int32), scale=scale, value_dim=rank,
    )
    absorbed = jnp.einsum("hr,rhd->hd", o_lat[0], w[..., nope:])
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=1e-5)


#: The decode kernel's cases over a pool of 130 pages of 8 rows: ``(dtype,
#: block_pages, slots, members)``, each slot ``(prefix, length)`` — the
#: physical pages its table starts with (a prefix is a list of pages, the
#: same for every slot that names it), then pages of its own — and how
#: many slots the cascade reads shared pages for.
_A, _B = [3, 17, 29, 41, 53, 65], [7, 19, 31, 43, 55]
KERNEL_CASES = {
    # no sharing: a slot with no rows but its first, one on a page
    # boundary, one over several blocks (of 3 pages: items across a slot)
    "f32-one-block": (jnp.float32, None, [([], 0), ([], 63), ([], 311)], 0),
    "f32-blocks-of-3": (jnp.float32, 3, [([], 0), ([], 63), ([], 311)], 0),
    "bf16-blocks-of-3": (jnp.bfloat16, 3, [([], 0), ([], 63), ([], 311)], 0),
    # one run of 6 pages, two whole blocks of 3, shared by every slot; the
    # first slot's own rows start on the page after it
    "shared-by-all": (jnp.float32, 3, [(_A, 48), (_A, 100), (_A, 311)], 3),
    # a run of 5 pages ends inside its second block of 4
    "run-ends-inside-a-block": (jnp.float32, 4, [(_B, 60), (_B, 45), (_B, 200)], 3),
    # two groups on different prefixes, and a slot on neither
    "two-groups": (
        jnp.float32, 3, [(_A, 60), (_B, 70), (_A, 90), (_B, 45), ([], 30)], 4,
    ),
    # a member that matches the run's first 2 pages only
    "a-member-leaves-early": (
        jnp.float32, 3, [(_A, 100), (_A, 80), (_A[:2], 120)], 3,
    ),
    "bf16-two-groups": (
        jnp.bfloat16, 3, [(_A, 60), (_B, 70), (_A, 90), (_B, 45), ([], 30)], 4,
    ),
    # 48 slots make the break-even 3 (float32): a group of two stays on
    # the per-slot path
    "two-below-the-break-even": (
        jnp.float32, None, [(_A, 60), (_A, 70)] + [([], 5)] * 46, 0,
    ),
}


def _kernel_case(slots, max_pages=40, page=8, pages=130):
    """``(table, lengths)``: each slot's prefix pages, then pages of its
    own drawn from the pages no prefix uses, -1 past its length."""
    rng = np.random.default_rng(1)
    free = [p for p in rng.permutation(pages) if p not in _A + _B]
    table = np.full((len(slots), max_pages), -1, np.int32)
    for i, (prefix, length) in enumerate(slots):
        need = length // page + 1
        mine = [free.pop() for _ in range(max(0, need - len(prefix)))]
        table[i, :need] = (list(prefix) + mine)[:need]
    return table, np.asarray([n for _, n in slots], np.int32)


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_paged_decode_kernel_is_its_oracle(case):
    """The cascade kernel (interpret mode) over scattered pages, without
    sharing and with runs shared by a group, against its oracle: float32
    1e-5; bfloat16 operands 2e-2 (the probabilities meet the values in
    bfloat16). The grouping engages for exactly the case's members."""
    dtype, block_pages, slots, members = KERNEL_CASES[case]
    rng = np.random.default_rng(1)
    heads, row, value_dim, page = 16, 72, 64, 8
    pool = jnp.asarray(rng.normal(size=(130, 1, page, 128)), dtype)
    table, lengths = _kernel_case(slots)
    q = jnp.asarray(rng.normal(size=(len(slots), heads, row)) * 0.5, dtype)
    need = mla.mla_cascade_need(
        len(slots), heads, 128, value_dim, jnp.dtype(dtype).itemsize
    )
    assert need == (3 if len(slots) == 48 else 2)
    _, shared, _ = mla.mla_shared_runs(table, lengths, page_size=page, need=need)
    assert int((shared > 0).sum()) == members
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    want = mla.mla_pool_decode_attention(
        q, pool, table, lengths, scale=0.3, value_dim=value_dim
    )
    got = mla.mla_cascade_decode_attention(
        q, pool, table, lengths, scale=0.3, value_dim=value_dim,
        interpret=True, block_pages=block_pages,
    )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_the_cascade_groups_a_hand_written_table(xp):
    """Pages of 4. Slots 0-2 start on page 10: slot 1 matches slot 0 for
    3 pages, slot 2 for 2 (then its own); slots 3-4 start on page 40 and
    match for the 2 whole pages slot 3 holds. Slot 5 holds no whole page,
    slot 6 none at all. At a break-even of 2 the first group's run is 3
    pages (2 members hold page 2) and the second's 2; at 3, 2 and none."""
    table = xp.asarray([
        [10, 11, 12, 13, 14], [10, 11, 12, 20, 21], [10, 11, 30, 31, -1],
        [40, 41, 42, -1, -1], [40, 41, 42, 43, -1], [50, -1, -1, -1, -1],
        [-1, -1, -1, -1, -1],
    ], np.int32)
    lengths = xp.asarray([19, 18, 13, 9, 11, 2, 0], np.int32)
    leader, shared, run = mla.mla_shared_runs(table, lengths, page_size=4, need=2)
    assert isinstance(shared, type(lengths))
    np.testing.assert_array_equal(shared, [3, 3, 2, 2, 2, 0, 0])
    np.testing.assert_array_equal(run, [3, 0, 0, 2, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(leader)[:5], [0, 0, 0, 3, 3])
    _, shared, run = mla.mla_shared_runs(table, lengths, page_size=4, need=3)
    np.testing.assert_array_equal(shared, [2, 2, 2, 0, 0, 0, 0])
    np.testing.assert_array_equal(run, [2, 0, 0, 0, 0, 0, 0])


def test_the_cascade_break_even_at_deepseek_v2_lite():
    """128 slots of 16 heads, rows of 640 lanes, values of 512, bfloat16:
    2 x 2,048 rows x 1,152 operations a row against 640 x 2 bytes at the
    v5e's 240.5 operations a byte: 15.3, so 16 members; the rows of fewer
    slots need fewer, never below 2."""
    assert mla.mla_cascade_need(128, 16, 640, 512, 2) == 16
    assert mla.mla_cascade_need(3, 4, 128, 32, 4) == 2
    assert mla.mla_head_rows(4, 4) == 8 and mla.mla_head_rows(4, 2) == 16


@pytest.mark.parametrize("offsets", [(0, 0), (0, 25), (37, 3)])
def test_the_window_kernel_is_its_oracle(offsets):
    """Causal from an offset, queries and keys of 24, values of 16, in
    blocks that do not divide the lengths: 1e-5 (float32)."""
    rng = np.random.default_rng(2)
    b, w, n, h = 2, 20, 61, 3
    q = jnp.asarray(rng.normal(size=(b, w, h, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, n, h, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, n, h, 16)), jnp.float32)
    off = jnp.asarray(offsets, jnp.int32)
    want = mla.mla_attention_reference(q, k, v, off, scale=0.2)
    got = mla.mla_flash_attention(
        q, k, v, off, scale=0.2, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
