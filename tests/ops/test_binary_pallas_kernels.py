"""§21 binary-kernel certification: the fused Pallas xnor-popcount
kernels (sign+pack producer, scaled GEMM, conv-as-gemm) are
BIT-IDENTICAL to the reference popcount composition — exact integers
plus one fp32 multiply, no ULP budget (docs/DESIGN.md §21).

Interpret mode is the numerics vehicle here (CPU tier-1): it executes
the same kernel program, so a bitwise mismatch in interpret mode is a
kernel bug, not a platform artifact. The sweep is adversarial on
purpose: ragged K via ``k_true``, block-edge shapes (axis == 1, just
past a block, non-multiples of every alignment), strides/padding grid,
poisoned unread input, extreme scales, bf16 inputs, ±0.0 and NaN sign
semantics.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zookeeper_tpu.ops.binary_compute import (
    _packed_conv_forward,
    pack_bits,
    pack_conv_kernel,
    pack_dense_kernel,
    pack_rows_packed,
    packed_dense_infer,
    resolve_binary_flavor,
    xnor_matmul_packed,
    xnor_matmul_packed_scaled,
)


# -- flavor seam -------------------------------------------------------------


def test_resolve_binary_flavor_seam():
    assert resolve_binary_flavor("reference") == "reference"
    assert resolve_binary_flavor("pallas") == "pallas"
    expected = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert resolve_binary_flavor("auto") == expected
    with pytest.raises(ValueError, match="flavor"):
        resolve_binary_flavor("palas")  # typo must be loud, not silent


def test_explicit_pallas_on_mxu_path_warns_and_degrades():
    """The MXU (use_popcount=False) paths have no fused flavor: an
    explicit "pallas" warns (the caller named a flavor it cannot get)
    and degrades to the reference composition; "auto" stays silent."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    kern = jnp.asarray(
        np.sign(rng.normal(size=(64, 8))).astype(np.float32)
    )
    packed, scale = pack_dense_kernel(kern)
    with pytest.warns(UserWarning, match="no fused"):
        y_warn = packed_dense_infer(
            x, packed, scale, 64, use_popcount=False, interpret=True,
            flavor="pallas",
        )
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        y_auto = packed_dense_infer(
            x, packed, scale, 64, use_popcount=False, interpret=True,
            flavor="auto",
        )
    np.testing.assert_array_equal(np.asarray(y_warn), np.asarray(y_auto))


# -- fused sign+pack producer ------------------------------------------------


@pytest.mark.parametrize(
    "m,k",
    [(m, k) for m in (1, 3, 37, 96) for k in (32, 96, 416)]
    # Past one _PACK_CHUNK: the ragged second chunk of the static walk.
    + [(5, 4096 + 64)],
)
def test_pack_rows_matches_pack_bits(m, k):
    rng = np.random.default_rng(m * 1000 + k)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    got = pack_rows_packed(x, interpret=True)
    want = pack_bits(x, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pack_rows_sign_edge_semantics():
    """±0.0 and NaN must take the SAME bit as pack_bits (both lower to
    the identical ``>= 0`` compare): +0.0 and -0.0 -> bit 1, NaN -> 0."""
    x = jnp.asarray(
        [[0.0, -0.0, np.nan, -np.nan] * 8, [1.0, -1.0, np.inf, -np.inf] * 8],
        jnp.float32,
    )
    got = np.asarray(pack_rows_packed(x, interpret=True))
    want = np.asarray(pack_bits(x, axis=-1))
    np.testing.assert_array_equal(got, want)
    # Pin the absolute semantics too, not just agreement: row 0 packs
    # bits 1,1,0,0 repeating -> 0b...0011 pattern.
    assert got[0, 0] & 0xF == 0b0011


def test_pack_rows_bf16_and_ragged_rows():
    rng = np.random.default_rng(7)
    # 41 rows: not a multiple of any block; bf16: sublane tile 16 | 32.
    x = jnp.asarray(rng.normal(size=(41, 64)), jnp.bfloat16)
    got = pack_rows_packed(x, interpret=True)
    want = pack_bits(x, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pack_rows_rejects_unaligned_k():
    with pytest.raises(ValueError, match="32"):
        pack_rows_packed(jnp.ones((4, 33), jnp.float32), interpret=True)


# -- fused-epilogue GEMM -----------------------------------------------------


def _signs(rng, shape):
    return np.where(rng.random(shape) < 0.5, -1.0, 1.0).astype(np.float32)


def _adversarial_scale(rng, n):
    # Spans 16 decades: any epilogue reassociation or double-rounding
    # difference from the reference one-multiply shows up bitwise.
    s = np.abs(rng.normal(size=n)).astype(np.float32)
    return (s * rng.choice([1e-8, 1.0, 1e8], size=n)).astype(np.float32)


@pytest.mark.parametrize(
    "m,n,k",
    [
        (1, 1, 32),  # degenerate axes
        (7, 33, 64),  # nothing aligned
        (130, 72, 96),  # just past one M block
        (64, 200, 512),  # multi-K-block accumulation
        (3, 129, 4608),  # QuickNet-section K depth, N just past a block
    ],
)
def test_scaled_gemm_bitwise_vs_reference(m, n, k):
    rng = np.random.default_rng(m * 7 + n * 3 + k)
    a = _signs(rng, (m, k))
    b = _signs(rng, (k, n))
    scale = _adversarial_scale(rng, n)
    ap = pack_bits(jnp.asarray(a), axis=-1)
    bp = pack_bits(jnp.asarray(b), axis=0)
    got = xnor_matmul_packed_scaled(
        ap, bp, jnp.asarray(scale), k_true=k, interpret=True
    )
    # The reference composition the zero-ULP argument is made against:
    # exact int32 counts -> exact fp32 cast -> ONE fp32 multiply.
    acc = np.asarray(
        xnor_matmul_packed(ap, bp, k_true=k, interpret=True)
    )
    np.testing.assert_array_equal(acc, a @ b)  # exact-integer contract
    want = acc.astype(np.float32) * scale[None, :]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_scaled_gemm_ragged_k_true_correction():
    """K not a multiple of 32: both operands pad the tail with MATCHED
    +1 bits (zero mismatches) and ``k_true`` keeps the count exact —
    the kernel must reproduce the true-K product bitwise."""
    rng = np.random.default_rng(11)
    for k_true in (1, 31, 33, 100):
        k_pad = -(-k_true // 32) * 32
        a = _signs(rng, (5, k_true))
        b = _signs(rng, (k_true, 40))
        a_pad = np.pad(a, ((0, 0), (0, k_pad - k_true)), constant_values=1.0)
        b_pad = np.pad(b, ((0, k_pad - k_true), (0, 0)), constant_values=1.0)
        scale = _adversarial_scale(rng, 40)
        got = xnor_matmul_packed_scaled(
            pack_bits(jnp.asarray(a_pad), axis=-1),
            pack_bits(jnp.asarray(b_pad), axis=0),
            jnp.asarray(scale),
            k_true=k_true,
            interpret=True,
        )
        want = (a @ b).astype(np.float32) * scale[None, :]
        np.testing.assert_array_equal(np.asarray(got), want)


def test_scaled_gemm_validates_scale_shape():
    ap = pack_bits(jnp.ones((4, 32), jnp.float32), axis=-1)
    bp = pack_bits(jnp.ones((32, 8), jnp.float32), axis=0)
    with pytest.raises(ValueError, match="scale"):
        xnor_matmul_packed_scaled(
            ap, bp, jnp.ones((4,), jnp.float32), k_true=32, interpret=True
        )


# -- conv-as-gemm ------------------------------------------------------------


def _conv_pair(rng, b, h, w, ci, co, kh, kw):
    x = jnp.asarray(_signs(rng, (b, h, w, ci)))
    scale = np.abs(rng.normal(size=co)).astype(np.float32) + 0.1
    q_kernel = jnp.asarray(_signs(rng, (kh, kw, ci, co)) * scale)
    packed, pscale = pack_conv_kernel(q_kernel)
    return x, packed, pscale


def _conv_ab(x, packed, scale, strides, padding, ci):
    kw = {"ci": ci, "use_popcount": True, "interpret": True}
    ref = _packed_conv_forward(
        x, packed, scale, strides, padding, flavor="reference", **kw
    )
    fused = _packed_conv_forward(
        x, packed, scale, strides, padding, flavor="pallas", **kw
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))
    return np.asarray(fused)


@pytest.mark.parametrize("strides", [(1, 1), (2, 2), (2, 1)])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_conv_gemm_strides_padding_grid(strides, padding):
    rng = np.random.default_rng(sum(strides) * 10 + len(padding))
    x, packed, scale = _conv_pair(rng, b=2, h=9, w=8, ci=17, co=33, kh=3, kw=3)
    _conv_ab(x, packed, scale, strides, padding, ci=17)


@pytest.mark.parametrize("ci,co,kh,kw", [(3, 8, 1, 1), (5, 33, 3, 3), (32, 130, 5, 3)])
def test_conv_gemm_ragged_channels_and_kernels(ci, co, kh, kw):
    """Ragged input channels exercise the +1 channel padding (k_true =
    kh*kw*ci stays the TRUE count); co past the 128-lane block
    exercises the output-channel padding slice."""
    rng = np.random.default_rng(ci * co)
    x, packed, scale = _conv_pair(rng, b=1, h=7, w=7, ci=ci, co=co, kh=kh, kw=kw)
    _conv_ab(x, packed, scale, (1, 1), "SAME", ci=ci)


def test_conv_gemm_poisoned_unread_input_rows():
    """VALID at stride 2 on an even height leaves the last input row
    unread by every window: garbage there (±1e30) must not leak into
    either flavor, and the two must still agree bitwise."""
    rng = np.random.default_rng(3)
    x, packed, scale = _conv_pair(rng, b=1, h=8, w=8, ci=16, co=16, kh=3, kw=3)
    xg = np.array(x)  # writable copy
    xg[:, -1, :, :] = 1e30 * np.where(rng.random(xg[:, -1].shape) < 0.5, -1, 1)
    xg[:, :, -1, :] = -1e30
    clean = _conv_ab(x, packed, scale, (2, 2), "VALID", ci=16)
    poisoned = _conv_ab(jnp.asarray(xg), packed, scale, (2, 2), "VALID", ci=16)
    # (8-3)//2+1 = 3 output rows read input rows 0..6 only; the
    # poisoned row 7 / col 7 are dead and the outputs match exactly.
    np.testing.assert_array_equal(clean, poisoned)


def test_conv_gemm_bf16_input_bitwise():
    """bf16 activations (the mixed-precision deployment dtype): the
    sign compare is exact in any float dtype, so the fused path stays
    bit-identical — the documented-ULP budget is for the fp32 epilogue
    multiply, which both flavors share as one op."""
    rng = np.random.default_rng(5)
    x, packed, scale = _conv_pair(rng, b=1, h=6, w=6, ci=32, co=16, kh=3, kw=3)
    _conv_ab(x.astype(jnp.bfloat16), packed, scale, (1, 1), "SAME", ci=32)


def test_grouped_and_depthwise_convs_excluded_upstream():
    """The §21 kernels never see grouped contractions: the layer seam
    rejects grouped/depthwise binary_compute before dispatch (grouping
    removes the K=ci compression the packed paths exist for)."""
    from zookeeper_tpu.ops.layers import QuantConv

    x = jnp.ones((1, 8, 8, 16), jnp.float32)
    for groups in (2, -1):  # grouped, depthwise
        layer = QuantConv(
            16, (3, 3), input_quantizer="ste_sign",
            kernel_quantizer="ste_sign", binary_compute="xnor_popcount",
            feature_group_count=groups, pallas_interpret=True,
        )
        with pytest.raises(ValueError, match="grouped conv"):
            layer.init(jax.random.PRNGKey(0), x)


# -- deployment walk ---------------------------------------------------------


def test_packed_deployment_walk_compile_free():
    """The packed QuickNet forward under the pallas flavor is ONE
    compilation: repeated batches re-enter the same executable
    (zero post-warmup recompiles — the serving contract §21 rides)."""
    from zookeeper_tpu.core import configure
    from zookeeper_tpu.models import QuickNet
    from zookeeper_tpu.ops.packed import pack_quantconv_params

    def build(packed):
        model = QuickNet()
        configure(
            model,
            {
                "blocks_per_section": (1, 1),
                "section_features": (32, 64),
                "binary_compute": "xnor_popcount",
                "packed_weights": packed,
                "pallas_interpret": True,
                "binary_flavor": "pallas",
            },
            name="model",
        )
        return model.build((16, 16, 3), num_classes=4)

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    variables = build(False).init(jax.random.PRNGKey(0), x, training=False)
    packed_vars = {
        **variables,
        "params": pack_quantconv_params(variables["params"]),
    }
    module = build(True)
    fwd = jax.jit(lambda v, xb: module.apply(v, xb, training=False))
    y0 = np.asarray(fwd(packed_vars, x))
    for seed in (1, 2):
        xb = jnp.asarray(
            np.random.default_rng(seed).normal(size=x.shape), jnp.float32
        )
        fwd(packed_vars, xb)
    assert fwd._cache_size() == 1  # zero post-warmup recompiles
    np.testing.assert_array_equal(y0, np.asarray(fwd(packed_vars, x)))
