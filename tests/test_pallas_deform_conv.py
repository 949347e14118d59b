"""The deformable convolution's Pallas pair (``ops/pallas/deform_conv.py``)
in interpret mode against the jnp form of ``ops/deform_conv.py``: the
output and its three gradients (map, offsets, kernel) at the
configuration's 4 groups and dilation 2, on maps of whole 128-channel
groups as the pair takes them: one whose rows are a multiple of 16
positions, and one the pair pads with zero columns (as the portrait
bucket's 64 × 38 conv5 map).

Both forms clip the same points and take the same jnp path from the
offsets to them, so the offsets' gradient agrees also at whole numbers
(where the clip's slope is split); in float32 at ``highest`` they agree
to the rounding of a few hundred terms.  Which form ``deform_conv`` runs
is checked from the traced program, without running the kernel
compiled (there is no TPU here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import deform_conv as dc
from mx_rcnn_tpu.ops.pallas import deform_conv as pdc

D, G = 2, 4
#: 13 rows of 48 positions: 624 positions in five blocks of 128 (the last
#: padded), every block edge inside a row
B, H, W, C, COUT = 2, 13, 48, 512, 8
#: the maps of the equality cases: that one, and 17 rows of 10 positions,
#: which the pair lays out 16 wide (two blocks, the edge inside a row)
MAPS = {"13x48": (13, 48), "17x10": (17, 10)}
TOL = 2e-5


def _offsets(kind, seed=0, hw=(H, W)):
    h, w = hw
    rng = np.random.RandomState(seed)
    shape = (B, h, w, 2 * 9 * G)
    if kind == "zero":
        return np.zeros(shape)
    if kind == "whole":
        return rng.randint(-2, 3, size=shape).astype(np.float64)
    if kind == "fraction":
        return rng.randint(-1, 2, size=shape) + rng.uniform(0.2, 0.8, shape)
    if kind == "edges":
        # points past every border, and points in the last row and column
        # (in [H - 1, H) and [W - 1, W): the corner past the edge clamped)
        off = rng.randint(-9, 10, size=shape) + rng.uniform(0.2, 0.8, shape)
        rows = np.arange(h)[None, :, None, None]
        cols = np.arange(w)[None, None, :, None]
        taps = np.arange(9)
        ti = np.tile(taps // 3 - 1, G)[None, None, None, :]
        tj = np.tile(taps % 3 - 1, G)[None, None, None, :]
        last_row = h - 1 - rows - D * ti + rng.uniform(0.0, 0.9, shape[:3] + (
            9 * G,))
        last_col = w - 1 - cols - D * tj + rng.uniform(0.0, 0.9, shape[:3] + (
            9 * G,))
        pick = rng.rand(*shape[:3], 9 * G)
        off[..., 0::2] = np.where(pick < 0.3, last_row, off[..., 0::2])
        off[..., 1::2] = np.where(pick > 0.7, last_col, off[..., 1::2])
        return off
    if kind == "far":
        # up to 6 cells: a block's band is three chunks or more
        return rng.uniform(-6.0, 6.0, size=shape)
    raise ValueError(kind)


def _inputs(kind, dtype=jnp.float32, seed=0, hw=(H, W)):
    rng = np.random.RandomState(seed + 100)
    x = jnp.asarray(rng.randn(B, *hw, C), dtype)
    k = jnp.asarray(rng.randn(3, 3, C, COUT) / np.sqrt(9 * C), dtype)
    off = jnp.asarray(_offsets(kind, seed, hw), jnp.float32)
    cot = jnp.asarray(rng.randn(B, *hw, COUT), jnp.float32)
    return x, off, k, cot


def _jnp_form(x, off, k):
    """``deform_conv`` where no TPU is: the jnp form."""
    return dc.deform_conv(x, off, k, D, G)


def _pair(x, off, k):
    return pdc.deform_conv_pallas(x, off, k, D, G, True)[0]


def _value_and_grads(fn, x, off, k, cot):
    def loss(a, o, kk):
        return jnp.sum(fn(a, o, kk).astype(jnp.float32) * cot)

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(x, off, k)


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        what, float(np.abs(got - want).max()), scale)


@pytest.mark.parametrize("hw", MAPS.values(), ids=MAPS.keys())
@pytest.mark.parametrize("kind", ["zero", "whole", "fraction", "edges", "far"])
def test_the_pair_equals_the_jnp_form(kind, hw):
    x, off, k, cot = _inputs(kind, hw=hw)
    with jax.default_matmul_precision("highest"):
        assert pdc.fits(*hw, C, G, 4)
        _close(jax.jit(_pair)(x, off, k), jax.jit(_jnp_form)(x, off, k), TOL)
        got = _value_and_grads(_pair, x, off, k, cot)
        want = _value_and_grads(_jnp_form, x, off, k, cot)
    _close(got[0], want[0], TOL)
    for g_got, g_want, name in zip(got[1], want[1], ("map", "offsets",
                                                     "kernel")):
        assert g_got.shape == g_want.shape and g_got.dtype == g_want.dtype
        _close(g_got, g_want, TOL, name)


def test_far_offsets_read_bands_of_three_chunks_and_more():
    """What the ``far`` case covers: bands of three chunks or more, and a
    block's points on both sides of a block edge."""
    _x, off, _k, _cot = _inputs("far")
    yc, _xc, keep = pdc._points(off, D, G)
    _start, chunks = pdc.bands(pdc._flat(yc),
                               pdc._flat(keep.astype(jnp.float32)), H)
    assert int(chunks.max()) >= 3
    assert int((chunks >= 3).sum()) > chunks.size // 2
    # a block edge inside a row, and a padded last block
    assert pdc._P % W and (H * W) % pdc._P


def test_a_bf16_map_is_within_its_rounding_of_the_jnp_form():
    """The cell's dtype: the weights enter the product in bfloat16 (one
    pass), the columns leave in bfloat16, as the jnp form casts them."""
    x, off, k, cot = _inputs("fraction", jnp.bfloat16, seed=3)
    got = _value_and_grads(_pair, x, off, k, cot)
    want = _value_and_grads(_jnp_form, x, off, k, cot)
    exact = _value_and_grads(_jnp_form, x.astype(jnp.float32), off,
                             k.astype(jnp.float32), cot)
    for g_got, g_want, g_exact in zip(got[1], want[1], exact[1]):
        assert g_got.dtype == g_want.dtype
        # no further from the float32 reading than twice the jnp form's
        # own bfloat16 rounding, and close to the jnp form
        err = float(jnp.abs(g_got.astype(jnp.float32) - g_exact).max())
        ref = float(jnp.abs(g_want.astype(jnp.float32) - g_exact).max())
        assert err <= 2 * ref + 1e-6, (err, ref)
        _close(g_got.astype(jnp.float32), g_want.astype(jnp.float32), 3e-2)


def _holds_the_pair(x, off, k) -> bool:
    jaxpr = jax.make_jaxpr(lambda a, o, kk: dc.deform_conv(a, o, kk, D, G))(
        x, off, k)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("pallas", ["0", "1"])
def test_the_dispatch_follows_use_pallas(monkeypatch, pallas):
    """``use_pallas()`` false takes the jnp form; true, the pair (only
    traced here: compiled without a TPU the kernel would not run)."""
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", pallas)
    x, off, k = (jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for v in _inputs("zero")[:3])
    assert dc.deform_conv_kernel(x, G) == (pallas == "1")
    assert _holds_the_pair(x, off, k) == (pallas == "1")


@pytest.mark.parametrize("shape, dtype, why", [
    ((1, 160, 224, 512), jnp.float32, "over the VMEM budget"),
    ((1, 100, 200, 512), jnp.bfloat16, "over the budget in bfloat16"),
    ((1, 13, 48, 256), jnp.float32, "groups of 64 channels"),
    ((1, 3, 16, 512), jnp.bfloat16, "fewer rows than a chunk"),
])
def test_maps_the_pair_does_not_take_run_the_jnp_form(monkeypatch, shape,
                                                      dtype, why):
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    b, h, w, c = shape
    x = jax.ShapeDtypeStruct(shape, dtype)
    off = jax.ShapeDtypeStruct((b, h, w, 2 * 9 * G), dtype)
    k = jax.ShapeDtypeStruct((3, 3, c, 8), dtype)
    assert not pdc.fits(h, w, c, G, jnp.dtype(dtype).itemsize), why
    assert not dc.deform_conv_kernel(x, G), why
    assert not _holds_the_pair(x, off, k), why


def test_the_cell_s_map_fits():
    """``dcn_train_b8``'s conv5: 8 × 38 × 64 × 512 in bfloat16, 4 groups."""
    assert pdc.fits(38, 64, 512, 4, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_portrait_bucket_s_map_fits(monkeypatch, dtype):
    """The configuration's second shape bucket, 1024 × 608: conv5 is
    64 × 38, laid out 48 wide, and takes the pair as the first does."""
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    assert pdc.padded_width(38) == 48 and pdc.padded_width(64) == 64
    assert pdc.fits(64, 38, 512, 4, jnp.dtype(dtype).itemsize)
    x = jax.ShapeDtypeStruct((8, 64, 38, 512), dtype)
    off = jax.ShapeDtypeStruct((8, 64, 38, 2 * 9 * G), dtype)
    k = jax.ShapeDtypeStruct((3, 3, 512, 512), dtype)
    assert _holds_the_pair(x, off, k)


@pytest.mark.parametrize("hw", MAPS.values(), ids=MAPS.keys())
def test_the_pair_counts_the_chunks_its_bands_read(hw):
    """The count that comes back beside the output is ``window_chunks``'
    for the same offsets, and ``window_chunks_min``'s at zero offsets."""
    for kind in ("zero", "far"):
        x, off, k, _cot = _inputs(kind, hw=hw)
        _y, chunks = pdc.deform_conv_pallas(x, off, k, D, G, True)
        assert int(chunks) == int(pdc.window_chunks(off, D, G))
        if kind == "zero":
            assert int(chunks) == pdc.window_chunks_min(B, *hw, D, G)


def test_kernel_rows_follow_the_columns_order():
    """Row (g·9 + t)·C/G + c of the reordered kernel is tap t, channel
    g·C/G + c of the HWIO kernel."""
    k = jnp.arange(3 * 3 * 8 * 2, dtype=jnp.float32).reshape(3, 3, 8, 2)
    rows = np.asarray(pdc.kernel_rows(k, 4))
    for g in range(4):
        for t in range(9):
            for c in range(2):
                np.testing.assert_array_equal(
                    rows[(g * 9 + t) * 2 + c], np.asarray(k)[t // 3, t % 3,
                                                             g * 2 + c])
