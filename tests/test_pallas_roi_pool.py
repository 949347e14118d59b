"""The Pallas ROI max pooling pair (``ops/pallas/roi_pool.py``), interpreted
on the CPU, against the independent MXNet loop of ``roi_pool_cases.py``:
values EQUAL, the gradient on the arg-max cell, every bin edge a 38x64 map
can meet, ``valid_hw``, empty bins, ties, and what dispatches to it.  What
Mosaic makes of the kernels is ``tests/test_chip_compile.py``'s to say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roi_pool_cases import (
    ROIS,
    clipped_rois,
    every_edge_rois,
    mxnet_roi_pool,
    position_map,
    small_map,
)

from mx_rcnn_tpu.ops.pallas.roi_pool import fits_vmem, roi_pool_pallas
from mx_rcnn_tpu.ops.roi_align import (
    extract_roi_features_batched,
    roi_pool,
)

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _map(width):
    """12 rows of ``width`` columns: at 20 (``small_map``, which 8 does not
    divide) every column bin is a masked maximum over all W, at 32 the
    kernel reads a bin through its 16-column window wherever it fits."""
    if width == 20:
        return small_map()
    return np.random.RandomState(11).randn(12, width, 5).astype(np.float32)


def _kernel(feat, rois, valid_hw=None):
    """One image through the pair at 7x7, 1/16, interpreted."""
    valid_hw = None if valid_hw is None else jnp.asarray(
        valid_hw, jnp.float32)[None]
    return roi_pool_pallas(feat[None], jnp.asarray(rois)[None], (7, 7),
                           1.0 / 16.0, True, valid_hw)[0]


def _as(feat, dtype):
    """→ (the map in ``dtype``, the same values in float32 for the loop)."""
    feat = jnp.asarray(feat).astype(dtype)
    return feat, np.asarray(feat.astype(jnp.float32))


@pytest.mark.parametrize("width", [20, 32])
@pytest.mark.parametrize("rois", sorted(ROIS))
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
def test_values_equal_the_mxnet_loop(dtype, rois, width):
    feat, exact = _as(_map(width), _DTYPES[dtype])
    rois = ROIS[rois]()
    got = _kernel(feat, rois)
    assert got.dtype == feat.dtype
    want, _ = mxnet_roi_pool(exact, rois, (7, 7), 1.0 / 16.0)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)), want.astype(np.float32))


@pytest.mark.parametrize("width", [20, 32])
@pytest.mark.parametrize("rois", sorted(ROIS))
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
def test_gradient_lands_on_the_arg_max_cell(dtype, rois, width):
    feat, exact = _as(_map(width), _DTYPES[dtype])
    rois = ROIS[rois]()
    # cotangents of 1 or 2: whole numbers whose sums on one cell (49 bins
    # of a one-cell roi) stay exact in bfloat16's 8 bits too
    cot = np.random.RandomState(5).randint(1, 3, (len(rois), 7, 7, 5))
    got = jax.grad(
        lambda f: (_kernel(f, rois).astype(jnp.float32)
                   * jnp.asarray(cot, jnp.float32)).sum())(feat)
    assert got.dtype == feat.dtype
    _, want = mxnet_roi_pool(exact, rois, (7, 7), 1.0 / 16.0, cot=cot)
    assert want.max() <= 256 and (want != 0).sum() > 50
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)), want.astype(np.float32))


def test_every_bin_edge_a_38x64_map_can_meet():
    """Every (first, last) cell pair of the cell's map on either axis, by a
    map whose values are its cells' positions: 0 rois with a wrong bin.
    2340 rois are also a count the roi block (8) does not divide."""
    feat, rois = position_map(38, 64), every_edge_rois(38, 64)
    assert len(rois) > 2000 and len(rois) % 8
    got = np.asarray(jax.jit(_kernel)(jnp.asarray(feat), jnp.asarray(rois)))
    want, _ = mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0)
    assert int((got != want.astype(np.float32)).any(axis=(1, 2, 3)).sum()) == 0


@pytest.mark.parametrize("n_rois", [1, 7, 13])
def test_a_roi_count_the_block_does_not_divide(n_rois):
    """The pad rois pool nothing and take no gradient; the real ones are
    the loop's, forward and back."""
    feat, rois = small_map(), ROIS["inner"]()[:n_rois]
    cot = np.random.RandomState(7).randint(1, 9, (n_rois, 7, 7, 5))
    got, vjp = jax.vjp(lambda f: _kernel(f, rois), jnp.asarray(feat))
    assert got.shape == (n_rois, 7, 7, 5)
    want, dwant = mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0, cot=cot)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(vjp(jnp.asarray(cot, jnp.float32))[0]),
        dwant.astype(np.float32))


@pytest.mark.parametrize("width", [20, 32])
def test_valid_hw_clips_the_bins_to_the_image_s_own_cells(width):
    feat, valid_hw = _map(width), (150.0, 270.0)
    for rois in (fn() for fn in ROIS.values()):
        cot = np.random.RandomState(3).randint(1, 9, (len(rois), 7, 7, 5))
        got, vjp = jax.vjp(lambda f: _kernel(f, rois, valid_hw),
                           jnp.asarray(feat))
        want, dwant = mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0,
                                     valid_hw, cot=cot)
        np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
        # the backward carries the same limits: nothing past them
        np.testing.assert_array_equal(
            np.asarray(vjp(jnp.asarray(cot, jnp.float32))[0]),
            dwant.astype(np.float32))
    full = np.asarray(_kernel(jnp.asarray(feat), clipped_rois()))
    clipped = np.asarray(_kernel(jnp.asarray(feat), clipped_rois(), valid_hw))
    assert (full != clipped).any() and (clipped[0] == 0).all()  # it binds


def test_empty_bins_emit_zero_and_take_no_gradient():
    """A roi wholly outside the map, and one the border cuts: a bin without
    cells is 0 (on a map that is negative everywhere) and its cotangent
    goes nowhere."""
    feat = -1.0 - np.abs(small_map())
    rois = np.asarray([[400.0, 300.0, 460.0, 380.0],      # all bins empty
                       [250.0, 150.0, 400.0, 260.0]], np.float32)
    got, vjp = jax.vjp(lambda f: _kernel(f, rois), jnp.asarray(feat))
    got = np.asarray(got)
    assert (got[0] == 0).all()
    empty = got[1] == 0
    assert empty.any() and not empty.all()
    cot = np.ones((2, 7, 7, 5), np.float32)
    grad = np.asarray(vjp(jnp.asarray(cot))[0])
    assert grad.sum() == (~empty).sum()       # the live bins' alone


@pytest.mark.parametrize("width", [20, 32])
def test_a_tie_goes_to_the_first_cell_in_row_major_order(width):
    """MXNet's rule (strict ``>`` in a row-major scan): a flat map gives
    every bin's whole cotangent to the bin's first cell, where the jnp
    sweep shares it among the tied cells; both put all of it on the map."""
    feat = np.zeros((12, width, 3), np.float32)
    feat[5, 7] = feat[5, 8] = feat[6, 3] = 2.0        # a three-way tie
    rois = np.asarray([[0.0, 0.0, 319.0, 191.0],       # the whole map
                       [48.0, 64.0, 175.0, 127.0]], np.float32)
    cot = np.random.RandomState(9).randint(1, 9, (2, 7, 7, 3)).astype(
        np.float32)
    got, vjp = jax.vjp(lambda f: _kernel(f, rois), jnp.asarray(feat))
    grad = np.asarray(vjp(jnp.asarray(cot))[0])
    want, dwant = mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0, cot=cot)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
    np.testing.assert_array_equal(grad, dwant.astype(np.float32))
    # 1x1 pooling of the whole map: cells (5, 7), (5, 8) and (6, 3) tie,
    # and (5, 7) is the first of them in row-major order
    one = jax.grad(lambda f: roi_pool_pallas(
        f[None], jnp.asarray(rois[:1])[None], (1, 1), 1.0 / 16.0, True
    ).sum())(jnp.asarray(feat))
    assert (np.asarray(one)[5, 7] == 1).all() and np.asarray(one).sum() == 3
    # conserved, in both rules (the sweep's shares are thirds: rounded)
    sweep = jax.grad(lambda f: (roi_pool(f, jnp.asarray(rois), (7, 7),
                                         1.0 / 16.0) * cot).sum())(
        jnp.asarray(feat))
    assert grad.sum() == cot.sum()
    assert abs(float(sweep.sum()) - cot.sum()) < 1e-2
    assert (np.asarray(sweep) != grad).any()            # and not the same


def test_batched_equals_the_jnp_sweep_image_by_image():
    rng = np.random.RandomState(0)
    feat = jnp.asarray(rng.permutation(3 * 9 * 11 * 6).reshape(
        3, 9, 11, 6).astype(np.float32))                # no two cells tie
    rois = jnp.asarray(np.stack([
        np.array([[0, 0, 60, 60], [16, 16, 120, 100], [5, 40, 90, 160],
                  [0, 0, 30, 30], [32, 0, 170, 80]], np.float32) + 3.0 * i
        for i in range(3)]))
    valid_hw = jnp.asarray([[144.0, 176.0], [100.0, 150.0], [120.0, 90.0]])
    for vhw in (None, valid_hw):
        got = roi_pool_pallas(feat, rois, (7, 7), 1.0 / 16, True, vhw)
        for i in range(3):
            want = roi_pool(feat[i], rois[i], (7, 7), 1.0 / 16,
                            valid_hw=None if vhw is None else vhw[i])
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))
    cot = jnp.asarray(rng.randint(1, 9, (3, 5, 7, 7, 6)).astype(np.float32))
    got = jax.grad(lambda f: (roi_pool_pallas(
        f, rois, (7, 7), 1.0 / 16, True) * cot).sum())(feat)
    want = jax.grad(lambda f: sum(
        (roi_pool(f[i], rois[i], (7, 7), 1.0 / 16) * cot[i]).sum()
        for i in range(3)))(feat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_vmem_bound_takes_the_cells_map_and_refuses_a_huge_one():
    for esize in (2, 4):
        assert fits_vmem(38, 64, 512, (7, 7), esize)      # vgg_train_b8
        assert fits_vmem(64, 64, 512, (7, 7), esize)      # the serve ladder
        assert not fits_vmem(304, 512, 512, (7, 7), esize)


def _lowered_for_tpu(mode, feat_shape=(2, 12, 20, 128), fwd_only=False):
    """The batched dispatcher's text lowered FOR the TPU on the CPU (no
    compile): the Pallas custom calls show by name."""
    def pool(f, r):
        def loss(x):
            # squared: the gradient needs the forward's output
            return (extract_roi_features_batched(
                x, r, mode, (7, 7), 1.0 / 16, fwd_only=fwd_only) ** 2).sum()

        return loss(f) if fwd_only else jax.grad(loss)(f)

    args = (jax.ShapeDtypeStruct(feat_shape, jnp.float32),
            jax.ShapeDtypeStruct((feat_shape[0], 16, 4), jnp.float32))
    return jax.jit(pool).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("fwd_only", [False, True], ids=["grad", "fwd_only"])
def test_roi_pool_takes_the_kernel_where_pallas_is_on(monkeypatch, fwd_only):
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    text = _lowered_for_tpu("roi_pool", fwd_only=fwd_only)
    assert "pallas_roi_pool_fwd" in text
    assert ("pallas_roi_pool_bwd" in text) == (not fwd_only)
    assert "_roi_features" not in text and "stablehlo.while" not in text


def test_roi_pool_takes_the_sweep_where_pallas_is_off(monkeypatch):
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "0")
    text = _lowered_for_tpu("roi_pool")
    assert "tpu_custom_call" not in text and "stablehlo.while" in text


def test_a_map_over_the_bound_takes_the_sweep(monkeypatch):
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    assert not fits_vmem(304, 512, 128, (7, 7), 4)
    text = _lowered_for_tpu("roi_pool", feat_shape=(1, 304, 512, 128))
    assert "tpu_custom_call" not in text and "stablehlo.while" in text


def test_roi_align_callers_are_untouched(monkeypatch):
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    text = _lowered_for_tpu("roi_align")
    assert "pallas_roi_features_fwd" in text
    assert "pallas_roi_features_bwd" in text
    assert "pallas_roi_pool" not in text


def test_the_pair_lowers_under_shard_map():
    """The DP train step runs the pooling under ``jax.shard_map`` with the
    replication checker on: both ``pallas_call``s say over which mesh axes
    their results vary (``ops.pallas.out_struct``)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

    def local(f, r):
        def loss(x):
            return (roi_pool_pallas(x, r, (7, 7), 1.0 / 16) ** 2).sum()

        value, grad = jax.value_and_grad(loss)(f)
        return jax.lax.pmean(value, "data"), grad

    step = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data"))))
    text = step.trace(
        jax.ShapeDtypeStruct((4, 12, 16, 128), jnp.float32),
        jax.ShapeDtypeStruct((4, 6, 4), jnp.float32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "pallas_roi_pool_fwd" in text and "pallas_roi_pool_bwd" in text
