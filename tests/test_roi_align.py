"""ROIAlign / ROIPool correctness tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.roi_align import roi_align, roi_pool


class TestRoiAlign:
    def test_constant_map(self):
        feat = jnp.full((20, 20, 3), 5.0)
        rois = jnp.array([[0.0, 0.0, 160.0, 160.0]])
        out = roi_align(feat, rois, (7, 7), 1.0 / 16.0, 2)
        assert out.shape == (1, 7, 7, 3)
        np.testing.assert_allclose(out, 5.0, atol=1e-5)

    def test_linear_ramp_exact(self):
        # bilinear sampling of a linear function is exact
        h, w = 32, 32
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        feat = jnp.array((2.0 * xx + 3.0 * yy)[:, :, None])
        roi = np.array([[32.0, 32.0, 96.0, 96.0]], np.float32)  # feat coords 2..6
        out = np.asarray(roi_align(jnp.array(feat), jnp.array(roi), (4, 4), 1.0 / 16.0, 2))
        # bin (0,0) center samples average to feat coords x=y=2+0.5
        bin_sz = 4.0 / 4.0
        for p in range(4):
            for q in range(4):
                cy = 2.0 + (p + 0.5) * bin_sz
                cx = 2.0 + (q + 0.5) * bin_sz
                np.testing.assert_allclose(out[0, p, q, 0], 2 * cx + 3 * cy, rtol=1e-5)

    def test_gradient_flows(self):
        feat = jnp.array(np.random.RandomState(0).rand(16, 16, 4).astype(np.float32))
        rois = jnp.array([[10.0, 10.0, 100.0, 100.0], [0.0, 0.0, 50.0, 70.0]])

        def loss(f):
            return roi_align(f, rois, (7, 7), 1.0 / 16.0, 2).sum()

        g = jax.grad(loss)(feat)
        assert g.shape == feat.shape
        assert float(jnp.abs(g).sum()) > 0
        # gradient concentrated inside the rois' footprint
        assert float(jnp.abs(g[14:, 14:]).sum()) < 1e-5

    def test_many_rois_chunked(self):
        feat = jnp.array(np.random.RandomState(1).rand(10, 10, 2).astype(np.float32))
        rois = jnp.array(np.random.RandomState(2).rand(77, 4).astype(np.float32) * 80)
        rois = rois.at[:, 2:].set(rois[:, :2] + 40)
        out = roi_align(feat, rois, (3, 3), 1.0 / 16.0, 2, chunk=16)
        assert out.shape == (77, 3, 3, 2)
        # chunking must not change values
        out2 = roi_align(feat, rois, (3, 3), 1.0 / 16.0, 2, chunk=77)
        np.testing.assert_allclose(out, out2, rtol=1e-6)


class TestRoiPool:
    def test_max_semantics(self):
        # place a spike; any bin containing it must return the spike value
        feat = np.zeros((10, 10, 1), np.float32)
        feat[3, 4, 0] = 9.0
        rois = jnp.array([[0.0, 0.0, 159.0, 159.0]])  # whole 10x10 feat map
        out = np.asarray(roi_pool(jnp.array(feat), rois, (2, 2), 1.0 / 16.0))
        assert out.max() == 9.0
        assert out.shape == (1, 2, 2, 1)
        # spike at feat (y=3,x=4) -> bin (0, 0) for 2x2 over 10 cells
        assert out[0, 0, 0, 0] == 9.0

    def test_quantization_matches_mxnet_rule(self):
        # roi [17, 17, 48, 48] px -> round(x/16) = cells [1..3]; 1x1 pool
        feat = np.arange(100, dtype=np.float32).reshape(10, 10, 1)
        rois = jnp.array([[17.0, 17.0, 48.0, 48.0]])
        out = np.asarray(roi_pool(jnp.array(feat), rois, (1, 1), 1.0 / 16.0))
        # max over cells rows 1..3 cols 1..3 = feat[3, 3] = 33
        assert out[0, 0, 0, 0] == 33.0

    def test_tiny_roi_all_bins_cover_one_cell(self):
        # 1-cell roi pooled to 7x7: MXNet floor/ceil edges make EVERY bin
        # cover that single cell (never empty for in-bounds rois)
        feat = np.full((10, 10, 1), -5.0, np.float32)
        rois = jnp.array([[0.0, 0.0, 1.0, 1.0]])
        out = np.asarray(roi_pool(jnp.array(feat), rois, (7, 7), 1.0 / 16.0))
        assert (out == -5.0).all()

    def test_out_of_bounds_bins_zero(self):
        # roi hanging off the feature map edge -> clipped bins are empty
        # -> 0 (MXNet emits 0 for empty bins)
        feat = np.full((10, 10, 1), -5.0, np.float32)
        rois = jnp.array([[0.0, 0.0, 300.0, 300.0]])  # cells 0..18, map has 10
        out = np.asarray(roi_pool(jnp.array(feat), rois, (7, 7), 1.0 / 16.0))
        assert (out == -5.0).sum() >= 9   # in-bounds bins see the map
        assert (out == 0.0).sum() >= 20   # off-map bins zeroed


def test_batched_roi_pool_sequential_matches_per_image():
    """extract_roi_features_batched's roi_pool branch runs a SEQUENTIAL
    lax.map over the batch (a vmapped scan body re-materializes every
    chunk's masked intermediate — 16.6 GB at flagship, observed OOM) and
    remats the chunk body; both must be invisible to results, and the
    backward must stay finite and match the per-image jacobian path."""
    import jax

    from mx_rcnn_tpu.ops.roi_align import (
        extract_roi_features,
        extract_roi_features_batched,
    )

    rng = np.random.RandomState(0)
    feat = jnp.asarray(rng.rand(3, 9, 11, 6).astype(np.float32))
    rois = jnp.asarray(
        np.stack(
            [
                np.array([[0, 0, 60, 60], [16, 16, 120, 100],
                          [5, 40, 90, 160], [0, 0, 30, 30],
                          [32, 0, 170, 80]], np.float32)
                + 3.0 * i
                for i in range(3)
            ]
        )
    )
    got = extract_roi_features_batched(feat, rois, "roi_pool", (7, 7), 1.0 / 16)
    want = jnp.stack([
        extract_roi_features(feat[i], rois[i], "roi_pool", (7, 7), 1.0 / 16)
        for i in range(3)
    ])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)

    def loss(f):
        out = extract_roi_features_batched(f, rois, "roi_pool", (7, 7), 1.0 / 16)
        return (out ** 2).sum()

    g = jax.grad(loss)(feat)
    gw = jax.grad(
        lambda f: sum(
            (extract_roi_features(f[i], rois[i], "roi_pool", (7, 7), 1.0 / 16) ** 2).sum()
            for i in range(3)
        )
    )(feat)
    assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(g), np.asarray(gw), rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------------------
# ROI max pooling against an independent loop written from MXNet's
# ``ROIPooling`` (``src/operator/roi_pooling.cc``): round the roi to cells
# (C's ``round``: a half goes away from zero), ``bin = roi / pooled``, bin
# ``p`` spans ``floor(p·bin) .. ceil((p+1)·bin)`` from the roi's start,
# clipped to the map; the maximum over the bin, 0 for an empty one; the
# gradient goes to the arg-max cell.  The loop takes the edges in exact
# integer arithmetic (the roi's extent is a whole number of cells) and
# slices the map: nothing of the masked-maxima formulations.  Held to it,
# EXACTLY and everywhere: the program's ``roi_pool`` and the pooling the
# benchmark's VGG reference brings (``benchmark/reference/models/vgg.py::
# roi_max_pool``), two formulations written apart.
#
# The frozen ``benchmark/reference/ops/roi_align.py::roi_pool`` (which no
# cell runs) keeps the formulation the program had until PR 33: a bin's far
# edge as ``ceil(start + (p+1)·(extent/7))`` in float32, which takes ONE
# MORE row or column wherever the exact product is a whole number and a
# rounding lifts it over (1243 of the 5852 (start, extent) pairs a 38x64
# map can meet under XLA's CPU backend, 1007 on a TPU v5e: my CPU and chip
# runs, PR 33), and ``jnp.round``, which takes a half cell to even.  One
# case below records that, so that nobody takes the copy for the operator.

import itertools  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from roi_pool_cases import (  # noqa: E402 — the loop and the roi sets
    ROIS as _ROIS,
    clipped_rois as _clipped_rois,
    every_edge_rois,
    small_map as _feat,
    half_cell_rois as _half_cell_rois,
    inner_rois as _inner_rois,
    mxnet_roi_pool as _mxnet_roi_pool,
    position_map,
)


def _pool(which):
    """→ ``pool(feat, rois, valid_hw=None)`` at 7x7, 1/16."""
    if which == "program":
        return lambda f, r, valid_hw=None: roi_pool(
            f, r, (7, 7), 1.0 / 16.0, valid_hw=valid_hw)
    if which == "kernel":   # the Pallas pair, interpreted on the CPU
        from mx_rcnn_tpu.ops.pallas.roi_pool import roi_pool_pallas

        return lambda f, r: roi_pool_pallas(
            f[None], r[None], (7, 7), 1.0 / 16.0, True)[0]
    if which == "reference":
        from reference.models.vgg import roi_max_pool

        return lambda f, r: roi_max_pool(f, r, (7, 7), 1.0 / 16.0)
    from reference.ops.roi_align import roi_pool as frozen_copy

    return lambda f, r: frozen_copy(f, r, (7, 7), 1.0 / 16.0)


@pytest.mark.parametrize("rois", sorted(_ROIS))
@pytest.mark.parametrize("which", ["program", "reference", "kernel"])
def test_roi_pool_equals_the_mxnet_loop(which, rois):
    feat, rois = _feat(), _ROIS[rois]()
    got = np.asarray(_pool(which)(jnp.asarray(feat), jnp.asarray(rois)))
    want, _ = _mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0)
    np.testing.assert_array_equal(got, want.astype(np.float32))


def test_roi_pool_cases_say_what_they_claim():
    feat = _feat()
    want, _ = _mxnet_roi_pool(feat, _clipped_rois(), (7, 7), 1.0 / 16.0)
    assert (want[5] == 0).all()                       # empty bins emit 0
    assert (want[0] == feat[11, 19]).all()            # one cell, 49 times
    want, _ = _mxnet_roi_pool(feat, _inner_rois(), (7, 7), 1.0 / 16.0)
    assert (want[0] == feat[3, 2]).all()
    assert (want[5] == feat[2:9, 1:8]).all()          # a cell a bin
    want, _ = _mxnet_roi_pool(feat, _half_cell_rois(), (7, 7), 1.0 / 16.0)
    assert (want[3] == feat[1, 3]).all()              # away from zero


def test_roi_pool_under_valid_hw_equals_the_mxnet_loop():
    """``valid_hw`` smaller than the canvas: the cells past the image's own
    extent never win a maximum (the program's serving path; the train
    graph and so the reference pass none)."""
    feat, valid_hw = _feat(), (150.0, 270.0)
    for rois in (_clipped_rois(), _inner_rois(), _half_cell_rois()):
        got = np.asarray(_pool("program")(
            jnp.asarray(feat), jnp.asarray(rois),
            valid_hw=jnp.asarray(valid_hw, jnp.float32)))
        want, _ = _mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0, valid_hw)
        np.testing.assert_array_equal(got, want.astype(np.float32))
    full, _ = _mxnet_roi_pool(feat, _clipped_rois(), (7, 7), 1.0 / 16.0)
    want, _ = _mxnet_roi_pool(feat, _clipped_rois(), (7, 7), 1.0 / 16.0,
                              valid_hw)
    assert (full != want).any() and (want[0] == 0).all()   # the limit binds


@pytest.mark.parametrize("which", ["program", "reference", "kernel"])
def test_roi_pool_takes_every_bin_edge_a_map_can_meet(which):
    """Every (start, extent) pair of the cell's 38x64 map, by a map whose
    values are its cells' positions: the count the float32 formulation
    got wrong on a sixth of the pairs is 0."""
    feat, rois = position_map(38, 64), every_edge_rois(38, 64)
    assert len(rois) > 2000
    pool = jax.jit(_pool(which))
    got = np.concatenate([
        np.asarray(pool(jnp.asarray(feat), jnp.asarray(rois[i:i + 256])))
        for i in range(0, len(rois), 256)])
    want, _ = _mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0)
    assert int((got != want.astype(np.float32)).any(axis=(1, 2, 3)).sum()) == 0


def test_the_frozen_copy_is_the_loop_s_bin_or_one_cell_more():
    """What the old float32 edges do, on the copy that still has them."""
    feat, rois = _feat(), _inner_rois()
    got = np.asarray(_pool("frozen_copy")(jnp.asarray(feat), jnp.asarray(rois)))
    variants = [
        _mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0, more=m)[0].astype(
            np.float32)
        for m in itertools.product((0, 1), repeat=2)]
    assert np.logical_or.reduce([got == v for v in variants]).all()
    # most values ARE the exact bin's (a wider bin often has the same
    # maximum), not all
    assert 0.9 < (got == variants[0]).mean() < 1.0


@pytest.mark.parametrize("rois", sorted(_ROIS))
@pytest.mark.parametrize("which", ["program", "reference", "kernel"])
def test_roi_pool_gradient_lands_on_the_arg_max_cell(which, rois):
    pool = _pool(which)
    feat, rois = _feat(), _ROIS[rois]()
    cot = np.random.RandomState(5).randint(1, 9, (len(rois), 7, 7, 5))
    got = np.asarray(jax.grad(
        lambda f: (pool(f, jnp.asarray(rois))
                   * jnp.asarray(cot, jnp.float32)).sum())(jnp.asarray(feat)))
    _, want = _mxnet_roi_pool(feat, rois, (7, 7), 1.0 / 16.0, cot=cot)
    # whole-number cotangents: the sums are exact in float32
    np.testing.assert_array_equal(got, want.astype(np.float32))
    assert (want != 0).sum() > 50
