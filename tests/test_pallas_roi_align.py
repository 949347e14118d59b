"""Pallas ROIAlign kernel vs the jnp gather reference, fwd and bwd
(SURVEY §5.1/§7.3: the ROIAlign backward is "the fiddliest kernel; test
against a jax.grad of a gather-based reference")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.pallas.roi_align import roi_align_pallas
from mx_rcnn_tpu.ops.roi_align import roi_align


def random_rois(rng, r, h_img, w_img):
    """(R, 4) boxes in image coords, including degenerate/border cases."""
    x1 = rng.rand(r) * w_img * 0.8
    y1 = rng.rand(r) * h_img * 0.8
    x2 = x1 + rng.rand(r) * (w_img - x1)
    y2 = y1 + rng.rand(r) * (h_img - y1)
    rois = np.stack([x1, y1, x2, y2], axis=1).astype(np.float32)
    if r >= 4:
        rois[0] = [0, 0, w_img - 1, h_img - 1]          # full image
        rois[1] = [5, 5, 5.5, 5.5]                       # sub-cell roi
        rois[2] = [w_img - 2, h_img - 2, w_img + 50, h_img + 50]  # past border
        rois[3] = [0, 0, 0, 0]                           # degenerate at origin
    return rois


class TestPallasRoiAlign:
    @pytest.mark.parametrize("pooled", [(7, 7), (14, 14)])
    def test_fwd_matches_jnp(self, rng, pooled):
        h, w, c = 20, 30, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 8, h * 16, w * 16))
        ref = roi_align(feat, rois, pooled, 1.0 / 16, 2)
        got = roi_align_pallas(
            feat[None], rois[None], pooled, 1.0 / 16, 2, True
        )[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_fwd_batched(self, rng):
        b, h, w, c = 3, 12, 16, 256
        feat = jnp.asarray(rng.randn(b, h, w, c).astype(np.float32))
        rois = jnp.asarray(
            np.stack([random_rois(rng, 6, h * 16, w * 16) for _ in range(b)])
        )
        got = roi_align_pallas(feat, rois, (7, 7), 1.0 / 16, 2, True)
        for i in range(b):
            ref = roi_align(feat[i], rois[i], (7, 7), 1.0 / 16, 2)
            np.testing.assert_allclose(
                np.asarray(got[i]), np.asarray(ref), rtol=1e-5, atol=1e-5
            )

    def test_bwd_matches_jnp_grad(self, rng):
        h, w, c = 14, 18, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 5, h * 16, w * 16))
        cot = jnp.asarray(rng.randn(5, 7, 7, c).astype(np.float32))

        ref_grad = jax.grad(
            lambda f: (roi_align(f, rois, (7, 7), 1.0 / 16, 2) * cot).sum()
        )(feat)
        got_grad = jax.grad(
            lambda f: (
                roi_align_pallas(f[None], rois[None], (7, 7), 1.0 / 16, 2, True)[0]
                * cot
            ).sum()
        )(feat)
        np.testing.assert_allclose(
            np.asarray(got_grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-4
        )

    def test_bf16_finite_and_close(self, rng):
        h, w, c = 10, 12, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 4, h * 16, w * 16))
        ref = roi_align(feat, rois, (7, 7), 1.0 / 16, 2)
        got = roi_align_pallas(
            feat[None].astype(jnp.bfloat16), rois[None], (7, 7), 1.0 / 16, 2, True
        )[0]
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
        )


class TestStreamingRoiAlign:
    """Streaming (row-blocked) kernel for over-VMEM maps: must match the
    gather reference exactly (interpret mode), including rois that
    straddle row-block boundaries and R not divisible by the roi block."""

    @pytest.fixture
    def rng(self):
        return np.random.RandomState(7)

    def test_fwd_matches_jnp(self, rng):
        from mx_rcnn_tpu.ops.pallas.roi_align_stream import roi_align_stream

        h, w, c = 40, 64, 128  # hblk=64? _pick_hblk(64,128)=64 -> force blocks
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 11, h * 4, w * 4))
        ref = roi_align(feat, rois, (7, 7), 0.25, 2)
        got = roi_align_stream(feat[None], rois[None], (7, 7), 0.25, 2, True)[0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_fwd_small_row_blocks(self, rng, monkeypatch):
        """Force tiny row blocks so every roi straddles many blocks."""
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as mod

        monkeypatch.setattr(mod, "_pick_hblk", lambda w, cblk, budget=0: 8)
        h, w, c = 33, 16, 128  # 33 rows -> 5 blocks incl. ragged last
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 6, h * 4, w * 4))
        ref = roi_align(feat, rois, (7, 7), 0.25, 2)
        got = mod.roi_align_stream(feat[None], rois[None], (7, 7), 0.25, 2, True)[0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_bwd_matches_jnp_grad(self, rng, monkeypatch):
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as mod

        monkeypatch.setattr(mod, "_pick_hblk", lambda w, cblk, budget=0: 8)
        h, w, c = 26, 20, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 5, h * 4, w * 4))
        cot = jnp.asarray(rng.randn(5, 7, 7, c).astype(np.float32))
        ref_grad = jax.grad(
            lambda f: (roi_align(f, rois, (7, 7), 0.25, 2) * cot).sum()
        )(feat)
        got_grad = jax.grad(
            lambda f: (
                mod.roi_align_stream(f[None], rois[None], (7, 7), 0.25, 2, True)[0]
                * cot
            ).sum()
        )(feat)
        np.testing.assert_allclose(
            np.asarray(got_grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-4
        )

    def test_batched_and_bf16(self, rng):
        from mx_rcnn_tpu.ops.pallas.roi_align_stream import roi_align_stream

        b, h, w, c = 2, 24, 32, 128
        feat = jnp.asarray(rng.randn(b, h, w, c).astype(np.float32))
        rois = jnp.stack(
            [jnp.asarray(random_rois(rng, 4, h * 4, w * 4)) for _ in range(b)]
        )
        ref = jax.vmap(lambda f, r: roi_align(f, r, (7, 7), 0.25, 2))(feat, rois)
        got = roi_align_stream(
            feat.astype(jnp.bfloat16), rois, (7, 7), 0.25, 2, True
        )
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
        )

    def test_degenerate_and_offscreen_rois(self, rng, monkeypatch):
        """Sub-cell-height rois reach ~y1+1 in sample space (the
        min-length clamp), so their hi-neighbour row can live in the
        NEXT row block; rois clipped off the map edges still touch the
        edge rows.  Block-skip must not drop those contributions."""
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as mod

        monkeypatch.setattr(mod, "_pick_hblk", lambda w, cblk, budget=0: 8)
        h, w, c = 24, 16, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(
            [
                # floor(y1*scale)=6 == block_boundary-2 (hblk 8), height<1 cell
                [8.0, 27.6, 20.0, 27.6],
                # y extent fully above the map (clips to row 0)
                [4.0, -300.0, 40.0, -200.0],
                # y extent fully below the map (clips to last row)
                [4.0, 500.0, 40.0, 600.0],
                # straddles the last ragged block edge
                [2.0, 91.0, 30.0, 95.9],
            ],
            jnp.float32,
        )
        ref = roi_align(feat, rois, (7, 7), 0.25, 2)
        got = mod.roi_align_stream(feat[None], rois[None], (7, 7), 0.25, 2, True)[0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )
        # gradients through the same rois
        cot = jnp.asarray(rng.randn(4, 7, 7, c).astype(np.float32))
        ref_g = jax.grad(
            lambda f: (roi_align(f, rois, (7, 7), 0.25, 2) * cot).sum()
        )(feat)
        got_g = jax.grad(
            lambda f: (
                mod.roi_align_stream(f[None], rois[None], (7, 7), 0.25, 2, True)[0]
                * cot
            ).sum()
        )(feat)
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(ref_g), rtol=1e-4, atol=1e-4
        )

    def test_mask_head_pooled_14(self, rng):
        """pooled=(14,14) (the mask head) auto-shrinks the roi block so
        the scratch accumulator stays within VMEM budget."""
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as mod

        assert mod._pick_rblk((14, 14), 128) <= 48
        h, w, c = 20, 24, 128
        feat = jnp.asarray(rng.randn(h, w, c).astype(np.float32))
        rois = jnp.asarray(random_rois(rng, 5, h * 4, w * 4))
        ref = roi_align(feat, rois, (14, 14), 0.25, 2)
        got = mod.roi_align_stream(feat[None], rois[None], (14, 14), 0.25, 2, True)[0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


# [start, count] of image 0 and image 1 over 21 rois in roi blocks of 8
# (three blocks, the last one ragged: 5 rois and 3 fillers)
_SPAN_CASES = {
    "starts-inside-a-block": ((3, 9), (0, 21)),
    "ends-on-a-block-edge": ((5, 11), (8, 8)),
    "covers-nothing": ((10, 0), (0, 0)),
    "covers-everything": ((0, 21), (0, 21)),
    "starts-past-block-0": ((17, 4), (9, 3)),
    "one-roi-and-an-empty-image": ((20, 1), (21, 0)),
}


def _own_mask(span, r):
    idx = np.arange(r)[None]
    span = np.asarray(span)
    return (idx >= span[:, :1]) & (idx < span[:, :1] + span[:, 1:])


class TestStreamingRoiAlignSpan:
    """The streaming pair with a span (``models/fpn.py::pool_levels``
    hands each level the rois sorted by level and the level's own
    ``[start, start + count)``): the span's rois are pooled as without
    it, nothing else is visited, and the map's gradient is that of the
    span's rois alone.  Row blocks of 8 rows and roi blocks of 8 rois, so
    every case crosses both kinds of block edge; 21 rois leave the last
    roi block ragged."""

    B, H, W, C, R = 2, 26, 20, 128, 21

    @pytest.fixture
    def rng(self):
        return np.random.RandomState(11)

    @pytest.fixture
    def mod(self, monkeypatch):
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as mod

        monkeypatch.setattr(mod, "_pick_hblk", lambda w, cblk, budget=0: 8)
        monkeypatch.setattr(mod, "_pick_rblk", lambda pooled, cblk, budget=0: 8)
        return mod

    def _inputs(self, rng):
        feat = rng.randn(self.B, self.H, self.W, self.C).astype(np.float32)
        rois = np.stack([random_rois(rng, self.R, self.H * 4, self.W * 4)
                         for _ in range(self.B)])
        return jnp.asarray(feat), jnp.asarray(rois)

    @pytest.mark.parametrize("case", list(_SPAN_CASES))
    def test_fwd_of_the_own_rois_is_bitwise_the_call_without_a_span(
            self, rng, mod, case):
        feat, rois = self._inputs(rng)
        span = jnp.asarray(_SPAN_CASES[case], jnp.int32)
        full = mod.roi_align_stream(feat, rois, (7, 7), 0.25, 2, True)
        got = mod.roi_align_stream(feat, rois, (7, 7), 0.25, 2, True, span)
        assert got.shape == full.shape
        own = _own_mask(span, self.R)
        np.testing.assert_array_equal(
            np.asarray(got)[own], np.asarray(full)[own])

    @pytest.mark.parametrize("case", list(_SPAN_CASES))
    def test_bwd_is_the_gather_gradient_of_the_own_rois_alone(
            self, rng, mod, case):
        """What the caller selects away carries no cotangent (and may hold
        anything: the selection is a ``where``).  An image whose count is
        0 gets a zero map back."""
        feat, rois = self._inputs(rng)
        span = jnp.asarray(_SPAN_CASES[case], jnp.int32)
        own = jnp.asarray(_own_mask(span, self.R))[..., None, None, None]
        cot = jnp.asarray(
            rng.randn(self.B, self.R, 7, 7, self.C).astype(np.float32))

        def loss(pool):
            return lambda f: (jnp.where(own, pool(f), 0.0) * cot).sum()

        ref = jax.grad(loss(lambda f: jax.vmap(
            lambda f1, r1: roi_align(f1, r1, (7, 7), 0.25, 2))(f, rois)))(feat)
        got = jax.grad(loss(lambda f: mod.roi_align_stream(
            f, rois, (7, 7), 0.25, 2, True, span)))(feat)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)
        for b, (_start, count) in enumerate(_SPAN_CASES[case]):
            if count == 0:
                assert not np.asarray(got[b]).any()

    def test_bf16_with_a_span(self, rng, mod):
        feat, rois = self._inputs(rng)
        span = jnp.asarray(_SPAN_CASES["starts-inside-a-block"], jnp.int32)
        full = mod.roi_align_stream(
            feat.astype(jnp.bfloat16), rois, (7, 7), 0.25, 2, True)
        got = mod.roi_align_stream(
            feat.astype(jnp.bfloat16), rois, (7, 7), 0.25, 2, True, span)
        assert got.dtype == jnp.bfloat16
        own = _own_mask(span, self.R)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32)[own], np.asarray(full, np.float32)[own])

    @pytest.mark.parametrize("with_span", [False, True],
                             ids=["every-roi", "span"])
    def test_scalar_prefetch_operands(self, rng, mod, with_span):
        """``span=None`` is the pair as it always lowered: one
        scalar-prefetch operand (the rois), forward and backward.  The
        span rides in as a second one only when the caller gives it."""
        feat, rois = self._inputs(rng)
        span = jnp.asarray(_SPAN_CASES["starts-inside-a-block"], jnp.int32)
        jaxpr = jax.make_jaxpr(jax.grad(lambda f, r, sp: mod.roi_align_stream(
            f, r, (7, 7), 0.25, 2, True, sp if with_span else None,
        ).sum()))(feat, rois, span)
        calls = _pallas_calls(jaxpr.jaxpr)
        assert [c.params["name"] for c in calls] == [
            "pallas_roi_features_stream_fwd", "pallas_roi_features_stream_bwd"]
        for call in calls:
            mapping = call.params["grid_mapping"]
            assert mapping.num_index_operands == (2 if with_span else 1)
            assert len(call.invars) == mapping.num_index_operands + 1

    @pytest.mark.parametrize("span,want", [
        ((0, 40), [1, 0, 0, 0]), ((39, 2), [1, 1, 0, 0]),
        ((50, 0), [0, 0, 0, 0]), ((100, 28), [0, 0, 1, 1]),
        ((128, 0), [0, 0, 0, 0]), ((80, 1), [0, 0, 1, 0]),
        ((0, 128), [1, 1, 1, 1]), ((40, 40), [0, 1, 0, 0]),
    ], ids=lambda v: "-".join(map(str, v)))
    def test_live_roi_blocks_by_hand(self, span, want):
        """128 rois at 14x14 and 256 channels walk four roi blocks of 40
        (the train cell's own numbers); which of them a span touches."""
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as real

        assert real._pick_rblk((14, 14), 128) == 40
        live = real.live_roi_blocks(
            jnp.asarray([span], jnp.int32), 128, (14, 14), 256)
        assert np.asarray(live).astype(int).tolist() == [want]


# canvas of the valid_hw cases: 12 x 16 cells at stride 16 = a 192 x 256
# bucket; (100, 150) is an image with 7 x 10 cells of content in it
_H, _W, _C = 12, 16, 128
_CANVAS_HW = (_H * 16.0, _W * 16.0)

# (valid_hw of each image, rois of every image or None for random ones)
_VALID_HW_CASES = {
    "both-axes-smaller": ([(100.0, 150.0)] * 2, None),
    "rows-only-smaller": ([(100.0, _CANVAS_HW[1])], None),
    "cols-only-smaller": ([(_CANVAS_HW[0], 150.0)], None),
    "equal-to-canvas": ([_CANVAS_HW] * 2, None),
    # x1 < 150 < x2 and y1 < 100 < y2, one axis and both
    "rois-crossing-the-valid-edge": ([(100.0, 150.0)], [
        [120, 20, 200, 80], [20, 60, 120, 140], [100, 70, 250, 190],
        [0, 0, 255, 191],
    ]),
    "rois-wholly-in-the-padding": ([(100.0, 150.0)], [
        [160, 110, 250, 190], [152, 10, 200, 90], [10, 104, 140, 180],
        [151, 101, 151.5, 101.5],
    ]),
    "images-of-different-extents": (
        [(100.0, 150.0), _CANVAS_HW, (192.0, 100.0), (33.0, 17.0)], None),
}


def _valid_hw_case(rng, name):
    valid, rois = _VALID_HW_CASES[name]
    b = len(valid)
    feat = rng.randn(b, _H, _W, _C).astype(np.float32)
    if rois is None:
        rois = np.stack([random_rois(rng, 6, *_CANVAS_HW) for _ in range(b)])
    else:
        rois = np.tile(np.asarray(rois, np.float32)[None], (b, 1, 1))
    return jnp.asarray(feat), jnp.asarray(rois), jnp.asarray(valid, jnp.float32)


class TestPallasRoiAlignValidHw:
    """The resident kernel's per-image valid-extent clamp (serving's
    padding invariance) against the gather path's, image by image."""

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 0.05)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", list(_VALID_HW_CASES))
    def test_fwd_matches_gather(self, rng, case, dtype, tol):
        feat, rois, valid_hw = _valid_hw_case(rng, case)
        got = roi_align_pallas(
            feat.astype(dtype), rois, (7, 7), 1.0 / 16, 2, True,
            valid_hw=valid_hw,
        )
        assert got.dtype == dtype
        for i in range(feat.shape[0]):
            ref = roi_align(feat[i], rois[i], (7, 7), 1.0 / 16, 2,
                            valid_hw=valid_hw[i])
            np.testing.assert_allclose(
                np.asarray(got[i], np.float32), np.asarray(ref),
                rtol=tol, atol=tol,
            )
        if case == "equal-to-canvas":
            # the limit is the canvas: the same arithmetic as without it
            canvas = roi_align_pallas(
                feat.astype(dtype), rois, (7, 7), 1.0 / 16, 2, True
            )
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(canvas, np.float32))
        if case == "rois-wholly-in-the-padding":
            # every sample clamps to the last valid row / column
            assert np.isfinite(np.asarray(got, np.float32)).all()

    def test_pooled_features_do_not_depend_on_the_canvas(self, rng):
        """The same valid content zero-padded into two canvases (two shape
        buckets) pools to bitwise-equal features: the clamp depends on the
        image alone, and cells past it carry weight zero."""
        content = rng.randn(7, 10, _C).astype(np.float32)
        rois = jnp.asarray(random_rois(rng, 9, 100.0, 150.0))[None]
        valid_hw = jnp.asarray([[100.0, 150.0]], jnp.float32)
        outs = []
        for h, w in ((_H, _W), (10, 20)):
            feat = np.zeros((1, h, w, _C), np.float32)
            feat[0, :7, :10] = content
            outs.append(np.asarray(roi_align_pallas(
                jnp.asarray(feat), rois, (7, 7), 1.0 / 16, 2, True,
                valid_hw=valid_hw,
            )))
        assert np.array_equal(outs[0], outs[1])
        assert np.abs(outs[0]).max() > 0.1

    def test_bwd_carries_the_limits(self, rng):
        """No training path passes ``valid_hw``; the backward is still the
        transpose of the forward it belongs to."""
        feat, rois, valid_hw = _valid_hw_case(
            rng, "images-of-different-extents")
        cot = jnp.asarray(
            rng.randn(*rois.shape[:2], 7, 7, _C).astype(np.float32))
        ref_grad = jax.grad(lambda f: sum(
            (roi_align(f[i], rois[i], (7, 7), 1.0 / 16, 2,
                       valid_hw=valid_hw[i]) * cot[i]).sum()
            for i in range(f.shape[0])
        ))(feat)
        got_grad, dvalid = jax.grad(
            lambda f, v: (roi_align_pallas(
                f, rois, (7, 7), 1.0 / 16, 2, True, valid_hw=v
            ) * cot).sum(), argnums=(0, 1),
        )(feat, valid_hw)
        np.testing.assert_allclose(
            np.asarray(got_grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-4
        )
        assert not np.asarray(dvalid).any()

    @pytest.mark.parametrize("with_valid_hw", [False, True],
                             ids=["canvas", "valid_hw"])
    def test_scalar_prefetch_operands(self, rng, with_valid_hw):
        """``valid_hw=None`` is the program training always ran: one
        scalar-prefetch operand (the rois), and the clamp a constant.  The
        limits ride in as a second one only when the caller gives them."""
        feat, rois, valid_hw = _valid_hw_case(rng, "both-axes-smaller")
        jaxpr = jax.make_jaxpr(lambda f, r, v: roi_align_pallas(
            f, r, (7, 7), 1.0 / 16, 2, True,
            valid_hw=v if with_valid_hw else None,
        ))(feat, rois, valid_hw)
        calls = _pallas_calls(jaxpr.jaxpr)
        assert len(calls) == 1
        mapping = calls[0].params["grid_mapping"]
        assert mapping.num_index_operands == (2 if with_valid_hw else 1)
        assert len(calls[0].invars) == mapping.num_index_operands + 1


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


class TestRoiAlignDispatchValidHw:
    """``extract_roi_features_batched`` on a TPU (``use_pallas`` steered:
    the backend here is the CPU): where a map with ``valid_hw`` goes."""

    @pytest.fixture
    def reached(self, monkeypatch):
        from mx_rcnn_tpu.ops import roi_align as ops_mod
        from mx_rcnn_tpu.ops.pallas import roi_align as resident_mod
        from mx_rcnn_tpu.ops.pallas import roi_align_stream as stream_mod
        from mx_rcnn_tpu.utils import platform

        monkeypatch.setattr(platform, "use_pallas", lambda: True)
        calls = []

        def record(name, real, **forced):
            def wrapped(*args, **kwargs):
                calls.append((name, kwargs.get("valid_hw")))
                return real(*args, **{**kwargs, **forced})
            return wrapped

        monkeypatch.setattr(resident_mod, "roi_align_pallas", record(
            "resident", resident_mod.roi_align_pallas, interpret=True))
        monkeypatch.setattr(stream_mod, "roi_align_stream", record(
            "stream", stream_mod.roi_align_stream))
        monkeypatch.setattr(ops_mod, "roi_align", record(
            "gather", ops_mod.roi_align))
        return calls

    @staticmethod
    def _trace(feat_shape, pooled, fwd_only, with_valid_hw=True):
        from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched

        b = feat_shape[0]
        return jax.eval_shape(
            lambda f, r, v: extract_roi_features_batched(
                f, r, "roi_align", pooled, 1.0 / 16, 2, fwd_only=fwd_only,
                valid_hw=v if with_valid_hw else None,
            ),
            jax.ShapeDtypeStruct(feat_shape, jnp.float32),
            jax.ShapeDtypeStruct((b, 300, 4), jnp.float32),
            jax.ShapeDtypeStruct((b, 2), jnp.float32),
        )

    def test_fitting_map_with_valid_hw_takes_the_resident_kernel(
            self, reached):
        """The serve graph's own shape: the C4 map at the ladder's extent."""
        out = self._trace((8, 64, 64, 1024), (14, 14), fwd_only=True)
        assert out.shape == (8, 300, 14, 14, 1024)
        assert [name for name, _ in reached] == ["resident"]
        assert reached[0][1] is not None

    def test_over_vmem_fwd_only_map_keeps_the_gather(self, reached):
        """FPN P2 at flagship resolution: forward-only graphs gather there,
        with ``valid_hw`` as without it."""
        self._trace((2, 152, 256, 256), (7, 7), fwd_only=True)
        assert [name for name, _ in reached] == ["gather"]
        assert reached[0][1] is not None

    def test_over_vmem_map_with_valid_hw_never_streams(self, reached):
        """The streaming kernel clamps to the canvas: ``valid_hw`` keeps an
        over-VMEM map off it even in a differentiated graph, and without
        ``valid_hw`` that graph streams as before."""
        self._trace((2, 152, 256, 256), (7, 7), fwd_only=False)
        assert [name for name, _ in reached] == ["gather"]
        del reached[:]
        self._trace((2, 152, 256, 256), (7, 7), fwd_only=False,
                    with_valid_hw=False)
        assert [name for name, _ in reached] == ["stream"]
