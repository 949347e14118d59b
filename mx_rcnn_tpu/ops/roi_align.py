"""ROI feature extraction: ROIAlign (bilinear) and exact ROIPool compat.

Reference: MXNet's C++/CUDA ``ROIPooling`` op (SURVEY N6) — max-pool each
roi into a fixed grid with quantized bin edges; the single external custom
kernel the reference graph depends on.  Two TPU-native implementations
behind one signature:

- :func:`roi_align` — bilinear sampling on continuous coordinates
  (align_corners=False convention, `sample_ratio`² points per bin,
  averaged).  Differentiable by construction (pure gather + arithmetic;
  XLA derives the scatter-add backward automatically — no hand-written
  ``custom_vjp`` needed for correctness; the Pallas kernels in
  ``ops/pallas/roi_align*.py`` are the perf path).
- :func:`roi_pool` — exact MXNet ROIPooling semantics: rois quantized by
  C's ``round(x * scale)``, bin edges floor/ceil in whole numbers, max
  over each bin, computed as two masked-max contractions (no
  data-dependent shapes).  The oracle of, and the path without a TPU
  beside, the Pallas pair in ``ops/pallas/roi_pool.py``.

Both jnp forms are chunked with ``lax.map`` over rois to bound their
intermediates in HBM (R×grid×W×C blow-up otherwise);
:func:`extract_roi_features_batched` is where a batch picks a kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _feat_limits(feat_hw, valid_hw, spatial_scale):
    """Per-axis sample-clamp limits: the canvas extent, or — when the true
    pre-padding image size ``valid_hw`` is given — the number of feature
    rows/cols that carry image content, ``ceil(h·scale)``.  Rows past that
    are functions of the zero padding only, and (crucially) the clamp at
    ``size − 1`` then lands at the same coordinate for every canvas the
    image fits in, so the pooled features do not depend on the shape
    bucket (the serving padding-invariance guarantee; see SERVING.md).
    One rule for both implementations: the gather below and the resident
    Pallas kernel (``ops/pallas/roi_align.py``, which hands the float
    limits to its kernel body as scalars) clamp samples to ``lim − 1``
    and cap ``hi`` there.  ``valid_hw``: one image's (2,), or a pair of
    (B,) vectors for a batch."""
    if valid_hw is None:
        return [(float(s), s) for s in feat_hw]
    lims = []
    for s, v in zip(feat_hw, (valid_hw[0], valid_hw[1])):
        lim = jnp.minimum(jnp.ceil(v * spatial_scale), float(s))
        lims.append((lim, lim.astype(jnp.int32)))
    return lims


def _bilinear_one_roi(feat, roi, pooled, sample_ratio, spatial_scale,
                      valid_hw=None):
    """(H, W, C) × (4,) roi → (ph, pw, C) via average of bilinear samples."""
    hf, wf = feat.shape[0], feat.shape[1]
    ph, pw = pooled
    x1, y1, x2, y2 = roi[0], roi[1], roi[2], roi[3]
    x1, y1, x2, y2 = (v * spatial_scale for v in (x1, y1, x2, y2))
    roi_w = jnp.maximum(x2 - x1, 1.0)
    roi_h = jnp.maximum(y2 - y1, 1.0)
    bin_w = roi_w / pw
    bin_h = roi_h / ph
    s = sample_ratio

    # sample grid: for bin p, samples at y1 + (p + (j+0.5)/s) * bin_h
    gy = y1 + (jnp.arange(ph * s) + 0.5) / s * bin_h      # (ph*s,)
    gx = x1 + (jnp.arange(pw * s) + 0.5) / s * bin_w      # (pw*s,)

    def axis_weights(g, lim_f, lim_i):
        g = jnp.clip(g, 0.0, lim_f - 1.0)
        lo = jnp.floor(g).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, lim_i - 1)
        whi = g - lo
        return lo, hi, 1.0 - whi, whi

    (lh_f, lh_i), (lw_f, lw_i) = _feat_limits((hf, wf), valid_hw, spatial_scale)
    ylo, yhi, wy0, wy1 = axis_weights(gy, lh_f, lh_i)
    xlo, xhi, wx0, wx1 = axis_weights(gx, lw_f, lw_i)

    # two-stage separable gather: rows then columns
    rows0 = jnp.take(feat, ylo, axis=0)       # (ph*s, W, C)
    rows1 = jnp.take(feat, yhi, axis=0)
    rows = rows0 * wy0[:, None, None] + rows1 * wy1[:, None, None]
    cols0 = jnp.take(rows, xlo, axis=1)       # (ph*s, pw*s, C)
    cols1 = jnp.take(rows, xhi, axis=1)
    samples = cols0 * wx0[None, :, None] + cols1 * wx1[None, :, None]

    # average the s×s samples per bin
    c = feat.shape[2]
    samples = samples.reshape(ph, s, pw, s, c)
    return samples.mean(axis=(1, 3))


def roi_align(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    pooled: tuple = (14, 14),
    spatial_scale: float = 1.0 / 16.0,
    sample_ratio: int = 2,
    chunk: int = 32,
    valid_hw=None,
) -> jnp.ndarray:
    """(H, W, C) feature + (R, 4) image-coord rois → (R, ph, pw, C).

    ``valid_hw`` (2,) = the true pre-padding image (h, w): samples are
    clamped to the valid feature extent instead of the canvas extent, so
    the output is independent of which shape bucket padded the image."""
    r = rois.shape[0]
    pad = (-r) % chunk
    rois_p = jnp.concatenate([rois, jnp.zeros((pad, 4), rois.dtype)], axis=0)
    chunks = rois_p.reshape(-1, chunk, 4)

    def run_chunk(rs):
        return jax.vmap(
            lambda roi: _bilinear_one_roi(
                feat, roi, pooled, sample_ratio, spatial_scale, valid_hw
            )
        )(rs)

    out = jax.lax.map(run_chunk, chunks)
    return out.reshape(-1, pooled[0], pooled[1], feat.shape[2])[:r]


def _round_half_away(v):
    """C's ``round`` (MXNet's): a half cell goes away from zero, where
    ``jnp.round`` takes it to the even neighbour."""
    t = jnp.trunc(v)
    return t + jnp.where(jnp.abs(v - t) >= 0.5, jnp.sign(v), 0.0)


def _bin_edges(roi, pooled, spatial_scale, feat_hw, valid_hw=None):
    """MXNet ROIPooling's bins for one roi, in whole numbers: →
    ``(hlo, hhi, wlo, whi)``, int32 ``(ph,)`` / ``(pw,)`` vectors; bin
    ``p`` reads the cells ``lo[p] <= i < hi[p]`` (none where ``hi <= lo``).
    The one place the edges are computed: the masked sweep below and the
    Pallas pair (``ops/pallas/roi_pool.py``, which takes them as scalars)
    both read them from here."""
    ph, pw = pooled
    # quantized roi in feature cells (+1 width convention), as whole
    # numbers: a bin's edges are then exact, where ``ceil(start + (p + 1)
    # * (extent / ph))`` in float32 took one cell more on a sixth of the
    # (start, extent) pairs (PERF.md, PR 33).  The bound keeps ``ph *
    # extent`` inside int32 for any float a roi can hold
    x1, y1, x2, y2 = (
        jnp.clip(_round_half_away(roi[i] * spatial_scale),
                 -(2.0 ** 24), 2.0 ** 24).astype(jnp.int32)
        for i in range(4)
    )
    roi_w = jnp.maximum(x2 - x1 + 1, 1)
    roi_h = jnp.maximum(y2 - y1 + 1, 1)

    def edges(start, extent, nbins, lim):
        # bin b spans floor(b * extent / nbins) .. ceil((b + 1) * extent /
        # nbins) from ``start``, clipped to the valid feature extent so
        # padded cells never win the max
        b = jnp.arange(nbins, dtype=jnp.int32)
        lo = jnp.clip(start + (b * extent) // nbins, 0, lim)           # (nb,)
        hi = jnp.clip(start - (-(b + 1) * extent) // nbins, 0, lim)
        return lo, hi

    (_, lh), (_, lw) = _feat_limits(feat_hw, valid_hw, spatial_scale)
    return edges(y1, roi_h, ph, lh) + edges(x1, roi_w, pw, lw)


def _maxpool_one_roi(feat, roi, pooled, spatial_scale, valid_hw=None):
    """Exact MXNet ROIPooling for one roi via masked-max contractions."""
    hf, wf = feat.shape[0], feat.shape[1]
    hlo, hhi, wlo, whi = _bin_edges(roi, pooled, spatial_scale, (hf, wf),
                                    valid_hw)

    def bin_mask(lo, hi, size):
        # mask[b, i]: cell i belongs to bin b
        i = jnp.arange(size, dtype=jnp.int32)
        return (i[None, :] >= lo[:, None]) & (i[None, :] < hi[:, None])

    mh = bin_mask(hlo, hhi, hf)   # (ph, H)
    mw = bin_mask(wlo, whi, wf)   # (pw, W)

    neg = jnp.finfo(feat.dtype).min
    # max over h per bin row, then over w per bin col
    tmp = jnp.where(mh[:, :, None, None], feat[None, :, :, :], neg).max(axis=1)  # (ph, W, C)
    out = jnp.where(mw[None, :, :, None], tmp[:, None, :, :], neg).max(axis=2)   # (ph, pw, C)
    # empty bins (hi<=lo) produce neg; MXNet emits 0 there
    empty = (~mh.any(axis=1))[:, None] | (~mw.any(axis=1))[None, :]
    return jnp.where(empty[:, :, None], 0.0, out)


def roi_pool(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    pooled: tuple = (7, 7),
    spatial_scale: float = 1.0 / 16.0,
    chunk: int = 4,
    valid_hw=None,
) -> jnp.ndarray:
    """(H, W, C) feature + (R, 4) rois → (R, ph, pw, C), max-pooled.

    ``chunk`` bounds the live (chunk, ph, H, W, C) masked-max
    intermediate; at the flagship VGG shape (38×64×512 bf16, ph=7) each
    chunked roi holds ~17 MB, so chunk=4 keeps the scan body ~70 MB.
    The body is rematerialized (jax.checkpoint): reverse-mode through
    lax.map otherwise SAVES each iteration's masked intermediate as a
    scan residual, the full (chunks, chunk, ph, H, W, C) tensor.
    Callers must also not vmap over the batch dim when they
    differentiate (vmap batches the scan body the same way):
    extract_roi_features_batched runs a sequential batch loop then.  On
    a TPU the batch goes to ``ops/pallas/roi_pool.py`` instead; this
    sweep is the path where there is none, and the kernels' oracle."""
    r = rois.shape[0]
    pad = (-r) % chunk
    rois_p = jnp.concatenate([rois, jnp.zeros((pad, 4), rois.dtype)], axis=0)
    chunks = rois_p.reshape(-1, chunk, 4)

    @jax.checkpoint
    def run_chunk(rs):
        return jax.vmap(
            lambda roi: _maxpool_one_roi(feat, roi, pooled, spatial_scale,
                                         valid_hw)
        )(rs)

    out = jax.lax.map(run_chunk, chunks)
    return out.reshape(-1, pooled[0], pooled[1], feat.shape[2])[:r]


def extract_roi_features(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    mode: str,
    pooled: tuple,
    spatial_scale: float,
    sample_ratio: int = 2,
    valid_hw=None,
) -> jnp.ndarray:
    """Dispatch on config ROI_MODE ('roi_align' | 'roi_pool')."""
    if mode == "roi_align":
        return roi_align(feat, rois, pooled, spatial_scale, sample_ratio,
                         valid_hw=valid_hw)
    if mode == "roi_pool":
        return roi_pool(feat, rois, pooled, spatial_scale, valid_hw=valid_hw)
    raise ValueError(f"unknown ROI_MODE {mode!r}")


def roi_align_kernel(feat, pooled, fwd_only: bool = False, valid_hw=None):
    """Which Pallas kernel pools this (B, H, W, C) map under ROIAlign:
    ``"resident"`` (``ops/pallas/roi_align.py`` keeps an (H, W, cblk)
    feature block in VMEM across the roi sweep), ``"stream"`` (maps over
    that budget — FPN P2 at flagship resolution is 152×256 — take
    ``ops/pallas/roi_align_stream.py``, which row-blocks the feature
    through VMEM and accumulates the roi-block outputs in scratch), or
    None: the jnp gather (no TPU, or an over-VMEM map that is
    forward-only or carries ``valid_hw``; see
    :func:`extract_roi_features_batched`)."""
    from mx_rcnn_tpu.ops.pallas.roi_align import fits_vmem
    from mx_rcnn_tpu.utils.platform import use_pallas

    if not use_pallas():
        return None
    if fits_vmem(
        feat.shape[1], feat.shape[2], feat.shape[3], pooled,
        feat.dtype.itemsize,
    ):
        return "resident"
    if not fwd_only and valid_hw is None:
        return "stream"
    return None


def roi_pool_kernel(feat, pooled) -> bool:
    """Whether the Pallas ROI max pooling pair (``ops/pallas/roi_pool.py``)
    pools this (B, H, W, C) map: on a TPU, and the map within the pair's
    own VMEM bound.  Elsewhere the jnp sweep does."""
    from mx_rcnn_tpu.ops.pallas.roi_pool import fits_vmem
    from mx_rcnn_tpu.utils.platform import use_pallas

    return use_pallas() and fits_vmem(
        feat.shape[1], feat.shape[2], feat.shape[3], pooled,
        feat.dtype.itemsize,
    )


def extract_roi_features_batched(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    mode: str,
    pooled: tuple,
    spatial_scale: float,
    sample_ratio: int = 2,
    fwd_only: bool = False,
    valid_hw=None,
    span=None,
) -> jnp.ndarray:
    """(B, H, W, C) × (B, R, 4) → (B, R, ph, pw, C).

    On TPU backends the roi_align path uses the Pallas MXU kernels
    (``ops/pallas/roi_align.py``, ``roi_align_stream.py``) and the
    roi_pool path the Pallas pair of ``ops/pallas/roi_pool.py``, forward
    and differentiated, with or without ``valid_hw``; elsewhere the
    chunked jnp implementations.

    ``fwd_only``: callers that never differentiate this op (eval /
    test_forward) should set it.  For over-VMEM maps the streaming
    kernel only beats the chunked gather when the backward pass is in
    play (real-TPU P2-shape timings, scripts/probe_stream_kernel.py:
    fwd 160 vs 121 ms, fwd+bwd 108 vs 326 ms), so forward-only graphs
    take the gather path there.

    ``valid_hw`` (B, 2) = true pre-padding image sizes (``im_info[:, :2]``):
    sample coordinates clamp to the valid feature extent instead of the
    canvas, making the pooled features independent of the shape bucket
    (the serving padding-invariance contract).  The resident Pallas
    kernel takes the same limits (``_feat_limits``) as a second
    scalar-prefetch operand, so on a TPU a map that fits VMEM is pooled
    by the kernel with or without ``valid_hw``.  The streaming kernel
    clamps to the canvas only: an over-VMEM map with ``valid_hw`` takes
    the jnp gather (every caller that passes ``valid_hw`` is forward-only,
    where the gather is the over-VMEM choice anyway).  That gather is not
    cheap on a TPU: a ``lax.map`` of ten 32-roi chunks was 85% of the
    serve cell's device time before the kernel took ``valid_hw``
    (PERF.md, PR 26).

    ``span`` (B, 2) int32 = ``[start, count]`` an image: the caller reads
    the rois ``start <= r < start + count`` only and selects every other
    row away (``models/fpn.py::pool_levels``: a level's own rois in a
    list sorted by level).  The streaming pair then visits those rois
    alone and leaves the other rows undefined; the resident kernels and
    the gather pool every roi as without it.
    """
    kernel = (roi_align_kernel(feat, pooled, fwd_only, valid_hw)
              if mode == "roi_align" else None)
    if kernel == "resident":
        from mx_rcnn_tpu.ops.pallas.roi_align import roi_align_pallas

        return roi_align_pallas(
            feat, rois, pooled, spatial_scale, sample_ratio,
            valid_hw=valid_hw,
        )
    if kernel == "stream":
        from mx_rcnn_tpu.ops.pallas.roi_align_stream import roi_align_stream

        return roi_align_stream(
            feat, rois, pooled, spatial_scale, sample_ratio, span=span
        )
    if mode == "roi_pool" and roi_pool_kernel(feat, pooled):
        from mx_rcnn_tpu.ops.pallas.roi_pool import roi_pool_pallas

        return roi_pool_pallas(
            feat, rois, pooled, spatial_scale, valid_hw=valid_hw
        )
    if mode == "roi_pool" and not fwd_only:
        # The differentiated pooling where no TPU is (or a map over the
        # kernels' VMEM bound).  SEQUENTIAL over the batch: under vmap
        # the chunked masked-max's remat body is batched, and its live
        # intermediate with it; lax.map keeps one image's chunk live at
        # a time.  Forward-only graphs (eval) fall through to the
        # batch-parallel vmap below.
        if valid_hw is None:
            return jax.lax.map(
                lambda fr: extract_roi_features(
                    fr[0], fr[1], mode, pooled, spatial_scale, sample_ratio
                ),
                (feat, rois),
            )
        return jax.lax.map(
            lambda fr: extract_roi_features(
                fr[0], fr[1], mode, pooled, spatial_scale, sample_ratio,
                valid_hw=fr[2],
            ),
            (feat, rois, valid_hw),
        )
    if valid_hw is None:
        return jax.vmap(
            lambda f, r: extract_roi_features(
                f, r, mode, pooled, spatial_scale, sample_ratio
            )
        )(feat, rois)
    return jax.vmap(
        lambda f, r, v: extract_roi_features(
            f, r, mode, pooled, spatial_scale, sample_ratio, valid_hw=v
        )
    )(feat, rois, valid_hw)
