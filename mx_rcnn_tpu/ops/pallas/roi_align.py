"""Pallas TPU ROIAlign — bilinear pooling as one-hot interpolation matmuls.

Reference: MXNet's ``roi_pooling.cu`` / torchvision ``roi_align.cu``
(SURVEY N6) — CUDA kernels that gather 4 neighbours per sample point and
scatter-add bilinear weights in the backward pass.  Gather/scatter is the
wrong shape for a TPU; this kernel reformulates ROIAlign as dense matrix
algebra that rides the MXU:

- Bilinear sampling is **separable**: the weight of cell (h, w) for sample
  point (gy, gx) factors into wy(h)·wx(w), and the s×s-sample average per
  output bin factors into (mean of row weights)·(mean of col weights).
- So per roi, pooling is exactly ``out = My @ feat @ Mxᵀ`` with
  My (PH, H) and Mx (PW, W) tiny interpolation matrices built on-chip
  from iota comparisons — two MXU contractions, zero gathers.
- Backward is the transpose pair ``dfeat += Myᵀ @ g @ Mx`` — again
  matmuls, accumulated across rois in a VMEM-resident block; no
  scatter-add (the CUDA kernel's atomics have no TPU analog).

Grid: (B, C-blocks, R) with roi boxes scalar-prefetched to SMEM; the
feature block stays resident in VMEM across the entire roi sweep, so HBM
traffic is feat×(C/CBLK reads) + out, independent of R.

Exactness: same edge semantics as ``ops.roi_align.roi_align`` (clip to
[0, lim-1], hi=lo+1 capped at lim-1, roi w/h floored at 1) — validated
against it in interpret mode by ``tests/test_pallas_roi_align.py``.
``lim`` is the canvas extent, a Python constant, when ``valid_hw`` is
None (training: one scalar-prefetch operand, the rois); with ``valid_hw``
it is ``ops.roi_align._feat_limits``' per-image valid extent, handed to
the same kernel body as a second scalar-prefetch operand (serving and
eval: pooled features independent of the shape bucket, SERVING.md
"Padding invariance").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.pallas import out_struct
from mx_rcnn_tpu.ops.roi_align import _feat_limits


def _interp_matrix(lo_f, whi, size: int, last, nbins: int, s: int):
    """Mean-of-samples one-hot interpolation matrix (nbins, size).

    ``lo_f``/``whi`` are (nbins*s,) f32 vectors of floor indices and
    hi-weights for each sample point; folds the 1/s sample average in.
    ``last``: the last cell a sample may read (see :func:`_sample_coords`).
    """
    n = nbins * s
    # int iota cast to f32: Mosaic's tpu.iota only emits integer vectors
    cell = jax.lax.broadcasted_iota(jnp.int32, (n, size), 1).astype(jnp.float32)
    lo = lo_f.reshape(n, 1)
    hi = jnp.minimum(lo + 1.0, last)
    w1 = whi.reshape(n, 1)
    m = jnp.where(cell == lo, 1.0 - w1, 0.0) + jnp.where(cell == hi, w1, 0.0)
    # average the s sample rows of each bin
    return m.reshape(nbins, s, size).sum(axis=1) * (1.0 / s)


def _sample_coords(c1, c2, last, nbins: int, s: int):
    """Sample-point floors/weights along one axis for one roi.

    c1/c2: scaled roi edges (scalars); ``last`` = limit − 1, the last
    cell a sample may read: a Python float (the canvas) or an SMEM scalar
    (the image's valid extent).  Returns (lo_f (nbins*s,), whi)."""
    length = jnp.maximum(c2 - c1, 1.0)
    bin_sz = length / nbins
    i = jax.lax.broadcasted_iota(jnp.int32, (nbins * s, 1), 0).astype(jnp.float32)
    g = c1 + (i + 0.5) / s * bin_sz                                  # (n, 1)
    g = jnp.clip(g, 0.0, last)
    lo_f = jnp.floor(g)
    return lo_f, g - lo_f


def _matrices_for_roi(rois_ref, lims_ref, b, r, hf: int, wf: int, pooled,
                      s: int, scale: float):
    """``rois_ref`` is scalar-prefetched SMEM in (B, 4, R) layout — the
    coordinate dim must NOT be minor: SMEM pads the minor dim to 128
    lanes, so (B, R, 4) would blow up 32× and overflow the 1 MB SMEM at
    eval roi counts (B=8, R=300 → 1.2 MB).  ``lims_ref``: None (clamp to
    the canvas) or the (2, B) per-image (rows, cols) limits, B minor for
    the same reason."""
    ph, pw = pooled
    if lims_ref is None:
        last_y, last_x = float(hf - 1), float(wf - 1)
    else:
        last_y, last_x = lims_ref[0, b] - 1.0, lims_ref[1, b] - 1.0
    x1 = rois_ref[b, 0, r] * scale
    y1 = rois_ref[b, 1, r] * scale
    x2 = rois_ref[b, 2, r] * scale
    y2 = rois_ref[b, 3, r] * scale
    ylo, ywhi = _sample_coords(y1, y2, last_y, ph, s)
    xlo, xwhi = _sample_coords(x1, x2, last_x, pw, s)
    my = _interp_matrix(ylo, ywhi, hf, last_y, ph, s)                # (PH, H)
    mx = _interp_matrix(xlo, xwhi, wf, last_x, pw, s)                # (PW, W)
    return my, mx


def _fwd_kernel(rois_ref, *refs, pooled, s, scale, rblk):
    """Blocked forward: RBLK rois per grid step.  ``refs`` is
    ``[lims_ref,] feat_ref, out_ref``.

    The W-contraction (the majority of the flops — W ≥ H in every
    landscape bucket) runs once on a STACKED (RBLK·PW, W) interpolation
    matrix: M=112 rows at the default rblk=8/pw=14 instead of 14, so the
    MXU's 128-row tiles are ~90% occupied instead of ~11%.  The
    H-contraction needs a different My per roi on the non-contracted
    side, so it stays per-roi; putting the SHORTER spatial axis (H) on
    the per-roi side minimizes that tail, and its (PH, H)@(H, PW, CB)
    form emits (PH, PW, CB) directly — no in-kernel transpose.  Blocking
    the per-roi side would need a block-diagonal My whose 7/8 zero flops
    exactly cancel the utilization win."""
    *lims, feat_ref, out_ref = refs  # the limits only with ``valid_hw``
    lims_ref = lims[0] if lims else None
    b, rb = pl.program_id(0), pl.program_id(2)
    hf, wf = feat_ref.shape[1], feat_ref.shape[2]
    _, pw = pooled  # only PW shapes the stacked contraction below
    mys, mxs = [], []
    for k in range(rblk):
        my, mx = _matrices_for_roi(
            rois_ref, lims_ref, b, rb * rblk + k, hf, wf, pooled, s, scale
        )
        mys.append(my)
        mxs.append(mx)
    mx_blk = jnp.concatenate(mxs, axis=0)                            # (RB*PW, W)
    feat = feat_ref[0]                                               # (H, W, CB)
    # Precision follows the graph's dtype: a bf16 training graph gets
    # single-pass bf16 dots with f32 accumulation (the same contract as
    # every conv around it); an f32 graph (eval parity) keeps 6-pass
    # HIGHEST — there the kernel must match the gather reference to
    # ~1e-5, not ~1e-3.
    if feat.dtype == jnp.bfloat16:
        prec = jax.lax.Precision.DEFAULT
        mx_blk = mx_blk.astype(jnp.bfloat16)
        mys = [m.astype(jnp.bfloat16) for m in mys]
    else:
        prec = jax.lax.Precision.HIGHEST
        feat = feat.astype(jnp.float32)

    # W first on the stacked matrix, H per-roi: the per-roi tail then
    # contracts the SHORTER axis (H) and emits (PH, PW, CB) directly —
    # no in-kernel transpose.  (A bf16 preferred_element_type would drop
    # the f32 cols buffer and fit cblk=512, but tpu.matmul requires a
    # 32-bit accumulator — Mosaic rejects it at lowering.)
    cols = jax.lax.dot_general(
        mx_blk, feat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )                                                                # (RB*PW, H, CB)
    if feat.dtype == jnp.bfloat16:
        cols = cols.astype(jnp.bfloat16)
    for k in range(rblk):
        out_k = jax.lax.dot_general(
            mys[k], cols[k * pw:(k + 1) * pw],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )                                                            # (PH, PW, CB)
        # per-roi sub-block stores: a single jnp.stack write measured
        # 3% SLOWER end-to-end (the stack materializes a VMEM concat)
        out_ref[0, k] = out_k.astype(out_ref.dtype)


def _bwd_kernel(rois_ref, *refs, pooled, s, scale, rblk):
    """Blocked backward: RBLK rois per grid step.  ``refs`` is
    ``[lims_ref,] g_ref, dfeat_ref``.

    dfeat is accumulated across the roi-block sweep in f32 (the
    out_shape is forced f32 regardless of feat dtype — sequential bf16
    adds would swallow small per-roi contributions); cast back outside
    the kernel.

    d = Σ_k Mxᵀ_k @ (Myᵀ_k @ g_k) restructured so the roi sum rides the
    contraction: the per-roi half (t_k = Myᵀ_k @ g_k, K=PH=14) stays
    small, but the second half stacks t_k into (W, RB·PW, CB)-shaped U
    and contracts K=RB·PW=112 against the stacked Mx — one matmul sums
    all RBLK rois, with ~90% K-tile occupancy instead of ~11% and 8×
    fewer accumulator read-modify-writes.

    Two deliberate asymmetries vs the forward kernel: the accumulator is
    laid out TRANSPOSED, (W, H, CB) — the stacked dot emits that order,
    and one XLA transpose of the final (B, W, H, C) outside the kernel
    replaces per-step in-kernel transposes (measured 35 ms → a few ms on
    the flagship step).  Precision mirrors the forward's dtype branch:
    bf16 cotangents (the bf16 training graph) take default MXU passes —
    6-pass HIGHEST buys nothing the rest of that backward has — while
    f32 cotangents (COMPUTE_DTYPE=float32 runs) keep HIGHEST so
    gradients round at ~1e-5, not bf16-mantissa ~1e-3."""
    *lims, g_ref, dfeat_ref = refs
    lims_ref = lims[0] if lims else None
    b, rb = pl.program_id(0), pl.program_id(2)
    wf, hf = dfeat_ref.shape[1], dfeat_ref.shape[2]
    ph, pw = pooled
    prec = (
        jax.lax.Precision.HIGHEST
        if g_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    ts, mxs = [], []
    for k in range(rblk):
        my, mx = _matrices_for_roi(
            rois_ref, lims_ref, b, rb * rblk + k, hf, wf, pooled, s, scale
        )
        g = g_ref[0, k].astype(jnp.float32)                          # (PH, PW, CB)
        # t_k: (H, PW, CB) = Myᵀ_k contract PH
        ts.append(jax.lax.dot_general(
            my, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ))
        mxs.append(mx)
    mx_blk = jnp.concatenate(mxs, axis=0)                            # (RB*PW, W)
    t_blk = jnp.concatenate(ts, axis=1)                              # (H, RB*PW, CB)
    # d: (W, H, CB) = stacked Mxᵀ contract RB·PW — sums the roi block
    d = jax.lax.dot_general(
        mx_blk, t_blk, (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )

    @pl.when(rb == 0)
    def _():
        dfeat_ref[0] = d

    @pl.when(rb > 0)
    def _():
        dfeat_ref[0] = dfeat_ref[0] + d


def _cblk(c: int, largest: int = 512) -> int:
    for blk in (512, 256, 128):
        if blk <= largest and c % blk == 0:
            return blk
    return c


_RBLK = 8  # rois per grid step; M/K tiles go 14 → 112 of the MXU's 128

# What one grid step may hold in VMEM, and what the kernels tell Mosaic
# they need (``vmem_limit_bytes``): Mosaic's own default scoped limit is
# 16 MiB of the v5e's 128 MiB, and the f32 backward at the flagship C4 map
# (17.5 MiB) and at FPN P3 (16.0 MiB) does not fit it.  24 MiB keeps
# resident exactly the maps the hardware rounds ran resident (C4 in both
# orientations, P3-P5 at 7x7) and keeps P3 at 14x14 and P2 on the
# streaming kernel.
_VMEM_BUDGET = 24 * 2**20


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(esize: int) -> int:
    """Rows of one (sublane, 128-lane) tile: 8 of 32-bit, 16 of bf16 —
    the second-minor dim of every VMEM block pads to it."""
    return 8 * (4 // esize)


def _fwd_bytes(h: int, w: int, blk: int, esize: int, pooled) -> int:
    """Upper bound on the scoped VMEM Mosaic allocates for one forward
    grid step, calibrated against the chip's compiler (its refusals name
    the size; 32 configurations: C4 landscape/portrait, P3, 38x50; f32
    and bf16; cblk 128/256; 7x7 and 14x14 — ISSUE 21).

    Exact part: the pipeline DOUBLE-buffers every block, the resident
    (H, W, blk) feature slab included, and pads second-minor dims to
    the sublane tile.  Bounded part: Mosaic's internal scratch (the f32
    stacked ``cols`` intermediate, operand relayouts, HIGHEST-precision
    splits) measured 1.0-2.6x ``cols``."""
    ph, pw = pooled
    sub = _sublanes(esize)
    feat = h * _pad(w, sub) * blk * esize
    out = _RBLK * ph * _pad(pw, sub) * blk * esize
    cols = _RBLK * pw * _pad(h, 8) * blk * 4
    return 2 * (feat + out) + 26 * cols // 10


def _bwd_bytes(h: int, w: int, blk: int, gsize: int, pooled) -> int:
    """Backward twin of :func:`_fwd_bytes` (same calibration): the
    double-buffered f32 (W, H, blk) accumulator and cotangent block,
    plus internal scratch measured 0.7-1.6x (stacked ``t_blk`` + the
    ``d`` product).  ``gsize``: cotangent dtype bytes."""
    ph, pw = pooled
    acc = w * _pad(h, 8) * blk * 4
    g = _RBLK * ph * _pad(pw, _sublanes(gsize)) * blk * gsize
    t_blk = h * _RBLK * pw * blk * 4
    return 2 * (acc + g) + 16 * (t_blk + acc) // 10


def fits_vmem(h: int, w: int, c: int, pooled, esize: int) -> bool:
    """True iff the resident kernels hold this map within budget at
    their smallest channel block, forward AND backward — so a map
    dispatched resident never fails to compile in its grad.  ``esize``:
    feature dtype bytes."""
    blk = _cblk(c, largest=128)
    return max(
        _fwd_bytes(h, w, blk, esize, pooled),
        _bwd_bytes(h, w, blk, esize, pooled),
    ) <= _VMEM_BUDGET


def _cblk_fit(step_bytes, c: int, largest: int = 256) -> int:
    """Largest channel block whose per-step VMEM (``step_bytes(blk)``)
    fits the budget.  Capped at 256, the largest the hardware rounds
    ran; whether 512 pays where it fits is not measured."""
    blk = _cblk(c, largest)
    while blk > 128 and step_bytes(blk) > _VMEM_BUDGET:
        blk //= 2
    return blk


def _compiler_params(step_bytes: int, semantics) -> pltpu.CompilerParams:
    # a quarter above the bound: the limit is a cap, not an allocation,
    # and maps the calibration never saw should compile too
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=step_bytes * 5 // 4,
    )


def _pad_rois(rois, rblk):
    """(B, R, 4) → ((B, 4, Rp) SMEM layout, Rp) with R padded to rblk.

    Pad rois are all-zero boxes — degenerate but numerically safe
    (length floors at 1 in _sample_coords), and their outputs are
    sliced away / their cotangents are structurally zero."""
    r = rois.shape[1]
    rp = _pad(r, rblk)
    rois_t = rois.astype(jnp.float32).transpose(0, 2, 1)
    if rp != r:
        rois_t = jnp.pad(rois_t, ((0, 0), (0, 0), (0, rp - r)))
    return rois_t, rp


def _scalar_prefetch(rois, valid_hw, feat_hw, scale):
    """→ (the kernels' scalar-prefetch operands, Rp): the padded rois
    and, with ``valid_hw`` (B, 2), the (2, B) f32 per-image limits of
    ``ops.roi_align._feat_limits`` — the gather path's own."""
    rois_t, rp = _pad_rois(rois, _RBLK)
    if valid_hw is None:
        return (rois_t,), rp
    lims = _feat_limits(feat_hw, (valid_hw[:, 0], valid_hw[:, 1]), scale)
    return (rois_t, jnp.stack([lim for lim, _ in lims])), rp


def _roi_align_fwd_impl(feat, rois, valid_hw, pooled, scale, s, interpret):
    b, hf, wf, c = feat.shape
    r = rois.shape[1]
    esize = feat.dtype.itemsize
    cblk = _cblk_fit(lambda blk: _fwd_bytes(hf, wf, blk, esize, pooled), c)
    prefetch, rp = _scalar_prefetch(rois, valid_hw, (hf, wf), scale)
    grid = (b, c // cblk, rp // _RBLK)
    kernel = partial(_fwd_kernel, pooled=pooled, s=s, scale=scale, rblk=_RBLK)
    out = pl.pallas_call(
        kernel,
        # every fwd grid step writes a disjoint out block — declaring all
        # three axes parallel lets Mosaic pipeline/overlap grid steps
        compiler_params=_compiler_params(
            _fwd_bytes(hf, wf, cblk, esize, pooled),
            ("parallel", "parallel", "parallel"),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, hf, wf, cblk),
                    lambda bb, cb, rr, *prefetch_refs: (bb, 0, 0, cb),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, _RBLK, pooled[0], pooled[1], cblk),
                lambda bb, cb, rr, *prefetch_refs: (bb, rr, 0, 0, cb),
            ),
        ),
        out_shape=out_struct(
            (b, rp, pooled[0], pooled[1], c), feat.dtype, prefetch[0], feat
        ),
        interpret=interpret,
        # a device trace names the kernel by this; ``_roi_features`` stays
        # in it because the benchmark's roi_align_roofline finds it so
        name="pallas_roi_features_fwd",
    )(*prefetch, feat)
    return out[:, :r] if rp != r else out


def _roi_align_bwd_impl(feat_shape, feat_dtype, rois, valid_hw, g, pooled,
                        scale, s, interpret):
    b, hf, wf, c = feat_shape
    r = rois.shape[1]
    gsize = g.dtype.itemsize
    cblk = _cblk_fit(lambda blk: _bwd_bytes(hf, wf, blk, gsize, pooled), c)
    prefetch, rp = _scalar_prefetch(rois, valid_hw, (hf, wf), scale)
    if rp != r:
        g = jnp.pad(g, ((0, 0), (0, rp - r)) + ((0, 0),) * (g.ndim - 2))
    grid = (b, c // cblk, rp // _RBLK)
    kernel = partial(_bwd_kernel, pooled=pooled, s=s, scale=scale, rblk=_RBLK)
    out = pl.pallas_call(
        kernel,
        # batch/channel blocks are independent; the roi axis carries the
        # accumulator read-modify-write and must stay sequential
        compiler_params=_compiler_params(
            _bwd_bytes(hf, wf, cblk, gsize, pooled),
            ("parallel", "parallel", "arbitrary"),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, _RBLK, pooled[0], pooled[1], cblk),
                    lambda bb, cb, rr, *prefetch_refs: (bb, rr, 0, 0, cb),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, wf, hf, cblk),
                lambda bb, cb, rr, *prefetch_refs: (bb, 0, 0, cb),
            ),
        ),
        # (B, W, H, C): the kernel accumulates transposed (see docstring)
        out_shape=out_struct((b, wf, hf, c), jnp.float32, prefetch[0], g),
        interpret=interpret,
        name="pallas_roi_features_bwd",
    )(*prefetch, g)
    return out.swapaxes(1, 2).astype(feat_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def roi_align_pallas(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    pooled: tuple = (14, 14),
    spatial_scale: float = 1.0 / 16.0,
    sample_ratio: int = 2,
    interpret: bool = False,
    valid_hw=None,
) -> jnp.ndarray:
    """(B, H, W, C) feature + (B, R, 4) image-coord rois → (B, R, ph, pw, C).

    Batched twin of ``ops.roi_align.roi_align`` backed by the Pallas MXU
    kernel; differentiable in ``feat`` (rois get zero cotangent, matching
    the stop-gradient proposal semantics of the reference's Proposal op).

    ``valid_hw`` (B, 2) = true pre-padding image sizes: samples clamp to
    each image's valid feature extent instead of the canvas, as the gather
    path does under the same argument.  The backward carries the same
    limits (it is the transpose of the forward it belongs to) and gives
    ``valid_hw`` a zero cotangent; today every caller that passes
    ``valid_hw`` is forward-only.
    """
    return _roi_align_fwd_impl(
        feat, rois, valid_hw, pooled, spatial_scale, sample_ratio, interpret
    )


def _vjp_fwd(feat, rois, pooled, spatial_scale, sample_ratio, interpret,
             valid_hw=None):
    out = _roi_align_fwd_impl(
        feat, rois, valid_hw, pooled, spatial_scale, sample_ratio, interpret
    )
    # feat rides along only for its shape/dtype; it is already live as a
    # backbone activation so this costs nothing extra
    return out, (feat, rois, valid_hw)


def _vjp_bwd(pooled, spatial_scale, sample_ratio, interpret, res, g):
    feat, rois, valid_hw = res
    dfeat = _roi_align_bwd_impl(
        feat.shape, feat.dtype, rois, valid_hw, g, pooled, spatial_scale,
        sample_ratio, interpret,
    )
    dvalid = None if valid_hw is None else jnp.zeros_like(valid_hw)
    return dfeat, jnp.zeros_like(rois), dvalid


roi_align_pallas.defvjp(_vjp_fwd, _vjp_bwd)
