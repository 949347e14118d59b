"""Streaming Pallas ROIAlign for feature maps too large for VMEM (FPN P2).

The resident kernel (``ops/pallas/roi_align.py``) keeps one (H, W, cblk)
feature slab in VMEM across the roi sweep — impossible for FPN's P2 at
flagship resolution (152×256×128 f32 ≈ 20 MB).  Until round 3 those
shapes silently fell back to the chunked-gather path (VERDICT r3 #3).

This kernel STREAMS the feature map through VMEM in row blocks instead:

- forward: grid (B, C-blocks, roi-blocks, H-blocks); a VMEM scratch
  accumulator holds the roi-block's (rblk, PH, PW, cblk) outputs while
  row blocks stream past; each roi adds ``My[:, rows] @ F @ Mxᵀ`` for
  the rows it intersects.  Whether a roi touches the row block is decided
  from scalars alone (``_row_extent``), before either matrix is built, so
  compute scales with roi extent, not map height.  HBM feature traffic
  is (R/rblk)× the map per channel block — independent of R's 512.
- backward: grid (B, C-blocks, H-blocks, roi-blocks); the (hblk, W,
  cblk) dfeat block stays resident while roi-blocks of cotangents
  stream past, accumulating ``My[:, rows]ᵀ @ g @ Mx``.

With a ``span`` (``[start, count]`` an image, a second scalar-prefetch
operand; ``models/fpn.py::pool_levels`` passes each pyramid level its
own rois' range in a list sorted by level) both kernels visit the rois
``start <= r < start + count`` only: the roi loop of a grid step runs over
the span's intersection with its roi block (dynamic ``fori_loop``
bounds), and a grid step whose intersection is empty is DEAD — it
computes nothing, and the index map of the block it reads (forward: the
feature row block; backward: the cotangent roi block) stays where the
neighbouring live step has or wants it, so nothing is fetched either.
Two things still happen on dead steps: the backward zeroes its output
block at the first roi block (an image with an empty span returns a zero
map), and the forward's output block of a dead roi block is written back
as whatever the buffer held — rows outside the span are UNDEFINED and the
caller selects them away.  Without a span (``None``) the pair has one
scalar operand and a static loop over every roi, fillers skipped by their
inverted boxes.

Same bilinear semantics as the resident kernel (shared interpolation
helpers; the row-restricted matrices are the same one-hot construction
with a global row offset, so rows outside the block simply get zero
weight).  Validated against the gather reference in interpret mode by
``tests/test_pallas_roi_align.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.pallas import out_struct
from mx_rcnn_tpu.ops.pallas.roi_align import _interp_matrix, _sample_coords


def _interp_matrix_rows(lo_f, whi, offset, hblk: int, nbins: int, s: int):
    """Row-restricted one-hot interpolation matrix (nbins, hblk): global
    row index = offset + local iota; sample points outside the block get
    zero weight automatically (their lo/hi never match)."""
    n = nbins * s
    cell = jax.lax.broadcasted_iota(jnp.int32, (n, hblk), 1).astype(
        jnp.float32
    ) + offset
    lo = lo_f.reshape(n, 1)
    w1 = whi.reshape(n, 1)
    # hi = lo + 1 capped at the LAST GLOBAL row (size-1), matching
    # _interp_matrix; the cap index is threaded via the caller's clip
    m = jnp.where(cell == lo, 1.0 - w1, 0.0) + jnp.where(
        cell == lo + 1.0, w1, 0.0
    )
    return m.reshape(nbins, s, hblk).sum(axis=1) * (1.0 / s)


def _scaled_roi(rois_ref, b, r, scale: float):
    """(x1, y1, x2, y2) of roi ``r`` of image ``b`` in feature cells."""
    return tuple(rois_ref[b, k, r] * scale for k in range(4))


def _row_extent(rois_ref, b, r, hf: int, scale: float):
    """(valid, lo_cell, hi_cell) of one roi, from scalars alone: whether
    it is a real box, and the conservative GLOBAL row extent of its sample
    support — the kernels' block-skip predicate, decided before any
    matrix is built.  Sample points live in
    [clip(y1), clip(y1 + max(y2-y1, 1))] (the min-length clamp in
    _sample_coords means a degenerate roi still reaches ~y1+1, NOT y2!),
    and each contributes to rows floor(g) and floor(g)+1; clamping into
    [0, hf-1] keeps fully-offscreen rois pointing at the edge rows their
    clipped samples actually hit."""
    x1, y1, x2, y2 = _scaled_roi(rois_ref, b, r, scale)
    valid = x2 >= x1  # inverted boxes are _pad_rois fillers
    lo_cell = jnp.clip(jnp.floor(y1), 0.0, float(hf - 1))
    hi_cell = jnp.clip(
        jnp.floor(y1 + jnp.maximum(y2 - y1, 1.0)) + 1.0, 0.0, float(hf - 1)
    )
    return valid, lo_cell, hi_cell


def _row_matrices(rois_ref, b, r, hf: int, wf: int, offset, hblk: int,
                  pooled, s: int, scale: float):
    """(My_sub (PH, hblk), Mx (PW, W)) for one roi and one row block.

    The hi=lo+1 cap at size-1 is folded into the coords: a sample with
    lo == size-1 gets whi forced to 0 so all its weight lands on lo —
    identical to the resident kernel's ``min(lo+1, size-1)`` + both
    one-hot terms colliding on the same cell.
    """
    ph, pw = pooled
    x1, y1, x2, y2 = _scaled_roi(rois_ref, b, r, scale)
    ylo, ywhi = _sample_coords(y1, y2, float(hf - 1), ph, s)
    xlo, xwhi = _sample_coords(x1, x2, float(wf - 1), pw, s)
    # cap: when lo is the last row/col, send the hi-weight to lo as well
    # (resident kernel achieves this because lo==hi makes both one-hot
    # terms hit the same cell; here lo+1 would fall outside)
    ywhi = jnp.where(ylo == float(hf - 1), 0.0, ywhi)
    xwhi = jnp.where(xlo == float(wf - 1), 0.0, xwhi)
    my = _interp_matrix_rows(ylo, ywhi, offset, hblk, ph, s)     # (PH, hblk)
    mx = _interp_matrix(xlo, xwhi, wf, float(wf - 1), pw, s)    # (PW, W)
    return my, mx


def _own_range(span_ref, b, rb, rblk: int):
    """[lo, hi) within roi block ``rb`` of image ``b`` that the image's
    span ``[start, start + count)`` covers; empty (hi <= lo) where the
    block holds none of the span's rois.  Scalars, so the kernels' loops
    and the index maps decide by the same rule."""
    start = span_ref[0, b]
    stop = start + span_ref[1, b]
    first = rb * rblk
    return jnp.maximum(start - first, 0), jnp.minimum(stop - first, rblk)


def live_roi_blocks(span, n_rois: int, pooled, channels: int):
    """(B, 2) spans → (B, n_rblk) bool: the roi blocks of each image's
    grid that hold a roi of its span — the kernels' own rule
    (:func:`_own_range`) outside them, for the step's counters: on the
    other (roi block, image) pairs every grid step is dead."""
    rblk = _pick_rblk(pooled, _cblk(channels))
    images = jnp.arange(span.shape[0])[:, None]
    blocks = jnp.arange(-(-n_rois // rblk))[None]
    lo, hi = _own_range(span.T, images, blocks, rblk)
    return hi > lo


def _block_range(span_ref, b, rb, rblk: int):
    """The roi loop's bounds within roi block ``rb``: the whole block
    (Python ints: a static loop) without a span, the span's part of it
    (scalars: dynamic bounds, possibly empty) with one."""
    if span_ref is None:
        return 0, rblk
    return _own_range(span_ref, b, rb, rblk)


def _roi_loop(rois_ref, b, rb, lo, hi, rblk, hf, scale, offset, hblk, visit):
    """``visit(i, r)`` for every roi ``r = rb*rblk + i``, ``lo <= i < hi``,
    that is a real box and whose rows reach the row block at ``offset``."""
    def body(i, _):
        r = rb * rblk + i
        valid, lo_cell, hi_cell = _row_extent(rois_ref, b, r, hf, scale)

        @pl.when(valid & (hi_cell >= offset) & (lo_cell <= offset + (hblk - 1)))
        def _():
            visit(i, r)

        return 0

    jax.lax.fori_loop(lo, hi, body, 0)


def _fwd_kernel(rois_ref, *refs, pooled, s, scale, hblk, n_hblk, rblk, hf):
    """``refs``: ``[span_ref,] feat_ref, out_ref, acc_ref``."""
    *span, feat_ref, out_ref, acc_ref = refs  # the span only where given
    span_ref = span[0] if span else None
    b = pl.program_id(0)
    rb = pl.program_id(2)
    hb = pl.program_id(3)
    wf = feat_ref.shape[2]
    offset = hb * hblk  # int; promotes against the f32 iota/extents
    lo, hi = _block_range(span_ref, b, rb, rblk)

    def step():
        @pl.when(hb == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        feat = feat_ref[0]                                       # (hblk, W, CB)
        # rows past H in the (padded) last block hold uninitialized memory;
        # their interpolation weight is zero, but 0·NaN/Inf would still
        # poison the matmul accumulator — mask them to real zeros
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (hblk, 1, 1), 0) + offset
        feat = jnp.where(row_ids < hf, feat, jnp.zeros_like(feat))
        f32 = feat.dtype != jnp.bfloat16

        def visit(i, r):
            my, mx = _row_matrices(
                rois_ref, b, r, hf, wf, offset, hblk, pooled, s, scale
            )
            if f32:
                rows = jax.lax.dot_general(
                    my, feat.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
                out = jax.lax.dot_general(
                    mx, rows, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )                                                # (PW, PH, CB)
            else:
                rows = jax.lax.dot_general(
                    my.astype(jnp.bfloat16), feat, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.bfloat16)
                out = jax.lax.dot_general(
                    mx.astype(jnp.bfloat16), rows, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            # TRANSPOSED accumulator (PW, PH, CB) — the second dot's
            # natural order; one transpose at the flush replaces
            # R×n_hblk in-kernel transposes (the resident backward
            # measured that pattern at 35 ms)
            acc_ref[i] = acc_ref[i] + out

        _roi_loop(rois_ref, b, rb, lo, hi, rblk, hf, scale, offset, hblk,
                  visit)

        @pl.when(hb == n_hblk - 1)
        def _():
            out_ref[0] = acc_ref[...].transpose(0, 2, 1, 3).astype(out_ref.dtype)

    if span_ref is None:
        step()
    else:
        # a roi block outside the span is a dead step: no zeroing, no
        # flush — its output block keeps whatever the buffer held, rows
        # the caller must not read (pool_levels selects them away)
        pl.when(hi > lo)(step)


def _bwd_kernel(rois_ref, *refs, pooled, s, scale, hblk, rblk, hf):
    """``refs``: ``[span_ref,] g_ref, dfeat_ref``."""
    *span, g_ref, dfeat_ref = refs
    span_ref = span[0] if span else None
    b = pl.program_id(0)
    hb = pl.program_id(2)
    rb = pl.program_id(3)
    wf = dfeat_ref.shape[2]
    offset = hb * hblk

    # on dead steps too: an image whose span is empty returns a zero map
    @pl.when(rb == 0)
    def _():
        dfeat_ref[...] = jnp.zeros_like(dfeat_ref)

    # mirror the resident backward's precision contract: f32 cotangents
    # (COMPUTE_DTYPE=float32 runs) keep HIGHEST (~1e-5 gradients), bf16
    # training graphs take default MXU passes
    prec = (
        jax.lax.Precision.HIGHEST
        if g_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )

    def visit(i, r):
        my, mx = _row_matrices(
            rois_ref, b, r, hf, wf, offset, hblk, pooled, s, scale
        )
        g = g_ref[0, i].astype(jnp.float32)                      # (PH, PW, CB)
        # t: (W, PH, CB) = Mxᵀ contract PW;  d: (hblk, W, CB)
        t = jax.lax.dot_general(
            mx, g, (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        d = jax.lax.dot_general(
            my, t, (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )                                                        # (hblk, W, CB)
        dfeat_ref[0] = dfeat_ref[0] + d

    # with a span a dead step's loop is empty: nothing else to skip
    lo, hi = _block_range(span_ref, b, rb, rblk)
    _roi_loop(rois_ref, b, rb, lo, hi, rblk, hf, scale, offset, hblk, visit)


# Both kernels' blocks are sized by budget (_pick_hblk: 2 MiB of feature
# rows; _pick_rblk: 4 MiB of roi-block accumulator), so what a grid step
# holds barely depends on the map: the chip's compiler allocates 8-22 MiB
# across P2/P3 at VOC and COCO canvases, f32 and bf16, 7x7 and 14x14
# (double-buffered blocks + the scratch accumulator + Mosaic's own
# temporaries; f32 forward is the top of the range — ISSUE 21).  That is
# over Mosaic's default 16 MiB scoped limit, so the kernels state theirs.
_VMEM_LIMIT = 32 * 2**20


def _cblk(c: int) -> int:
    return 128 if c % 128 == 0 else c


def _pick_hblk(w: int, cblk: int, budget: int = 2 * 2**20) -> int:
    h = budget // (w * cblk * 4)
    return max(8, (h // 8) * 8)


def _pick_rblk(pooled, cblk: int, budget: int = 4 * 2**20) -> int:
    """roi-block size bounded by the f32 scratch accumulator's VMEM
    footprint — (rblk, ph, pw, cblk) must fit ``budget`` at any pooled
    size (the 14×14 mask head quadruples the 7×7 box head's area)."""
    r = budget // (pooled[0] * pooled[1] * cblk * 4)
    return max(8, min(128, (r // 8) * 8))


def _pad_rois(rois, rblk):
    b, r, _ = rois.shape
    pad = (-r) % rblk
    if pad:
        # inverted (x2 < x1) filler rois: the kernels' validity term in
        # the block-skip predicate drops them entirely, so padding costs
        # no MXU work (their rows would otherwise clip into block 0)
        filler = jnp.tile(
            jnp.asarray([0.0, 0.0, -1.0, -1.0], rois.dtype), (b, pad, 1)
        )
        rois = jnp.concatenate([rois, filler], axis=1)
    return rois, r


def _scalar_prefetch(rois, span, rblk):
    """→ (the kernels' scalar-prefetch operands, true R): the padded rois
    as (B, 4, Rp) f32 and, with a ``span`` (B, 2), its (2, B) int32
    ``[start, count]`` rows."""
    rois_p, r_true = _pad_rois(rois, rblk)
    rois_t = rois_p.astype(jnp.float32).transpose(0, 2, 1)
    if span is None:
        return (rois_t,), r_true
    return (rois_t, span.astype(jnp.int32).T), r_true


def _fwd_impl(feat, rois, span, pooled, scale, s, interpret):
    b, hf, wf, c = feat.shape
    cblk = _cblk(c)
    rblk = _pick_rblk(pooled, cblk)
    prefetch, r_true = _scalar_prefetch(rois, span, rblk)
    r = prefetch[0].shape[2]
    hblk = _pick_hblk(wf, cblk)
    n_hblk = -(-hf // hblk)
    grid = (b, c // cblk, r // rblk, n_hblk)
    kernel = partial(
        _fwd_kernel, pooled=pooled, s=s, scale=scale, hblk=hblk,
        n_hblk=n_hblk, rblk=rblk, hf=hf,
    )

    def feat_block(bb, cb, rb, hb, rois_ref, span_ref=None):
        if span_ref is not None:
            # a dead step fetches nothing: before the span it waits on the
            # row block the first live step needs, after it it stays on
            # the last one fetched
            lo, hi = _own_range(span_ref, bb, rb, rblk)
            hb = jnp.where(hi > lo, hb, jnp.where(hi <= 0, n_hblk - 1, 0))
        return bb, hb, 0, cb

    out = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[pl.BlockSpec((1, hblk, wf, cblk), feat_block)],
            out_specs=pl.BlockSpec(
                (1, rblk, pooled[0], pooled[1], cblk),
                lambda bb, cb, rb, hb, *prefetch_refs: (bb, rb, 0, 0, cb),
            ),
            scratch_shapes=[
                # transposed (PW, PH) layout — see the kernel's flush
                pltpu.VMEM((rblk, pooled[1], pooled[0], cblk), jnp.float32)
            ],
        ),
        out_shape=out_struct(
            (b, r, pooled[0], pooled[1], c), feat.dtype, *prefetch, feat
        ),
        interpret=interpret,
        name="pallas_roi_features_stream_fwd",
    )(*prefetch, feat)
    return out[:, :r_true]


def _bwd_impl(feat_shape, feat_dtype, rois, span, g, pooled, scale, s,
              interpret):
    b, hf, wf, c = feat_shape
    cblk = _cblk(c)
    rblk = _pick_rblk(pooled, cblk)
    prefetch, _ = _scalar_prefetch(rois, span, rblk)
    r = prefetch[0].shape[2]
    if r != g.shape[1]:
        g = jnp.concatenate(
            [g, jnp.zeros((b, r - g.shape[1]) + g.shape[2:], g.dtype)], axis=1
        )
    hblk = _pick_hblk(wf, cblk)
    n_hblk = -(-hf // hblk)
    n_rblk = r // rblk
    grid = (b, c // cblk, n_hblk, n_rblk)
    kernel = partial(
        _bwd_kernel, pooled=pooled, s=s, scale=scale, hblk=hblk,
        rblk=rblk, hf=hf,
    )

    def g_block(bb, cb, hb, rb, rois_ref, span_ref=None):
        if span_ref is not None:
            # a dead step fetches nothing: the cotangent block stays on the
            # span's nearest roi block (the first one ahead of the span,
            # the last one fetched behind it)
            start = span_ref[0, bb]
            last = jnp.maximum(start + span_ref[1, bb] - 1, start)
            rb = jnp.clip(
                rb,
                jnp.minimum(jax.lax.div(start, rblk), n_rblk - 1),
                jax.lax.div(last, rblk),
            )
        return bb, rb, 0, 0, cb

    out = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rblk, pooled[0], pooled[1], cblk), g_block)
            ],
            out_specs=pl.BlockSpec(
                (1, hblk, wf, cblk),
                lambda bb, cb, hb, rb, *prefetch_refs: (bb, hb, 0, cb),
            ),
        ),
        out_shape=out_struct((b, hf, wf, c), jnp.float32, *prefetch, g),
        interpret=interpret,
        name="pallas_roi_features_stream_bwd",
    )(*prefetch, g)
    return out.astype(feat_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def roi_align_stream(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    pooled: tuple = (7, 7),
    spatial_scale: float = 0.25,
    sample_ratio: int = 2,
    interpret: bool = False,
    span=None,
) -> jnp.ndarray:
    """(B, H, W, C) × (B, R, 4) → (B, R, ph, pw, C); the streaming twin
    of ``roi_align_pallas`` for maps over the VMEM budget.

    ``span`` (B, 2) int32 = ``[start, count]`` an image: only the rois
    ``start <= r < start + count`` are pooled (and only they receive the
    cotangent's contribution to the map); the rows of every other roi are
    UNDEFINED and the caller must select them away (``jnp.where``, not a
    product: they may hold NaN).  ``None`` pools every roi."""
    return _fwd_impl(
        feat, rois, span, pooled, spatial_scale, sample_ratio, interpret
    )


def _vjp_fwd(feat, rois, pooled, spatial_scale, sample_ratio, interpret,
             span=None):
    out = _fwd_impl(
        feat, rois, span, pooled, spatial_scale, sample_ratio, interpret
    )
    return out, (feat, rois, span)


def _vjp_bwd(pooled, spatial_scale, sample_ratio, interpret, res, g):
    feat, rois, span = res
    dfeat = _bwd_impl(
        feat.shape, feat.dtype, rois, span, g, pooled, spatial_scale,
        sample_ratio, interpret,
    )
    dspan = None if span is None else np.zeros(span.shape, jax.dtypes.float0)
    return dfeat, jnp.zeros_like(rois), dspan


roi_align_stream.defvjp(_vjp_fwd, _vjp_bwd)
