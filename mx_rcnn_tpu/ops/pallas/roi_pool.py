"""Pallas TPU ROI max pooling — MXNet's ``ROIPooling``, forward and backward.

Reference: ``src/operator/roi_pooling.cc`` (SURVEY N6): a roi is rounded
to whole feature cells, cut into ``ph x pw`` bins with floor / ceil edges,
each bin emits the maximum of its cells (0 where it has none) and keeps
the winning cell's position (``max_idx``); the backward hands a bin's
whole cotangent to that one cell.  The arithmetic is compare / select on
the VPU; nothing here touches the MXU.

- **Edges outside, as whole numbers.**  The wrapper computes every roi's
  ``ph + ph + pw + pw`` bin edges with ``ops.roi_align._bin_edges`` (the
  jnp sweep's own: C's ``round`` of the scaled corners, integer floor /
  ceil, clipped to the map or, under ``valid_hw``, to the image's valid
  extent) and scalar-prefetches them.  No float reaches a bin edge in a
  kernel, and ``valid_hw`` is nothing but other integers.
- **Forward.**  Grid (image, channel block, roi block); the ``(H, W,
  cblk)`` map block stays resident in VMEM across the roi sweep.  For a
  roi and a bin row: a running maximum over the bin's own rows (a loop
  with the bin's bounds, not a mask over all H), then the ``pw`` column
  bins by masked maximum over the 16 8-aligned columns around the bin
  (over all W for a roi whose bins do not fit that).  Beside every
  pooled value the kernel writes its **arg-max cell** ``h * W + w``
  (int32; -1 for an empty bin): the residual of the backward.
- **Backward.**  Same grid; an f32 ``(H, W, cblk)`` accumulator resident
  across the roi sweep, zeroed at the first roi block, cast to the map's
  dtype outside.  A bin's cotangent goes to the cell the saved index
  names, and only the bin's own rows are touched.  The map is not read
  again and nothing is rematerialised.

**Ties.**  Where several cells of a bin hold the maximum, the FIRST in
row-major order takes the index and the whole cotangent, as
``roi_pooling.cc`` does (strict ``>`` in a row-major scan).  The jnp
sweep (``ops.roi_align.roi_pool``) shares a tie's gradient between the
tied cells, once a stage; either way a bin's whole cotangent arrives on
the map.  The forward is a maximum, so its values equal the sweep's bit
for bit in every dtype; the gradients are equal wherever no two cells of
a bin tie (``tests/test_pallas_roi_pool.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.pallas import out_struct
from mx_rcnn_tpu.ops.pallas.roi_align import (
    _VMEM_BUDGET,
    _cblk,
    _cblk_fit,
    _compiler_params,
    _pad,
    _sublanes,
)
from mx_rcnn_tpu.ops.roi_align import _bin_edges

_RBLK = 8      # rois per grid step
_LANES = 128   # channels worked at a time: one vreg column
_WINDOW = 16   # columns a column bin's maximum reads where the bin fits


def _lane_width(cblk: int) -> int:
    return _LANES if cblk % _LANES == 0 else cblk


def _roi_edges(edges_ref, b, r, pooled):
    """One roi's bin edges as scalars, from the (B, 2·ph + 2·pw, Rp)
    scalar-prefetch operand (the roi axis minor: SMEM pads the minor dim
    to 128 lanes) → (hlo, hhi, wlo, whi), lists of int32 scalars."""
    ph, pw = pooled
    rows = [edges_ref[b, i, r] for i in range(2 * ph + 2 * pw)]
    return (rows[:ph], rows[ph:2 * ph], rows[2 * ph:2 * ph + pw],
            rows[2 * ph + pw:])


def _column_windows(wlo, whi, wf: int):
    """Where each column bin's ``_WINDOW`` columns start (8-aligned: a
    dynamic slice of the sublane dimension has to be) and whether every
    bin of the roi lies inside its window, as scalars → (starts, fits);
    (None, None) for a map the windows do not tile.  A bin up to 9
    columns wide always fits; a roi with a wider one (57 cells or more
    across) takes the masked maximum over all W."""
    if wf % 8 or wf < _WINDOW:
        return None, None
    starts = [jnp.minimum(lo & -8, wf - _WINDOW) for lo in wlo]
    fits = whi[0] - starts[0] <= _WINDOW
    for hi, start in zip(whi[1:], starts[1:]):
        fits = jnp.logical_and(fits, hi - start <= _WINDOW)
    return starts, fits


def _lane_blocks(cblk: int, body):
    """``body(cs)`` for every ``_LANES``-wide slice ``cs`` of a channel
    block, as ONE traced loop.  Python traces a kernel's body whenever a
    program that holds it is traced, also op by op under ``model.init``
    while the loader's threads hold the interpreter: unrolled four times
    the forward's 49 bins took 34 s of a process's set-up there (PERF.md,
    PR 34), so what can be a loop at little cost (0.3 of 6 ms) is one."""
    lanes = _lane_width(cblk)

    def block(j, carry):
        body(pl.ds(pl.multiple_of(j * lanes, lanes), lanes))
        return carry

    jax.lax.fori_loop(0, cblk // lanes, block, 0)


def _fwd_kernel(edges_ref, feat_ref, out_ref, idx_ref, best_ref, cell_ref,
                *, pooled, rblk):
    """RBLK rois a grid step, ``_LANES`` channels at a time.  ``best_ref``
    / ``cell_ref`` (ph, W, lanes): every bin row's column maxima and the
    cells that hold them, between the two stages."""
    ph, pw = pooled
    b, rb = pl.program_id(0), pl.program_id(2)
    hf, wf, cblk = feat_ref.shape[1], feat_ref.shape[2], feat_ref.shape[3]
    lanes = _lane_width(cblk)
    neg = -jnp.inf
    # past every cell: what a column outside the bin offers the minimum
    far = hf * wf
    col = jax.lax.broadcasted_iota(jnp.int32, (wf, lanes), 0)
    binq = jax.lax.broadcasted_iota(jnp.int32, (pw, lanes), 0)

    def one_roi(k, carry):
        hlo, hhi, wlo, whi = _roi_edges(edges_ref, b, rb * rblk + k, pooled)
        starts, fits = _column_windows(wlo, whi, wf)

        def lane_block(cs):
            # stage 1: per bin row, the maximum over its own rows and the
            # first row that holds it (strict ``>``: the earliest wins)
            for p in range(ph):
                def row(h, acc):
                    best, arg = acc
                    v = feat_ref[0, h, :, cs].astype(jnp.float32)
                    gt = v > best
                    return jnp.where(gt, v, best), jnp.where(gt, h, arg)

                best, arg = jax.lax.fori_loop(
                    hlo[p], hhi[p], row,
                    (jnp.full((wf, lanes), neg, jnp.float32),
                     jnp.zeros((wf, lanes), jnp.int32)),
                )
                best_ref[p] = best
                cell_ref[p] = arg * wf + col
            # stage 2: the column bins, by masked maximum over a window of
            # ``win`` columns from ``starts[q]`` (or over all W).  Among
            # the columns that hold a bin's maximum the smallest cell
            # number is the first cell in row-major order (each column
            # offers its earliest row)
            def columns(win):
                outs = [jnp.zeros((pw, lanes), jnp.float32)] * ph
                idxs = [jnp.full((pw, lanes), -1, jnp.int32)] * ph
                for q in range(pw):
                    if win is None:
                        rows, at = slice(None), col
                    else:
                        rows = pl.ds(pl.multiple_of(starts[q], 8), win)
                        at = starts[q] + col[:win]
                    inside = (at >= wlo[q]) & (at < whi[q])
                    for p in range(ph):
                        t = jnp.where(inside, best_ref[p, rows, :], neg)
                        m = jnp.max(t, axis=0, keepdims=True)        # (1, L)
                        i = jnp.min(
                            jnp.where(t == m, cell_ref[p, rows, :], far),
                            axis=0, keepdims=True)
                        # no row or no column: the maximum of nothing
                        live = m > neg
                        outs[p] = jnp.where(
                            binq == q, jnp.where(live, m, 0.0), outs[p])
                        idxs[p] = jnp.where(
                            binq == q, jnp.where(live, i, -1), idxs[p])
                for p in range(ph):
                    out_ref[0, k, p, :, cs] = outs[p].astype(out_ref.dtype)
                    idx_ref[0, k, p, :, cs] = idxs[p]

            if starts is None:
                columns(None)
            else:
                jax.lax.cond(fits, lambda: columns(_WINDOW),
                             lambda: columns(None))

        _lane_blocks(cblk, lane_block)
        return carry

    jax.lax.fori_loop(0, rblk, one_roi, 0)


def _bwd_kernel(edges_ref, g_ref, idx_ref, dfeat_ref, *, pooled, rblk):
    """RBLK rois a grid step into the resident f32 accumulator: for a bin
    row, every one of its rows takes, bin by bin, the cotangent where the
    saved index names a cell of that row."""
    ph, pw = pooled
    b, rb = pl.program_id(0), pl.program_id(2)
    wf, cblk = dfeat_ref.shape[2], dfeat_ref.shape[3]
    lanes = _lane_width(cblk)
    col = jax.lax.broadcasted_iota(jnp.int32, (wf, lanes), 0)

    @pl.when(rb == 0)
    def _():
        dfeat_ref[...] = jnp.zeros(dfeat_ref.shape, dfeat_ref.dtype)

    def one_roi(k, carry):
        hlo, hhi, _, _ = _roi_edges(edges_ref, b, rb * rblk + k, pooled)

        def lane_block(cs):
            for p in range(ph):
                g = g_ref[0, k, p, :, cs].astype(jnp.float32)        # (pw, L)
                idx = idx_ref[0, k, p, :, cs]

                def row(h, c):
                    cell = h * wf + col
                    acc = dfeat_ref[0, h, :, cs]
                    for q in range(pw):
                        acc = acc + jnp.where(
                            idx[q:q + 1] == cell, g[q:q + 1], 0.0)
                    dfeat_ref[0, h, :, cs] = acc
                    return c

                jax.lax.fori_loop(hlo[p], hhi[p], row, 0)

        _lane_blocks(cblk, lane_block)
        return carry

    jax.lax.fori_loop(0, rblk, one_roi, 0)


def _fwd_bytes(h: int, w: int, blk: int, esize: int, pooled) -> int:
    """Upper bound on one forward grid step's VMEM: the double-buffered
    map block and the two output blocks (second-minor dims padded to the
    sublane tile), and the two stage buffers."""
    ph, pw = pooled
    feat = h * _pad(w, _sublanes(esize)) * blk * esize
    out = _RBLK * ph * blk * (
        _pad(pw, _sublanes(esize)) * esize + _pad(pw, 8) * 4)
    stage = 2 * ph * _pad(w, 8) * _lane_width(blk) * 4
    return 2 * (feat + out) + stage


def _bwd_bytes(h: int, w: int, blk: int, gsize: int, pooled) -> int:
    """Backward twin: the double-buffered f32 accumulator, cotangent and
    index blocks.  ``gsize``: cotangent dtype bytes."""
    ph, pw = pooled
    acc = h * _pad(w, 8) * blk * 4
    g = _RBLK * ph * blk * (
        _pad(pw, _sublanes(gsize)) * gsize + _pad(pw, 8) * 4)
    return 2 * (acc + g)


def fits_vmem(h: int, w: int, c: int, pooled, esize: int) -> bool:
    """True iff the pair holds this map within budget at its smallest
    channel block, forward AND backward — so a map dispatched to the
    kernels never fails to compile in its grad.  ``esize``: feature dtype
    bytes."""
    blk = _cblk(c, largest=128)
    return max(
        _fwd_bytes(h, w, blk, esize, pooled),
        _bwd_bytes(h, w, blk, esize, pooled),
    ) <= _VMEM_BUDGET


@partial(jax.jit, static_argnums=(2, 3, 4))
def _edges(rois, valid_hw, pooled, scale, feat_hw):
    """(B, R, 4) rois → (B, 2·ph + 2·pw, Rp) int32 edges, R padded to the
    roi block.  Pad rois get all-zero edges: every bin empty, no row
    visited.  One program where the caller runs op by op."""
    def image(rs, vhw):
        return jax.vmap(
            lambda roi: jnp.concatenate(
                _bin_edges(roi, pooled, scale, feat_hw, vhw))
        )(rs)

    edges = jax.vmap(image, in_axes=(0, None if valid_hw is None else 0))(
        rois, valid_hw)
    r = rois.shape[1]
    edges = edges.transpose(0, 2, 1)                                 # (B, E, R)
    return jnp.pad(edges, ((0, 0), (0, 0), (0, _pad(r, _RBLK) - r)))


def _roi_pool_fwd_impl(feat, rois, valid_hw, pooled, scale, interpret):
    """→ (pooled (B, R, ph, pw, C), (edges, arg-max cells (B, Rp, ph, pw,
    C) int32)): the second is the backward's residual."""
    b, hf, wf, c = feat.shape
    r = rois.shape[1]
    esize = feat.dtype.itemsize
    cblk = _cblk_fit(
        lambda blk: _fwd_bytes(hf, wf, blk, esize, pooled), c, largest=512)
    edges = _edges(rois, valid_hw, pooled, scale, (hf, wf))
    rp = edges.shape[2]
    lanes = _lane_width(cblk)
    block = pl.BlockSpec(
        (1, _RBLK, pooled[0], pooled[1], cblk),
        lambda bb, cb, rr, e: (bb, rr, 0, 0, cb),
    )
    shape = (b, rp, pooled[0], pooled[1], c)
    out, idx = pl.pallas_call(
        partial(_fwd_kernel, pooled=pooled, rblk=_RBLK),
        # every grid step writes disjoint blocks and fills the stage
        # buffers before it reads them
        compiler_params=_compiler_params(
            _fwd_bytes(hf, wf, cblk, esize, pooled),
            ("parallel", "parallel", "parallel"),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // cblk, rp // _RBLK),
            in_specs=[
                pl.BlockSpec(
                    (1, hf, wf, cblk), lambda bb, cb, rr, e: (bb, 0, 0, cb)
                ),
            ],
            out_specs=[block, block],
            scratch_shapes=[
                pltpu.VMEM((pooled[0], wf, lanes), jnp.float32),
                pltpu.VMEM((pooled[0], wf, lanes), jnp.int32),
            ],
        ),
        out_shape=[
            out_struct(shape, feat.dtype, edges, feat),
            out_struct(shape, jnp.int32, edges, feat),
        ],
        interpret=interpret,
        # a device trace names the kernel by this (NOT ``_roi_features``:
        # the benchmark finds the ROIAlign kernels by that)
        name="pallas_roi_pool_fwd",
    )(edges, feat)
    return (out[:, :r] if rp != r else out), (edges, idx)


def _roi_pool_bwd_impl(feat_shape, feat_dtype, edges, idx, g, pooled,
                       interpret):
    b, hf, wf, c = feat_shape
    r, rp = g.shape[1], idx.shape[1]
    gsize = g.dtype.itemsize
    cblk = _cblk_fit(
        lambda blk: _bwd_bytes(hf, wf, blk, gsize, pooled), c, largest=512)
    if rp != r:
        g = jnp.pad(g, ((0, 0), (0, rp - r)) + ((0, 0),) * (g.ndim - 2))
    block = pl.BlockSpec(
        (1, _RBLK, pooled[0], pooled[1], cblk),
        lambda bb, cb, rr, e: (bb, rr, 0, 0, cb),
    )
    out = pl.pallas_call(
        partial(_bwd_kernel, pooled=pooled, rblk=_RBLK),
        # the roi axis carries the accumulator and stays sequential
        compiler_params=_compiler_params(
            _bwd_bytes(hf, wf, cblk, gsize, pooled),
            ("parallel", "parallel", "arbitrary"),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // cblk, rp // _RBLK),
            in_specs=[block, block],
            out_specs=pl.BlockSpec(
                (1, hf, wf, cblk), lambda bb, cb, rr, e: (bb, 0, 0, cb)
            ),
        ),
        out_shape=out_struct((b, hf, wf, c), jnp.float32, edges, g),
        interpret=interpret,
        name="pallas_roi_pool_bwd",
    )(edges, g, idx)
    return out.astype(feat_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def roi_pool_pallas(
    feat: jnp.ndarray,
    rois: jnp.ndarray,
    pooled: tuple = (7, 7),
    spatial_scale: float = 1.0 / 16.0,
    interpret: bool = False,
    valid_hw=None,
) -> jnp.ndarray:
    """(B, H, W, C) feature + (B, R, 4) image-coord rois → (B, R, ph, pw, C).

    Batched twin of ``ops.roi_align.roi_pool`` backed by the Pallas pair;
    differentiable in ``feat`` (rois get zero cotangent, as the
    reference's Proposal op stops the gradient).  ``valid_hw`` (B, 2) =
    true pre-padding image sizes: bins are clipped to each image's valid
    feature extent instead of the canvas, as the sweep does under the
    same argument."""
    return _roi_pool_fwd_impl(
        feat, rois, valid_hw, pooled, spatial_scale, interpret
    )[0]


def _vjp_fwd(feat, rois, pooled, spatial_scale, interpret, valid_hw=None):
    out, (edges, idx) = _roi_pool_fwd_impl(
        feat, rois, valid_hw, pooled, spatial_scale, interpret
    )
    # feat rides along only for its shape/dtype; it is already live as a
    # backbone activation so this costs nothing extra
    return out, (feat, rois, valid_hw, edges, idx)


def _vjp_bwd(pooled, spatial_scale, interpret, res, g):
    feat, rois, valid_hw, edges, idx = res
    dfeat = _roi_pool_bwd_impl(
        feat.shape, feat.dtype, edges, idx, g, pooled, interpret
    )
    dvalid = None if valid_hw is None else jnp.zeros_like(valid_hw)
    return dfeat, jnp.zeros_like(rois), dvalid


roi_pool_pallas.defvjp(_vjp_fwd, _vjp_bwd)
