"""Pallas TPU deformable convolution's sampling — the columns of
``ops/deform_conv.py``, forward and backward, with one image's map in VMEM.

What is computed is ``ops.deform_conv``'s ``(positions, 9·C)`` columns:
for every output position, tap and deformable group the bilinear sample
of the group's channels at the point the offsets give, MXNet's border
rule included.  The caller hands over the points already clipped to the
map (``yc``, ``xc``) with MXNet's in-map test as ``keep``; clipping, the
taps' grid and the offsets' layout stay jnp, so their gradients are
``jax.grad``'s as in the jnp form.

- **Grid** (image, group, position block of ``_P``).  The group's slice
  of the image's map, ``(H·W', C/G)``, is one block whose index does not
  move over the position blocks: it stays resident in VMEM.  ``W'`` is
  the map's width padded with zero columns to a multiple of 16, so that
  every chunk starts on a sublane tile in every dtype (the portrait
  bucket's 38-wide conv5 map is read 48 wide); the points are clipped to
  the real width, so a padded column never takes weight, and its
  gradient is sliced off.
- **Bands.**  The wrapper finds, for every (image, group, block, tap), the
  map rows the block's kept points reach — from the first corner row to
  the last — and scalar-prefetches the band's first row and the number of
  ``_CR``-row chunks that cover it.  Any offset is exact: a wide band is
  only more chunks.  The chunks' total comes back beside the output, as
  the ``deform_window_chunks_u<i>`` counter.
- **Forward.**  For a tap and a chunk the kernel builds the bilinear
  weights of the block's points over the chunk's ``_CR·W'`` map positions,
  transposed — ``(chunk positions, block positions)``, each row of the
  chunk the column weights times that row's weight, so a point's numbers
  are lanes — and multiplies it into the chunk on the MXU, accumulating
  in float32.  The weights enter the product in the map's dtype (one
  bfloat16 pass for a bfloat16 map).
- **Backward.**  Same grid; the group's float32 ``(H·W', C/G)`` map
  gradient is the resident output block, zeroed at the first position
  block and written back once.  A chunk adds the weights times the
  columns' cotangent (the transposed product of the forward), and the
  points' gradient is the weights' slopes against ``chunk · cotangent``
  (one product, then a reduction over the chunk): zero across a clamped
  edge, where both corner rows are the same row.

The columns come out grouped by deformable group, ``(g, tap, c)`` along
the last axis; ``kernel_rows`` puts a ``(3, 3, C, Cout)`` kernel in that
order.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.deform_conv import TAPS, inside_map, sample_points
from mx_rcnn_tpu.ops.pallas import out_struct
from mx_rcnn_tpu.ops.pallas.roi_align import (
    _VMEM_BUDGET,
    _compiler_params,
    _pad,
    _sublanes,
)

_P = 128     # output positions a grid step: the lanes of a point's numbers
_CR = 4      # map rows a chunk: _CR·W' positions deep in the MXU
_COLS = 16   # the map's width is padded to a multiple of this (W')
_TAPS = 9
_LANES = 128


def kernel_rows(kernel: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(3, 3, C, Cout) kernel → (G·9·C/G, Cout): rows in the columns'
    (group, tap, channel) order."""
    kh, kw, c, cout = kernel.shape
    k = kernel.reshape(kh * kw, groups, c // groups, cout)
    return k.transpose(1, 0, 2, 3).reshape(kh * kw * c, cout)


def padded_width(w: int) -> int:
    """The map's width as the pair lays it out, ``W'``."""
    return _pad(w, _COLS)


def bands(yc, keep, h: int, xp=jnp):
    """(…, 9, N) clipped rows and in-map flags, N a multiple of ``_P`` →
    (first row, chunks), each (…, N / _P, 9) int32: the map rows from the
    first corner row to the last that a block's kept points read, in
    ``_CR``-row chunks (0 chunks where no point is kept).  ``xp`` is
    ``jnp``, or ``np`` for a count on the host."""
    *lead, taps, n = yc.shape
    shape = (*lead, taps, n // _P, _P)
    y0 = xp.floor(yc).astype("int32").reshape(shape)
    y1 = xp.minimum(y0 + 1, h - 1)
    live = keep.reshape(shape) > 0
    lo = xp.where(live, y0, h).min(-1)
    hi = xp.where(live, y1, -1).max(-1)
    chunks = xp.maximum(hi - lo + _CR, 0) // _CR
    start = xp.where(chunks > 0, lo, 0)
    return xp.swapaxes(start, -1, -2), xp.swapaxes(chunks, -1, -2)


def _point(pts_ref, k, h: int, w: int):
    """Tap ``k``'s points of the block as (1, P) rows: the corner rows and
    columns (int32), the fractional parts, the in-map flag."""
    yc = pts_ref[0, 0, pl.ds(k, 1), :]
    xc = pts_ref[0, 0, pl.ds(_TAPS + k, 1), :]
    keep = pts_ref[0, 0, pl.ds(2 * _TAPS + k, 1), :]
    y0f, x0f = jnp.floor(yc), jnp.floor(xc)
    y0, x0 = y0f.astype(jnp.int32), x0f.astype(jnp.int32)
    return (y0, jnp.minimum(y0 + 1, h - 1), yc - y0f,
            x0, jnp.minimum(x0 + 1, w - 1), xc - x0f, keep)


def _row_weights(r, nominal, y0, y1, ly, keep):
    """Map row ``r``'s bilinear weight for every point and its slope
    against the point's row, each (1, P); both 0 for a row an earlier
    chunk already took (``r < nominal``: the last chunk is moved up to
    end at the map's last row)."""
    live = jnp.where(r >= nominal, keep, 0.0)
    at0, at1 = y0 == r, y1 == r
    return (live * (jnp.where(at0, 1.0 - ly, 0.0) + jnp.where(at1, ly, 0.0)),
            live * (jnp.where(at1, 1.0, 0.0) - jnp.where(at0, 1.0, 0.0)))


def _column_weights(w: int, x0, x1, lx):
    """The map columns' bilinear weights for every point and their slopes
    against the point's column, each (W', P): a point's numbers are lanes
    (0 in a padded column: no point's corners reach it)."""
    col = jax.lax.broadcasted_iota(jnp.int32, (w, x0.shape[-1]), 0)
    at0, at1 = col == x0, col == x1
    return (jnp.where(at0, 1.0 - lx, 0.0) + jnp.where(at1, lx, 0.0),
            jnp.where(at1, 1.0, 0.0) - jnp.where(at0, 1.0, 0.0))


def _chunk_rows(s0, c, h: int):
    """Chunk ``c`` of a band from ``s0``: (its nominal first row, the
    first row it reads), the second kept inside the map."""
    nominal = s0 + c * _CR
    return nominal, jnp.minimum(nominal, h - _CR)


def _fwd_kernel(start_ref, count_ref, pts_ref, map_ref, out_ref, *, h, w, wp,
                nb, groups):
    b, g, rb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = ((b * groups + g) * nb + rb) * _TAPS
    p = pts_ref.shape[-1]
    cg = map_ref.shape[-1]

    def tap(k, carry):
        y0, y1, ly, x0, x1, lx, keep = _point(pts_ref, k, h, w)
        wx, _ = _column_weights(wp, x0, x1, lx)
        s0 = start_ref[base + k]

        def chunk(c, acc):
            nominal, s = _chunk_rows(s0, c, h)
            # the chunk's weights, transposed: (chunk positions, P)
            wt = jnp.concatenate(
                [wx * _row_weights(s + i, nominal, y0, y1, ly, keep)[0]
                 for i in range(_CR)], axis=0).astype(map_ref.dtype)
            rows = map_ref[0, pl.ds(pl.multiple_of(s * wp, _COLS), _CR * wp),
                           :]
            return acc + jax.lax.dot_general(
                wt, rows, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(0, count_ref[base + k], chunk,
                                jnp.zeros((p, cg), jnp.float32))
        out_ref[0, :, pl.ds(pl.multiple_of(k * cg, _LANES), cg)] = (
            acc.astype(out_ref.dtype))
        return carry

    jax.lax.fori_loop(0, _TAPS, tap, 0)


def _bwd_kernel(start_ref, count_ref, pts_ref, map_ref, g_ref, dmap_ref,
                dpts_ref, *, h, w, wp, nb, groups):
    b, g, rb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = ((b * groups + g) * nb + rb) * _TAPS
    p = pts_ref.shape[-1]
    cg = map_ref.shape[-1]

    @pl.when(rb == 0)
    def _():
        dmap_ref[...] = jnp.zeros(dmap_ref.shape, dmap_ref.dtype)

    def tap(k, carry):
        y0, y1, ly, x0, x1, lx, keep = _point(pts_ref, k, h, w)
        wx, dwx = _column_weights(wp, x0, x1, lx)
        gk = g_ref[0, :, pl.ds(pl.multiple_of(k * cg, _LANES), cg)]  # (P, Cg)
        s0 = start_ref[base + k]

        def chunk(c, carry):
            dy, dx = carry
            nominal, s = _chunk_rows(s0, c, h)
            at = pl.ds(pl.multiple_of(s * wp, _COLS), _CR * wp)
            # every chunk position's channels against every point's
            # cotangent: (chunk positions, P)
            dots = jax.lax.dot_general(
                map_ref[0, at, :], gk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            pieces = []
            for i in range(_CR):
                wy, dwy = _row_weights(s + i, nominal, y0, y1, ly, keep)
                row = dots[i * wp:(i + 1) * wp]                   # (W', P)
                dy = dy + dwy * jnp.sum(wx * row, axis=0, keepdims=True)
                dx = dx + wy * jnp.sum(dwx * row, axis=0, keepdims=True)
                pieces.append(wx * wy)
            wt = jnp.concatenate(pieces, axis=0).astype(gk.dtype)
            dmap_ref[0, at, :] += jnp.dot(
                wt, gk, preferred_element_type=jnp.float32)
            return dy, dx

        zero = jnp.zeros((1, p), jnp.float32)
        dy, dx = jax.lax.fori_loop(0, count_ref[base + k], chunk,
                                   (zero, zero))
        dpts_ref[0, 0, pl.ds(k, 1), :] = dy
        dpts_ref[0, 0, pl.ds(_TAPS + k, 1), :] = dx
        return carry

    jax.lax.fori_loop(0, _TAPS, tap, 0)


def _fwd_bytes(h: int, wp: int, cg: int, esize: int) -> int:
    """Upper bound on one forward grid step's VMEM: the double-buffered
    map, points and columns blocks, and a chunk's weights (float32 and
    cast), its products with the points and the accumulator."""
    rows = _pad(h * wp, _sublanes(esize)) * cg * esize
    pts = _pad(3 * _TAPS, 8) * _P * 4
    out = _P * _TAPS * cg * esize
    chunk = 3 * _CR * wp * _P * 4 + 2 * _P * cg * 4
    return 2 * (rows + pts + out) + chunk


def _bwd_bytes(h: int, wp: int, cg: int, esize: int) -> int:
    """Backward twin: the float32 map gradient beside the map, the
    columns' cotangent, the points and their gradient."""
    acc = _pad(h * wp, 8) * cg * 4
    return _fwd_bytes(h, wp, cg, esize) + 2 * (
        acc + _pad(2 * _TAPS, 8) * _P * 4)


def fits(h: int, w: int, c: int, groups: int, esize: int) -> bool:
    """True where the pair takes this map: whole 128-channel groups, at
    least a chunk of rows, and one group's map, padded to ``W'``, with its
    float32 gradient within the VMEM budget, forward and backward."""
    cg = c // groups
    return (c % groups == 0 and cg % _LANES == 0 and h >= _CR
            and _bwd_bytes(h, padded_width(w), cg, esize) <= _VMEM_BUDGET)


def _flat(v):
    """(B, H, W, 9, G) → (B, G, 9, N): positions last, padded to N, a
    multiple of ``_P`` (with zeros: a padded point is never kept)."""
    b, h, w, taps, groups = v.shape
    v = v.reshape(b, h * w, taps, groups).transpose(0, 3, 2, 1)
    return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, _pad(h * w, _P) - h * w)))


def _blocks(hw: int, cg: int):
    """The grid's blocks: (rows of a block's points, given how many rows,
    the group's map (resident over the position blocks), a block's
    columns)."""
    def points(rows):
        return pl.BlockSpec((1, 1, rows, _P),
                            lambda bb, gg, rr, s, k: (bb, gg, 0, rr))

    return (points,
            pl.BlockSpec((1, hw, cg), lambda bb, gg, rr, s, k: (bb, 0, gg)),
            pl.BlockSpec((1, _P, _TAPS * cg),
                         lambda bb, gg, rr, s, k: (bb, rr, gg)))


def _columns_fwd(x, pts, start, chunks, hw, groups, interpret):
    """(B, H·W', C) map → columns (B, N, G·9·C/G)."""
    b, _, c = x.shape
    h, w = hw
    wp = padded_width(w)
    cg = c // groups
    n = pts.shape[-1]
    nb = n // _P
    points, group_map, block_cols = _blocks(h * wp, cg)
    return pl.pallas_call(
        partial(_fwd_kernel, h=h, w=w, wp=wp, nb=nb, groups=groups),
        compiler_params=_compiler_params(
            _fwd_bytes(h, wp, cg, x.dtype.itemsize),
            ("parallel", "parallel", "parallel")),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups, nb),
            in_specs=[points(3 * _TAPS), group_map],
            out_specs=block_cols,
        ),
        out_shape=out_struct((b, n, _TAPS * c), x.dtype, x, pts),
        interpret=interpret,
        # a device trace names the kernel by this
        name="pallas_deform_conv_fwd",
    )(start, chunks, pts, x)


def _columns_bwd(x, pts, start, chunks, g, hw, groups, interpret):
    """→ (the map's gradient (B, H·W', C) float32, the points' (B, G, 18,
    N): rows then columns)."""
    b, _, c = x.shape
    h, w = hw
    wp = padded_width(w)
    cg = c // groups
    n = pts.shape[-1]
    nb = n // _P
    points, group_map, block_cols = _blocks(h * wp, cg)
    return pl.pallas_call(
        partial(_bwd_kernel, h=h, w=w, wp=wp, nb=nb, groups=groups),
        # the position blocks carry the map's gradient and stay in order
        compiler_params=_compiler_params(
            _bwd_bytes(h, wp, cg, x.dtype.itemsize),
            ("parallel", "parallel", "arbitrary")),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups, nb),
            in_specs=[points(3 * _TAPS), group_map, block_cols],
            out_specs=[group_map, points(2 * _TAPS)],
        ),
        out_shape=[
            out_struct((b, h * wp, c), jnp.float32, x, pts, g),
            out_struct((b, groups, 2 * _TAPS, n), jnp.float32, x, pts, g),
        ],
        interpret=interpret,
        name="pallas_deform_conv_bwd",
    )(start, chunks, pts, x, g)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sample(x, pts, start, chunks, hw, groups, interpret):
    return _columns_fwd(x, pts, start, chunks, hw, groups, interpret)


def _sample_fwd(x, pts, start, chunks, hw, groups, interpret):
    cols = _columns_fwd(x, pts, start, chunks, hw, groups, interpret)
    return cols, (x, pts, start, chunks)


def _sample_bwd(hw, groups, interpret, res, g):
    x, pts, start, chunks = res
    dmap, dpts = _columns_bwd(x, pts, start, chunks, g, hw, groups, interpret)
    # the flags and the bands take no gradient
    dpts = jnp.concatenate([dpts, jnp.zeros_like(dpts[:, :, :_TAPS])], axis=2)
    return dmap.astype(x.dtype), dpts, None, None


_sample.defvjp(_sample_fwd, _sample_bwd)


def columns(x, yc, xc, keep, groups: int, interpret: bool = False):
    """(B, H, W, C) map, (B, H, W, 9, G) clipped points and in-map flags →
    ((B, H·W, G·9·C/G) columns in the map's dtype, the chunks their bands
    read (int32)), differentiable in the map and the points (``fits``
    says which maps the pair takes)."""
    b, h, w, c = x.shape
    pts = jnp.concatenate(
        [_flat(yc), _flat(xc), _flat(keep.astype(jnp.float32))], axis=2)
    start, chunks = bands(pts[:, :, :_TAPS], pts[:, :, 2 * _TAPS:], h)
    wp = padded_width(w)
    if wp != w:
        # zero columns: the points are clipped to w - 1, so none reads
        # them, and jnp.pad's transpose slices their gradient off
        x = jnp.pad(x, ((0, 0), (0, 0), (0, wp - w), (0, 0)))
    cols = _sample(x.reshape(b, h * wp, c), pts, start.reshape(-1),
                   chunks.reshape(-1), (h, w), groups, interpret)
    return cols[:, :h * w], chunks.sum()


def _points(offsets, dilation: int, groups: int):
    """The jnp form's sampling points, clipped to the map, and MXNet's
    in-map test: (yc, xc, keep), each (B, H, W, 9, G)."""
    h, w = offsets.shape[1:3]
    y, x = sample_points(offsets, dilation, groups)
    keep = inside_map(y, x, (h, w))
    return jnp.clip(y, 0.0, h - 1.0), jnp.clip(x, 0.0, w - 1.0), keep


@partial(jax.jit, static_argnums=(3, 4, 5))
def deform_conv_pallas(x, offsets, kernel, dilation: int, groups: int,
                       interpret: bool = False):
    """``ops.deform_conv.deform_conv`` with the columns formed by the
    Pallas pair: (B, H, W, C) map, (B, H, W, 2·9·G) offsets, (3, 3, C,
    Cout) kernel → ((B, H, W, Cout) in the kernel's dtype, the chunks the
    bands read, as :func:`window_chunks` counts them).  One program where
    the caller runs op by op."""
    b, h, w, _c = x.shape
    yc, xc, keep = _points(offsets, dilation, groups)
    cols, chunks = columns(x, yc, xc, keep, groups, interpret)
    k = kernel_rows(kernel, groups)
    return (cols.astype(k.dtype) @ k).reshape(b, h, w, k.shape[-1]), chunks


def window_chunks(offsets, dilation: int, groups: int) -> jnp.ndarray:
    """The chunks the pair's bands read for these (B, H, W, 2·9·G) offsets
    (int32; what they would read where the jnp form runs).  At zero
    offsets a block of ``_P`` positions reaches a few rows a tap; larger
    offsets widen the bands and the count with them."""
    yc, _xc, keep = _points(offsets, dilation, groups)
    _start, chunks = bands(_flat(yc), _flat(keep.astype(jnp.float32)),
                           offsets.shape[1])
    return chunks.sum()


def window_chunks_min(b: int, h: int, w: int, dilation: int,
                      groups: int) -> int:
    """:func:`window_chunks` at zero offsets, from the shapes alone:
    counted on the host with numpy."""
    taps = np.asarray(TAPS)                                      # (9, 2)
    y = np.arange(h)[:, None, None] + dilation * taps[:, 0]     # (H, 1, 9)
    x = np.arange(w)[None, :, None] + dilation * taps[:, 1]     # (1, W, 9)
    y, x = np.broadcast_arrays(y, x)
    keep = inside_map(y, x, (h, w))
    pad = ((0, 0), (0, _pad(h * w, _P) - h * w))

    def flat(v):                                         # (H, W, 9) → (9, N)
        return np.pad(v.reshape(h * w, len(TAPS)).T.astype(np.float32), pad)

    _start, chunks = bands(flat(np.clip(y, 0, h - 1)), flat(keep), h, xp=np)
    # every image and group reads the same bands at zero offsets
    return b * groups * int(chunks.sum())
