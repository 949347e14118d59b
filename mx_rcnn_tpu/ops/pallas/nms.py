"""Pallas TPU NMS kernel — blocked greedy suppression.

Reference: ``rcnn/cython/nms_kernel.cu`` (SURVEY N1) — the classic
py-faster-rcnn bitmask GPU kernel: 64×64 IoU tiles, per-(box, block)
suppression bitmasks, host-side sequential reduce.  The TPU formulation
keeps the same blocked structure but runs *entirely* on-chip with no host
reduce and no bitmask materialization:

- boxes arrive score-sorted (the proposal path already top-k sorts);
- process lane-width (128) blocks of boxes in order;
- per block: an exact sequential greedy scan *within* the block (128
  tiny VPU steps on (1, 128) vectors), then one vectorized (128, N) IoU
  slab that kills every later box overlapping a surviving block member —
  the O(N²) work rides the VPU in 8×128 tiles, and the unavoidable
  greedy serialization is only O(N) scalar steps instead of O(N²).

Layout notes (TPU tiling): boxes are carried as (8, N) — four coordinate
sublanes + area + three padding sublanes — so the lane dimension is the
box index and every slab op is natively tiled; a (N, 4) layout would
waste 32× VMEM in lane padding.

Semantics identical to ``ops.nms.nms_mask`` (validated against it and the
numpy oracle in tests/test_pallas_nms.py): invalid boxes neither survive
nor suppress; returns a keep mask over the *sorted* input.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.pallas import out_struct

BLOCK = 128


def _nms_kernel(
    boxes_ref,
    keep_in_ref,
    keep_ref,
    kept_ref,
    *,
    thresh: float,
    n: int,
    chunk: int,
    max_keep: int,
):
    """boxes_ref: (8, N) [x1, y1, x2, y2, area, pad...]; keep_ref: (1, N)
    f32 output aliased onto ``keep_in_ref`` (the validity mask) — arrives
    as validity, leaves as the keep mask.  ``chunk`` (divides N) is the
    lane width of the cross-block suppression slabs: only chunks at or
    after the current block are visited, so the O(N²) IoU work drops to
    the ~N²/2 upper triangle that can actually suppress.

    ``max_keep`` ≤ 0 runs the full greedy scan.  When > 0, the heavy
    cross-block chunk sweep collapses to an empty loop (its upper bound
    drops to ``first_chunk`` via the SMEM survivor counter ``kept_ref``)
    once ≥ ``max_keep`` boxes have survived: in descending-score order
    every survivor past that point ranks below the first ``max_keep``
    survivors, so a caller that keeps only the top ``max_keep``
    survivors (ops.nms.nms) sees identical results.  The mask beyond the
    stopping point is NOT a valid full NMS mask — truncated-exactness
    only.  (Mosaic cannot nest the vector-carry fixpoint inside a
    while/cond region, so the sweep itself stays an unconditional fori
    and only the chunk loop's dynamic bound is gated — the per-block
    128×128 fixpoint that still runs is ~2% of the skipped slab work.)"""
    keep_ref[:, :] = keep_in_ref[:, :]
    kept_ref[0] = 0.0
    n_blocks = n // BLOCK
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)    # (1,C)

    def iou_slab(blk, blk_area, allx, all_area):
        """IoU of a (8, BLOCK) block vs (8, M) boxes → (BLOCK, M)."""
        # transpose block coords into the sublane dim: (BLOCK, 1) each
        bx1 = blk[0:1, :].reshape(BLOCK, 1)
        by1 = blk[1:2, :].reshape(BLOCK, 1)
        bx2 = blk[2:3, :].reshape(BLOCK, 1)
        by2 = blk[3:4, :].reshape(BLOCK, 1)
        ba = blk_area.reshape(BLOCK, 1)
        iw = jnp.minimum(bx2, allx[2:3, :]) - jnp.maximum(bx1, allx[0:1, :]) + 1.0
        ih = jnp.minimum(by2, allx[3:4, :]) - jnp.maximum(by1, allx[1:2, :]) + 1.0
        inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)        # (BLOCK, M)
        union = ba + all_area - inter
        return inter / jnp.maximum(union, 1e-12)

    def outer(j, _):
        start = pl.multiple_of(j * BLOCK, BLOCK)
        blk = boxes_ref[:, pl.ds(start, BLOCK)]                    # (8,128)
        blk_area = blk[4:5, :]                                     # (1,128)
        valid_row = keep_ref[:, pl.ds(start, BLOCK)]               # (1,128) f32

        # Intra-block greedy via synchronous fixpoint iteration instead of
        # a 128-step scalar scan (TPU scalar-loop overhead is ~µs/step —
        # the scan was the whole kernel's cost).  Iterating
        #   alive_i ← valid_i ∧ ¬∃j<i (alive_j ∧ iou_ji > t)
        # is exact once iteration count ≥ the longest suppression-
        # dependency chain (each pass finalizes one more DAG level), and
        # the while_loop stops at the first unchanged pass — typically
        # 3-6 vectorized (128×128) VPU steps.
        sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
        iou_b = iou_slab(blk, blk_area, blk, blk_area)
        kill_edge = jnp.where((iou_b > thresh) & (sub < col), 1.0, 0.0)

        def fix_cond(carry):
            return carry[1]

        def fix_body(carry):
            alive_col, _ = carry
            killed = jnp.max(kill_edge * alive_col, axis=0, keepdims=True)
            new_row = jnp.where(killed > 0.5, 0.0, valid_row)      # (1,128)
            new_col = new_row.reshape(BLOCK, 1)
            return new_col, jnp.any(new_col != alive_col)

        alive_col, _ = jax.lax.while_loop(
            fix_cond, fix_body, (valid_row.reshape(BLOCK, 1), True)
        )
        alive = alive_col.reshape(1, BLOCK)
        keep_ref[:, pl.ds(start, BLOCK)] = alive

        # cross-block: surviving block members kill all later overlaps.
        # Visit only chunks containing boxes after this block — the
        # first such chunk may straddle the block, so the in-chunk
        # ``later`` lane mask protects its leading boxes.
        alive_col2 = alive.reshape(BLOCK, 1) > 0.5

        def chunk_body(kc, _):
            cstart = pl.multiple_of(kc * chunk, chunk)
            cbox = boxes_ref[:, pl.ds(cstart, chunk)]              # (8,C)
            iou_c = iou_slab(blk, blk_area, cbox, cbox[4:5, :])
            killed = jnp.max(
                jnp.where((iou_c > thresh) & alive_col2, 1.0, 0.0),
                axis=0,
                keepdims=True,
            )                                                      # (1,C)
            later = (cstart + lane_c) >= (start + BLOCK)
            cur = keep_ref[:, pl.ds(cstart, chunk)]
            keep_ref[:, pl.ds(cstart, chunk)] = jnp.where(
                later & (killed > 0.5), 0.0, cur
            )
            return 0

        first_chunk = (start + BLOCK) // chunk
        hi = n // chunk
        if max_keep > 0:
            # enough survivors → empty chunk loop from here on; the
            # counter only grows, so once collapsed it stays collapsed
            hi = jnp.where(kept_ref[0] < float(max_keep), hi, first_chunk)
        jax.lax.fori_loop(first_chunk, hi, chunk_body, 0)
        # re-read the block's final mask from VMEM for the survivor
        # count: summing the while-carry vector directly trips a Mosaic
        # relayout bug (replicated-offset carry → scalar reduce)
        alive_mem = keep_ref[:, pl.ds(start, BLOCK)]
        kept_ref[0] = kept_ref[0] + jnp.sum(alive_mem)
        return 0

    jax.lax.fori_loop(0, n_blocks, outer, 0)


@partial(jax.jit, static_argnames=("thresh", "interpret", "max_keep"))
def nms_mask_sorted_pallas(
    boxes: jnp.ndarray,
    valid: jnp.ndarray,
    thresh: float,
    interpret: bool = False,
    max_keep: int = 0,
) -> jnp.ndarray:
    """Keep mask for (N, 4) boxes ALREADY sorted by descending score.

    ``valid`` (N,) bool marks real rows.  N is padded to a lane multiple
    internally; returns (N,) bool.  ``interpret=True`` runs the kernel in
    the Pallas interpreter (CPU tests).  ``max_keep`` > 0 enables the
    early-exit sweep: the mask is only exact for selecting the top
    ``max_keep`` survivors by score (see the kernel docstring).
    """
    n = boxes.shape[0]
    n_pad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    # cross-block slab lane width: the largest candidate whose padding
    # waste stays ≤ 12.5% of the block-padded N (a fixed 2048 would pad
    # the default test shape 6016 → 8192, +36% slab area; 1536 pads it
    # to 6144, +2%).  BLOCK always divides n_pad, so the loop terminates.
    for chunk in (2048, 1536, 1024, 512, 256, BLOCK):
        padded = ((n_pad + chunk - 1) // chunk) * chunk
        if chunk <= n_pad and padded - n_pad <= n_pad // 8:
            break
    n_pad = ((n_pad + chunk - 1) // chunk) * chunk
    coords = jnp.zeros((8, n_pad), jnp.float32)
    bt = boxes.astype(jnp.float32).T                               # (4, N)
    coords = coords.at[0:4, :n].set(bt)
    area = (bt[2] - bt[0] + 1.0) * (bt[3] - bt[1] + 1.0)
    coords = coords.at[4, :n].set(area)
    keep0 = jnp.zeros((1, n_pad), jnp.float32).at[0, :n].set(
        valid.astype(jnp.float32)
    )

    keep = pl.pallas_call(
        partial(
            _nms_kernel,
            thresh=float(thresh),
            n=n_pad,
            chunk=chunk,
            max_keep=int(max_keep),
        ),
        out_shape=out_struct((1, n_pad), jnp.float32, coords, keep0),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        input_output_aliases={1: 0},
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        name="pallas_nms_mask",  # the kernel's name in a device trace
    )(coords, keep0)
    return keep[0, :n] > 0.5


def nms_mask_pallas(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    thresh: float,
    valid: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in twin of ``ops.nms.nms_mask`` backed by the Pallas kernel:
    sorts by score, runs the kernel, scatters back to input order."""
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    scores = jnp.where(valid, scores, -jnp.inf)
    order = jnp.argsort(-scores)
    keep_sorted = nms_mask_sorted_pallas(
        boxes[order], valid[order], thresh, interpret
    )
    return jnp.zeros((n,), bool).at[order].set(keep_sorted)
