"""Pallas TPU kernel: fused masked weighted histogram over reservoirs.

The hot inner loop of both ``query_histogram`` and the sort-free quantile
refinement (``repro.core.quantile``): every evaluation needs, for a flat
buffer of reservoir slots, the per-(stratum, bin) *weighted* mass and the
per-(stratum, bin) *sampled-item count* (the count feeds the Eq. 6
indicator variance; the weighted mass is the Horvitz–Thompson value).

TPU layout: bin membership and stratum membership are both one-hot
comparisons with items on lanes (VPU), and the [S, B] accumulation is one
``[S, BM] · [B, BM]ᵀ`` matmul per item tile (MXU, contracting the lane
axis of both operands):

    in_bin[b, j]  = (x[j] >= e_b) & (x[j] < e_{b+1}) & mask[j]
    onehot[s, j]  = (sid[j] == s) & mask[j]
    whist  += onehot · (in_bin ⊙ w)ᵀ        cnt += onehot · in_binᵀ

The bin edges ride along as ``[B, 1]`` lower/upper columns, the mask as
int32 (Mosaic has no i1 memory blocks); nothing moves between lanes and
sublanes. The weighted matmul runs at ``HIGHEST`` precision so the
weights keep f32 accuracy. The two ``[S, B]`` accumulators stay resident
in VMEM across sequential grid steps (revisited output blocks persist —
TPU grids run in order on a core). The last bin is right-closed so
``edges[-1]`` itself is counted. Interpret mode is chosen by the caller
(``kernels/ops.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE_CONTRACT = (((1,), (1,)), ((), ()))     # A · Bᵀ over the lane axis


def _whist_kernel(x_ref, sid_ref, w_ref, mask_ref, lo_ref, hi_ref,
                  whist_ref, cnt_ref, *, num_strata: int, num_bins: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        whist_ref[...] = jnp.zeros_like(whist_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)                       # [1, BM]
    w = w_ref[...].astype(jnp.float32)                       # [1, BM]
    mask = mask_ref[...] != 0                                # [1, BM]
    lo = lo_ref[...].astype(jnp.float32)                     # [B, 1]
    hi = hi_ref[...].astype(jnp.float32)                     # [B, 1]

    bins = jax.lax.broadcasted_iota(jnp.int32, (num_bins, 1), 0)
    closed = bins == num_bins - 1                            # last bin ≤ hi
    in_bin = (x >= lo) & ((x < hi) | ((x == hi) & closed))
    in_bin = (in_bin & mask).astype(jnp.float32)             # [B, BM]

    strata = jax.lax.broadcasted_iota(
        jnp.int32, (num_strata, x.shape[1]), 0)
    onehot = ((sid_ref[...] == strata) & mask
              ).astype(jnp.float32)                          # [S, BM]

    cnt_ref[...] += jax.lax.dot_general(
        onehot, in_bin, _LANE_CONTRACT,
        preferred_element_type=jnp.float32)
    whist_ref[...] += jax.lax.dot_general(
        onehot, in_bin * w, _LANE_CONTRACT,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_strata", "block_m",
                                             "interpret"))
def weighted_hist(values: jax.Array, stratum_ids: jax.Array,
                  weights: jax.Array, mask: jax.Array, edges: jax.Array,
                  num_strata: int, block_m: int = 256,
                  interpret: bool = False):
    """Fused per-(stratum, bin) weighted histogram of a flat slot buffer.

    Args:
      values: ``[M]`` float — slot values (e.g. flattened reservoirs).
      stratum_ids: ``[M]`` int32 in ``[0, num_strata)``.
      weights: ``[M]`` float — per-item HT weight (``W_i`` of its stratum).
      mask: ``[M]`` bool — dead slots contribute nothing.
      edges: ``[B + 1]`` float, ascending; bin ``b`` is
        ``[edges[b], edges[b+1])`` with the last bin right-closed.
      num_strata: static stratum count ``S``.
      block_m: item-axis tile.

    Returns:
      ``(whist, counts)`` — both ``[S, B]`` float32: weighted mass and
      number of sampled (masked-in) items per cell.
    """
    m = values.shape[0]
    num_bins = edges.shape[0] - 1
    mask = mask.astype(jnp.int32)
    if m % block_m != 0:
        pad = block_m - m % block_m
        values = jnp.pad(values, (0, pad))
        stratum_ids = jnp.pad(stratum_ids, (0, pad))
        weights = jnp.pad(weights, (0, pad))
        mask = jnp.pad(mask, (0, pad))
        m = values.shape[0]
    grid = (m // block_m,)
    item = lambda: pl.BlockSpec((1, block_m), lambda i: (0, i))
    edge_spec = pl.BlockSpec((num_bins, 1), lambda i: (0, 0))
    acc = pl.BlockSpec((num_strata, num_bins), lambda i: (0, 0))
    kernel = functools.partial(_whist_kernel, num_strata=num_strata,
                               num_bins=num_bins)
    whist, cnt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[item(), item(), item(), item(), edge_spec, edge_spec],
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((num_strata, num_bins), jnp.float32),
                   jax.ShapeDtypeStruct((num_strata, num_bins), jnp.float32)],
        interpret=interpret,
    )(values[None, :], stratum_ids[None, :], weights[None, :], mask[None, :],
      edges[:-1, None], edges[1:, None])
    return whist, cnt
