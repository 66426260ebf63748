"""Pallas TPU kernel: fused per-stratum (count, Σx, Σx²) — the stats pass.

This is the per-window hot loop of StreamApprox: every query/error-bound
evaluation needs per-stratum moments of the sampled (or raw, for the native
baseline / STS pass 1) items. A segment reduction is re-cast as a masked
one-hot reduction on the VPU instead of a scalar scatter loop, with items
on lanes so no lane-to-sublane relayout is needed —

    onehot[s, j] = (sid[j] == s) & mask[j]                 ([S, BM], VPU)
    counts[s] += Σ_j onehot[s, j];  sums[s] += Σ_j onehot[s, j]·x[j]; …

The item axis is tiled with ``block_m``; the three ``[S, 1]`` accumulators
live in VMEM across sequential grid steps (TPU grids execute in order on a
core, so revisited output blocks act as accumulators). The mask travels
as int32 (Mosaic has no i1 memory blocks). The sums stay in f32 on the
VPU: an MXU matmul would round ``x`` to bf16 at default precision.
Interpret mode is chosen by the caller (``kernels/ops.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stats_kernel(x_ref, sid_ref, mask_ref, counts_ref, sums_ref,
                  sumsqs_ref, *, num_strata: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)
        sumsqs_ref[...] = jnp.zeros_like(sumsqs_ref)

    x = x_ref[...].astype(jnp.float32)                        # [1, BM]
    strata = jax.lax.broadcasted_iota(
        jnp.int32, (num_strata, x.shape[1]), 0)               # [S, BM]
    onehot = ((sid_ref[...] == strata) & (mask_ref[...] != 0)
              ).astype(jnp.float32)
    xm = onehot * x
    counts_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)
    sums_ref[...] += jnp.sum(xm, axis=1, keepdims=True)
    sumsqs_ref[...] += jnp.sum(xm * x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_strata", "block_m",
                                             "interpret"))
def stratified_stats(values: jax.Array, stratum_ids: jax.Array,
                     mask: jax.Array, num_strata: int,
                     block_m: int = 1024,
                     interpret: bool = False):
    """Fused per-stratum moments of a flat item buffer.

    Args:
      values: ``[M]`` float — item values.
      stratum_ids: ``[M]`` int32 in ``[0, num_strata)``.
      mask: ``[M]`` bool — invalid items contribute nothing.
      num_strata: static stratum count ``S``.
      block_m: item-axis tile (multiple of 128 for lane alignment).

    Returns:
      ``(counts, sums, sumsqs)`` — each ``[S]`` float32.
    """
    m = values.shape[0]
    mask = mask.astype(jnp.int32)
    if m % block_m != 0:
        pad = block_m - m % block_m
        values = jnp.pad(values, (0, pad))
        stratum_ids = jnp.pad(stratum_ids, (0, pad))
        mask = jnp.pad(mask, (0, pad))
        m = values.shape[0]
    grid = (m // block_m,)
    kernel = functools.partial(_stats_kernel, num_strata=num_strata)
    out_shape = [jax.ShapeDtypeStruct((num_strata, 1), jnp.float32)] * 3
    item_spec = pl.BlockSpec((1, block_m), lambda i: (0, i))
    acc_spec = pl.BlockSpec((num_strata, 1), lambda i: (0, 0))
    counts, sums, sumsqs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[item_spec, item_spec, item_spec],
        out_specs=[acc_spec, acc_spec, acc_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(values[None, :], stratum_ids[None, :], mask[None, :])
    return counts[:, 0], sums[:, 0], sumsqs[:, 0]
