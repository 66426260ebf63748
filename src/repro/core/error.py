"""Error estimation for approximate linear queries — paper §3.3.

Implements the stratified random-sampling variance estimators (Eqs. 5–9) and
the 68-95-99.7 confidence machinery. All functions are pure jnp and operate
on per-stratum summary statistics so that they compose with the distributed
merge (each worker's (stratum × shard) cell is an independent stratum; the
variance of the total is the sum of cell variances — Eq. 5).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils import dataclass_pytree

#: z multipliers of the paper's "68-95-99.7" rule.
Z_FOR_CONFIDENCE = {0.68: 1.0, 0.95: 2.0, 0.997: 3.0}


@dataclass_pytree
@dataclasses.dataclass
class Estimate:
    """An approximate query result ``value ± error`` (paper Algorithm 2)."""
    value: jax.Array
    variance: jax.Array

    def error_bound(self, confidence: float = 0.95) -> jax.Array:
        z = Z_FOR_CONFIDENCE.get(confidence)
        if z is None:
            raise ValueError(
                f"confidence must be one of {sorted(Z_FOR_CONFIDENCE)} "
                "(the paper's 68-95-99.7 rule)")
        return z * jnp.sqrt(jnp.maximum(self.variance, 0.0))

    def interval(self, confidence: float = 0.95):
        e = self.error_bound(confidence)
        return self.value - e, self.value + e


@dataclass_pytree
@dataclasses.dataclass
class StratumStats:
    """Per-stratum sufficient statistics of the *sampled* items.

    ``counts`` is ``C_i`` (stream arrivals), ``taken`` is ``Y_i`` (sample
    size), and ``(sums, sumsqs)`` are moments of the Y_i sampled values.
    Everything downstream (queries, variances, adaptive allocation) reads
    only this summary — one fused pass over the reservoir produces it.
    """
    counts: jax.Array   # [S] int32   C_i
    taken: jax.Array    # [S] int32   Y_i
    sums: jax.Array     # [S] f32     Σ_j I_ij
    sumsqs: jax.Array   # [S] f32     Σ_j I_ij²

    def mean(self) -> jax.Array:
        """Per-stratum sample mean ``Ī_i`` (Eq. 7), 0 where Y_i = 0."""
        y = jnp.maximum(self.taken, 1).astype(jnp.float32)
        return jnp.where(self.taken > 0, self.sums / y, 0.0)

    def s2(self) -> jax.Array:
        """Unbiased per-stratum sample variance ``s_i²`` (Eq. 7).

        Zero where ``Y_i < 2`` (a single sample carries no spread
        information; the finite-population factor ``C_i - Y_i`` also vanishes
        whenever the stratum was fully taken).
        """
        y = self.taken.astype(jnp.float32)
        mean = self.mean()
        ss = self.sumsqs - y * mean * mean
        return jnp.where(self.taken > 1,
                         jnp.maximum(ss, 0.0) / jnp.maximum(y - 1.0, 1.0),
                         0.0)


def _pairwise_sum(x: jax.Array, axis: int) -> jax.Array:
    """Sum over ``axis`` by pairwise halving, in an order this code fixes.

    ``jnp.sum`` adds in the order the compiler's layout for ``x`` gives,
    and two programs may lay the same array out differently: on a TPU the
    mesh and vmap emissions do, and their f32 sums then round apart. The
    barrier between levels keeps XLA from folding the halvings back into
    one reduction; within a level each sum has two terms, so any order
    gives the same bits.
    """
    width = 1 << max(x.shape[axis] - 1, 0).bit_length()
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, width - x.shape[axis])
    x = jnp.pad(x, pad)
    while width > 1:
        width //= 2
        x = jax.lax.optimization_barrier(
            jax.lax.slice_in_dim(x, 0, width, axis=axis)
            + jax.lax.slice_in_dim(x, width, 2 * width, axis=axis))
    return jnp.squeeze(x, axis)


def stratum_stats_from_sample(
    xs: jax.Array, counts: jax.Array, taken: jax.Array,
    slot_mask: jax.Array, fixed_order: bool = False) -> StratumStats:
    """Build :class:`StratumStats` from reservoir contents ``xs [S, N]``.

    ``fixed_order`` sums each row with :func:`_pairwise_sum`. Sharded
    emissions set it: the mesh and vmap placements must agree bitwise,
    which only a chip run (``chip_smoke.py --mesh4``) can check, since CPU
    layouts agree either way.
    """
    m = slot_mask.astype(xs.dtype)
    xs32 = (xs * m).astype(jnp.float32)
    row_sum = _pairwise_sum if fixed_order else jnp.sum
    return StratumStats(
        counts=counts,
        taken=taken,
        sums=row_sum(xs32, axis=1),
        sumsqs=row_sum(xs32 * xs32 * m.astype(jnp.float32), axis=1),
    )


def var_sum(stats: StratumStats) -> jax.Array:
    """Eq. 6: ``Var(SUM) = Σ_i C_i (C_i − Y_i) s_i² / Y_i``."""
    c = stats.counts.astype(jnp.float32)
    y = jnp.maximum(stats.taken, 1).astype(jnp.float32)
    per = c * jnp.maximum(c - y, 0.0) * stats.s2() / y
    return jnp.sum(per)


def var_mean(stats: StratumStats) -> jax.Array:
    """Eq. 9: ``Var(MEAN) = Σ_i ω_i² (s_i²/Y_i) (C_i−Y_i)/C_i``."""
    c = stats.counts.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(c), 1.0)
    omega = c / total
    y = jnp.maximum(stats.taken, 1).astype(jnp.float32)
    fpc = jnp.where(c > 0, jnp.maximum(c - y, 0.0) / jnp.maximum(c, 1.0), 0.0)
    per = omega * omega * stats.s2() / y * fpc
    return jnp.sum(per)


def estimate_sum(stats: StratumStats) -> Estimate:
    """Eqs. 2–3: ``SUM = Σ_i W_i Σ_j I_ij`` with Eq. 6 variance."""
    c = stats.counts.astype(jnp.float32)
    n = jnp.maximum(stats.taken, 1).astype(jnp.float32)
    w = jnp.where(stats.counts > stats.taken, c / n, 1.0)
    return Estimate(value=jnp.sum(w * stats.sums), variance=var_sum(stats))


def estimate_mean(stats: StratumStats) -> Estimate:
    """Eq. 4 / Eq. 8 with Eq. 9 variance."""
    total = jnp.maximum(jnp.sum(stats.counts), 1).astype(jnp.float32)
    return Estimate(value=estimate_sum(stats).value / total,
                    variance=var_mean(stats))


def estimate_counts(n: jax.Array, counts: jax.Array,
                    taken: jax.Array) -> Estimate:
    """Vectorized per-cell COUNT estimates (Eqs. 2–3, 6 on indicators).

    ``n [S, B]`` is the number of *sampled* items of stratum ``s`` falling
    in cell ``b`` (a histogram bin, a candidate heavy-hitter key, ...).
    Each cell is an independent linear query on its 0/1 indicator, whose
    per-stratum moments are ``sums = sumsqs = n`` — so the whole ``[B]``
    vector of estimates and Eq. 6 variances comes out of one broadcasted
    pass instead of a Python loop over cells.
    """
    n = n.astype(jnp.float32)
    c = counts.astype(jnp.float32)[:, None]                  # [S, 1]
    y = jnp.maximum(taken, 1).astype(jnp.float32)[:, None]   # [S, 1]
    w = jnp.where(counts[:, None] > taken[:, None], c / y, 1.0)
    value = jnp.sum(w * n, axis=0)                           # [B]
    # Indicator variance: ss = Σ1² − Y·mean² = n − n²/Y  (Eq. 7 on 0/1s).
    ss = jnp.maximum(n - n * n / y, 0.0)
    s2 = jnp.where(taken[:, None] > 1, ss / jnp.maximum(y - 1.0, 1.0), 0.0)
    per = c * jnp.maximum(c - y, 0.0) * s2 / y               # Eq. 6 per cell
    return Estimate(value=value, variance=jnp.sum(per, axis=0))


def _group_sum(x: jax.Array, group_ids: jax.Array,
               num_groups: int) -> jax.Array:
    return jnp.zeros((num_groups,), x.dtype).at[group_ids].add(x)


def estimate_sum_grouped(stats: StratumStats, group_ids: jax.Array,
                         num_groups: int) -> Estimate:
    """Per-group SUM estimates (Eqs. 2–3, 6) over a partition of cells.

    ``group_ids [G]`` assigns each stratum cell to one of ``num_groups``
    disjoint windows (e.g. the per-key windows: cells grouped by their
    stratum key). Every group is its own stratified estimate — cells are
    independently sampled, so Eq. 5 applies per group exactly as it does
    for the merged window — and the whole vector comes out of one
    segment-sum pass. Returns a vector :class:`Estimate` ``[num_groups]``.
    """
    c = stats.counts.astype(jnp.float32)
    y = jnp.maximum(stats.taken, 1).astype(jnp.float32)
    w = jnp.where(stats.counts > stats.taken, c / y, 1.0)
    per_var = c * jnp.maximum(c - y, 0.0) * stats.s2() / y   # Eq. 6 per cell
    return Estimate(
        value=_group_sum(w * stats.sums, group_ids, num_groups),
        variance=_group_sum(per_var, group_ids, num_groups))


def estimate_mean_grouped(stats: StratumStats, group_ids: jax.Array,
                          num_groups: int) -> Estimate:
    """Per-group MEAN estimates (Eq. 4 / Eq. 8 with Eq. 9 variance).

    The stratum weights ``ω_i = C_i / C_group`` are normalized within
    each group, so each entry equals :func:`estimate_mean` evaluated on
    that group's cells alone. Groups with no arrivals report 0 ± 0.
    """
    c = stats.counts.astype(jnp.float32)
    tot = jnp.maximum(_group_sum(c, group_ids, num_groups), 1.0)
    omega = c / tot[group_ids]
    y = jnp.maximum(stats.taken, 1).astype(jnp.float32)
    w = jnp.where(stats.counts > stats.taken, c / y, 1.0)
    value = _group_sum(w * stats.sums, group_ids, num_groups) / tot
    fpc = jnp.where(c > 0, jnp.maximum(c - y, 0.0) / jnp.maximum(c, 1.0),
                    0.0)
    per = omega * omega * stats.s2() / y * fpc                # Eq. 9 per cell
    return Estimate(value=value,
                    variance=_group_sum(per, group_ids, num_groups))


def merge_stats(*stats: StratumStats) -> StratumStats:
    """Concatenate independent stratum summaries (Eq. 5: variances add).

    Used to merge (a) the per-interval states of a sliding window and (b)
    the per-worker local summaries of the distributed execution — in both
    cases every (source, partition) cell is an independently-sampled stratum.
    """
    return StratumStats(
        counts=jnp.concatenate([s.counts for s in stats]),
        taken=jnp.concatenate([s.taken for s in stats]),
        sums=jnp.concatenate([s.sums for s in stats]),
        sumsqs=jnp.concatenate([s.sumsqs for s in stats]),
    )


def required_sample_size_mean(
    counts: jax.Array,
    s2: jax.Array,
    target_half_width: jax.Array,
    z: float = 2.0,
    min_per_stratum: int = 8,
    max_per_stratum: Optional[int] = None,
) -> jax.Array:
    """Neyman allocation solving Eq. 9 for a target CI half-width on MEAN.

    Given last window's per-stratum sizes ``C_i`` and spreads ``s_i²``,
    returns the per-stratum ``N_i`` whose total is minimal subject to
    ``z·sqrt(Var(MEAN)) <= target_half_width``. This is the paper's "virtual
    cost function" instantiated for an accuracy budget (§7-I).
    """
    c = counts.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(c), 1.0)
    s = jnp.sqrt(jnp.maximum(s2, 0.0))
    v_target = (target_half_width / z) ** 2
    # n_total for Neyman: n = (Σ ω_i s_i)² / (V + Σ ω_i s_i² / C_total)
    omega = c / total
    a = jnp.sum(omega * s)
    b = jnp.sum(omega * omega * s2 / jnp.maximum(c, 1.0))  # fpc correction
    n_total = (a * a) / jnp.maximum(v_target + b, 1e-20)
    alloc = n_total * jnp.where(a > 0, omega * s / jnp.maximum(a, 1e-20),
                                1.0 / counts.shape[0])
    alloc = jnp.ceil(alloc).astype(jnp.int32)
    alloc = jnp.maximum(alloc, min_per_stratum)
    alloc = jnp.minimum(alloc, jnp.maximum(counts, min_per_stratum))
    if max_per_stratum is not None:
        alloc = jnp.minimum(alloc, max_per_stratum)
    return alloc
