"""Distributed OASRS execution — paper §3.2 "Distributed execution".

Design (mapped from the paper's w-worker scheme to an SPMD mesh):

* Each shard along the ``data`` (and ``pod``) mesh axes owns a *local*
  OASRS state: reservoirs of size ``N_i / w`` and local counters. The
  ingestion path (``local_update``) contains **zero collectives** — this is
  the paper's "no synchronization among workers" property, checkable in the
  compiled HLO (``tests/test_distributed.py`` asserts the update program has
  no all-reduce).
* A query performs ONE ``psum`` of O(strata) scalars at window close: each
  (worker × stratum) cell is an independently-sampled stratum, so partial
  estimates and partial variances both sum exactly (Eq. 5).
* Straggler mitigation / elasticity (beyond-paper, DESIGN.md §3.4): a shard
  that misses the window deadline contributes ``alive = 0``; surviving
  partials are inflated by ``w_total / w_alive``. Because the stream
  aggregator round-robins items across shards, shard loads are exchangeable
  and the inflated estimator stays unbiased — only variance grows, which the
  error bound reports honestly.

These helpers are written to be called INSIDE ``shard_map``; they take the
mesh axis name(s) the stream is partitioned over.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import error as err
from repro.core import oasrs
from repro.core import quantile as qt
from repro.core import sketches as sk
from repro.kernels import ops

AxisNames = Union[str, Sequence[str]]


def _psum(x, axis_names: AxisNames):
    return jax.lax.psum(x, axis_names)


def _psum_packed(parts, axis_names: AxisNames) -> list:
    """ONE psum over several f32 partials: they travel packed in a single
    flat vector (a tuple psum may lower to one collective per leaf), and
    come back in their own shapes."""
    parts = [jnp.asarray(p, jnp.float32) for p in parts]
    flat = _psum(jnp.concatenate([p.reshape(-1) for p in parts]),
                 axis_names)
    out, at = [], 0
    for p in parts:
        out.append(flat[at:at + p.size].reshape(p.shape))
        at += p.size
    return out


def local_update(state: oasrs.OASRSState, stratum_ids: jax.Array,
                 payload, mask=None,
                 backend: Optional[str] = None) -> oasrs.OASRSState:
    """Per-shard ingestion — intentionally just the local chunk fold.

    Named separately to make the no-collective property a grep-able,
    testable contract of the module. ``backend`` selects the fold
    implementation (``"jnp"`` | ``"pallas"`` | ``None`` = auto, Pallas
    on TPU); all backends are bitwise-identical.
    """
    return oasrs.update_chunk(state, stratum_ids, payload, mask,
                              backend=backend)


def global_sum(local_stats: err.StratumStats, axis_names: AxisNames,
               alive: Optional[jax.Array] = None) -> err.Estimate:
    """Merge per-shard partial SUM estimates with one psum.

    ``alive``: scalar 0/1 per shard (1 = met the window deadline).
    """
    local = err.estimate_sum(local_stats)
    return _merge_partials(local, axis_names, alive)


def global_mean(local_stats: err.StratumStats, axis_names: AxisNames,
                alive: Optional[jax.Array] = None) -> err.Estimate:
    """Merge per-shard partials into the global MEAN estimate.

    MEAN = SUM / ΣC needs the global item count; both numerator and
    denominator ride the same psum (still one fused collective).
    """
    local_sum = err.estimate_sum(local_stats)
    local_count = jnp.sum(local_stats.counts).astype(jnp.float32)
    if alive is None:
        alive = jnp.float32(1.0)
    a = alive.astype(jnp.float32)
    num, var, cnt, n_alive, n_total = _psum_packed(
        (a * local_sum.value, a * a * local_sum.variance, a * local_count,
         a, jnp.float32(1.0)), axis_names)
    inflate = n_total / jnp.maximum(n_alive, 1.0)
    total = jnp.maximum(cnt * inflate, 1.0)
    # Var(MEAN) = Var(SUM)/totalᒾ for the stratified estimator (ω_i fold-in).
    return err.Estimate(value=num * inflate / total,
                        variance=var * inflate * inflate / (total * total))


def _merge_partials(local: err.Estimate, axis_names: AxisNames,
                    alive: Optional[jax.Array]) -> err.Estimate:
    if alive is None:
        alive = jnp.float32(1.0)
    a = alive.astype(jnp.float32)
    val, var, n_alive, n_total = _psum_packed(
        (a * local.value, a * a * local.variance, a, jnp.float32(1.0)),
        axis_names)
    inflate = n_total / jnp.maximum(n_alive, 1.0)
    # Dropping shards multiplies the estimator by w/w_alive: the variance of
    # the inflated estimator picks up inflate² on the surviving partials.
    return err.Estimate(value=val * inflate,
                        variance=var * inflate * inflate)


# ---------------------------------------------------------------------------
# Nonlinear queries: single-psum merges of per-shard partial sketches.
# Each keeps the ingest contract intact — collectives appear only at query
# time, and each query issues exactly ONE psum (of one packed vector).
# ---------------------------------------------------------------------------

def global_histogram(view, edges: jax.Array, axis_names: AxisNames,
                     alive: Optional[jax.Array] = None,
                     use_pallas: bool = False) -> err.Estimate:
    """Merge per-shard per-bin COUNT estimates with one psum.

    ``view`` is the shard-local :class:`~repro.core.quantile.SampleView`;
    each (shard × stratum) cell is an independently-sampled stratum, so
    the per-bin values and Eq. 6 variances both sum exactly (Eq. 5).
    """
    local = qt.cell_counts(view, edges, use_pallas=use_pallas)
    return _merge_partials(local, axis_names, alive)


def global_key_counts(view, keys: jax.Array, axis_names: AxisNames,
                      alive: Optional[jax.Array] = None) -> err.Estimate:
    """Merge per-shard per-key COUNT estimates (heavy-hitter phase 2).

    ``keys`` must be replicated across shards (candidates come from any
    shard's local top-k, domain knowledge, or the previous window). The
    per-key frequency is a linear query, so values and variances merge
    with one psum.
    """
    local = sk.key_counts(view, keys)
    return _merge_partials(local, axis_names, alive)


def global_quantile(view, qs, value_range, axis_names,
                    num_bins: int = 2048,
                    num_replicates: int = 0,
                    key: Optional[jax.Array] = None) -> err.Estimate:
    """Global quantiles from per-shard weighted histograms — one psum.

    Each shard bins its HT-weighted sample over the (replicated)
    ``value_range = (lo, hi)`` bracket into ``num_bins`` fine bins; the
    single psum merges ``[R+1, B]`` histograms (replicate 0 is the actual
    sample, the rest stratified-bootstrap resamples), the below-range
    mass and the total weight in one collective. Every shard then inverts
    the identical global CDF, so the result is replicated.

    ``value_range`` typically comes from the previous window (or domain
    bounds); mass outside the bracket is still accounted for in
    ``below``/``total``, and targets beyond the bracket clamp to its
    edges. Resolution is ``(hi − lo) / num_bins``.
    """
    qs = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    lo, hi = value_range
    edges = lo + (hi - lo) * jnp.linspace(0.0, 1.0, num_bins + 1)
    g, n = view.values.shape
    w = jnp.broadcast_to(view.weights()[:, None], (g, n))
    valid = view.slot_mask()
    gid = jnp.broadcast_to(
        jnp.arange(g, dtype=jnp.int32)[:, None], (g, n))

    def binned(values):
        # Same fused pass (and bin convention) as the local "hist" path.
        wv = jnp.where(valid, w, 0.0)
        whist, _ = ops.weighted_histogram(
            values.reshape(-1), gid.reshape(-1), w.reshape(-1),
            valid.reshape(-1), edges, g, use_pallas=False)
        hist = jnp.sum(whist, axis=0)                         # [B]
        below = jnp.sum(jnp.where(values < lo, wv, 0.0))
        return hist, below, jnp.sum(wv)

    h0, b0, t0 = binned(view.values)
    hists, belows, totals = h0[None], b0[None], t0[None]
    if num_replicates > 0:
        if key is None:
            raise ValueError("pass key= for bootstrap replicates")
        reps = jax.vmap(
            lambda k: binned(qt.bootstrap_resample(view, k)))(
                jax.random.split(key, num_replicates))
        hists = jnp.concatenate([hists, reps[0]])
        belows = jnp.concatenate([belows, reps[1]])
        totals = jnp.concatenate([totals, reps[2]])

    g_hist, g_below, g_total = _psum_packed((hists, belows, totals),
                                            axis_names)

    invert = jax.vmap(lambda h, b, t: qt.invert_weighted_cdf(
        h, edges, b, qs * jnp.maximum(t, 1e-20)))
    values = invert(g_hist, g_below, g_total)                 # [R+1, Q]
    variance = (jnp.var(values[1:], axis=0, ddof=1)
                if num_replicates > 1 else jnp.zeros_like(values[0]))
    return err.Estimate(value=values[0], variance=variance)


def sts_global_counts(local_counts: jax.Array,
                      axis_names: AxisNames) -> jax.Array:
    """The STS baseline's pass-1 synchronization barrier (all-reduce).

    Exists so benchmarks can contrast the collective footprint of STS
    against the collective-free OASRS ingestion path.
    """
    return _psum(local_counts, axis_names)


def split_capacity(total_capacity: jax.Array, num_shards: int) -> jax.Array:
    """Per-worker reservoir size ``N_i / w`` (ceil so Σ ≥ N_i)."""
    return jnp.maximum(
        (total_capacity + num_shards - 1) // num_shards, 1).astype(jnp.int32)


def _aux_rows(aux_words: int, width: int) -> int:
    """Rows of ``width`` words that hold ``aux_words`` words, padded."""
    return -(-aux_words // width)


def gather_words(cells: int, slots: int, aux_words: int) -> int:
    """u32 words one device contributes to :func:`gather_cells`: its
    ``cells`` rows of ``slots + 2`` words (samples, count, taken), then
    the ``aux_words`` padded to whole rows of that width."""
    width = slots + 2
    return (cells + _aux_rows(aux_words, width)) * width


def gather_cells(view: qt.SampleView, aux: jax.Array,
                 axis_name: str, num_shards: int) -> tuple:
    """The mesh emission merge: ONE collective per emission.

    Called inside ``shard_map``.  Each device holds its shard's local
    merged view — ``values [G, N]`` f32, ``counts``/``taken [G]`` i32 —
    plus a flat u32 ``aux`` vector (PRNG lead key, slot→interval
    assignments, liveness bits…).  A single tiled ``all_gather`` over
    ``axis_name`` concatenates the shards in shard-index order,
    reproducing bitwise the vmap oracle's host-side
    ``[W, G, N] → [W·G, N]`` reshape-concat, with the aux payload riding
    the same collective in padded tail rows — so every device sees every
    shard's aux (e.g. shard 0's lead key seeds the emission PRNG
    identically everywhere; under shard_map each device would otherwise
    only see its OWN shard's).

    Every word travels as u32 through ``bitcast_convert_type``, so the
    concatenations and the collective move integer bits only. Packed as
    f32, a TPU concatenates with a float ``max`` that flushes the small
    integers' bit patterns (denormals) to zero and rewrites the negative
    ones (NaNs).

    Returns ``(merged_view [W·G, N], aux_all [W, A] u32)``.
    """
    g, n = view.values.shape
    u32 = jnp.uint32
    width = n + 2

    def words(x, dtype):
        return jax.lax.bitcast_convert_type(x.astype(dtype), u32)

    packed = jnp.concatenate(
        [words(view.values, jnp.float32),
         words(view.counts, jnp.int32)[:, None],
         words(view.taken, jnp.int32)[:, None]], axis=-1)   # [G, N+2]

    a = aux.shape[0]
    rows = _aux_rows(a, width)
    aux_rows = jnp.concatenate(
        [aux.astype(u32), jnp.zeros((rows * width - a,), u32)]
    ).reshape(rows, width)
    packed = jnp.concatenate([packed, aux_rows], axis=0)    # [G+rows, N+2]

    gathered = jax.lax.all_gather(
        packed, axis_name, axis=0, tiled=True)
    gathered = gathered.reshape(num_shards, g + rows, width)

    cells = gathered[:, :g, :].reshape(num_shards * g, width)

    def back(x, dtype):
        return jax.lax.bitcast_convert_type(x, dtype)

    merged = qt.SampleView(values=back(cells[:, :n], jnp.float32),
                           counts=back(cells[:, n], jnp.int32),
                           taken=back(cells[:, n + 1], jnp.int32))
    aux_all = gathered[:, g:, :].reshape(num_shards, rows * width)[:, :a]
    return merged, aux_all
