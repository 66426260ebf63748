"""Nonlinear approximate queries I: weighted quantiles over OASRS samples.

Quantiles are **not** linear queries, so the closed-form stratified
variance (Eq. 6) does not apply. The estimator stack here is:

* **Point estimate** — the generalized inverse of the HT-weighted
  empirical CDF. Each sampled item of stratum ``i`` carries weight
  ``W_i = C_i / Y_i`` (Eq. 1), which makes
  ``F̂(t) = Σ_k w_k·1[x_k ≤ t] / Σ_i C_i`` an unbiased estimator of the
  stream CDF; the q-quantile is ``inf{t : F̂(t) ≥ q}``. Two
  interchangeable, fully-jitted evaluation schemes:

  - ``weighted_quantile`` — sorted-cumulative-weight: one stable sort of
    the slot buffer that carries the weights as its payload, then
    ``searchsorted`` on the cumulative weights.
  - ``quantile_refine`` — sort-free histogram refinement: R rounds of
    B-bin weighted histograms (the ``weighted_hist`` Pallas kernel is the
    inner loop) that shrink the bracket by B× per round, then linear
    interpolation inside the final bracket. Resolution after R rounds is
    ``range / Bᴿ``; no data-dependent shapes, so it scans/vmaps.

* **Error bounds** — a *stratified bootstrap*: reservoirs are resampled
  with replacement **within each stratum** (preserving the stratified
  design) using JAX's counter-based PRNG (vmapped ``threefry`` keys — no
  host randomness), the estimator is re-evaluated per replicate, and the
  replicate variance is reported through the standard
  :class:`~repro.core.error.Estimate` so the 68-95-99.7 interval
  machinery applies unchanged.

All entry points operate on a :class:`SampleView` — the ``(values,
counts, taken)`` projection of one OASRS state, of a merged sliding
window (``repro.core.window.sample_view``), or of any other collection of
independently-sampled strata cells.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import error as err
from repro.core.oasrs import OASRSState
from repro.kernels import ops
from repro.utils import Pytree, dataclass_pytree

Extract = Callable[[Pytree], jax.Array]

_BIG = 3.0e38   # +inf stand-in that survives float32 arithmetic


@dataclass_pytree
@dataclasses.dataclass
class SampleView:
    """Weighted-sample projection: ``G`` independently-sampled cells.

    ``values [G, N]`` are slot payloads, ``counts [G]`` the stream
    arrivals ``C_g`` and ``taken [G]`` the live sample sizes ``Y_g`` of
    each cell (slots ``>= Y_g`` are dead). For a single OASRS state the
    cells are its strata; for a sliding window they are the
    (interval × stratum) cells — in both cases each cell is an
    independently-sampled stratum, so every estimator here treats them
    uniformly.
    """
    values: jax.Array   # [G, N] f32
    counts: jax.Array   # [G] int32
    taken: jax.Array    # [G] int32

    def weights(self) -> jax.Array:
        """Per-cell HT weight ``W_g`` (Eq. 1)."""
        c = self.counts.astype(jnp.float32)
        y = jnp.maximum(self.taken, 1).astype(jnp.float32)
        return jnp.where(self.counts > self.taken, c / y, 1.0)

    def slot_mask(self) -> jax.Array:
        slots = jnp.arange(self.values.shape[1], dtype=jnp.int32)[None, :]
        return slots < self.taken[:, None]

    def flat(self):
        """``(x, w, valid, cell_ids)`` flattened over all slots."""
        g, n = self.values.shape
        x = self.values.reshape(-1)
        w = jnp.broadcast_to(self.weights()[:, None], (g, n)).reshape(-1)
        valid = self.slot_mask().reshape(-1)
        gid = jnp.broadcast_to(
            jnp.arange(g, dtype=jnp.int32)[:, None], (g, n)).reshape(-1)
        return x, w, valid, gid


def sample_view(state: OASRSState,
                extract: Extract = lambda v: v) -> SampleView:
    """Project one OASRS state onto its weighted sample."""
    xs = extract(state.values)
    if xs.shape[:2] != (state.num_strata, state.max_capacity):
        raise ValueError(
            f"extract must return [S, N_max]-leading array, got {xs.shape}")
    return SampleView(values=xs.astype(jnp.float32), counts=state.counts,
                      taken=state.taken())


# ---------------------------------------------------------------------------
# Point estimators.
# ---------------------------------------------------------------------------

def weighted_quantile(x: jax.Array, w: jax.Array, valid: jax.Array,
                      qs: jax.Array) -> jax.Array:
    """Sorted-cumulative-weight inverse of the weighted empirical CDF.

    ``x, w, valid`` are flat slot buffers; ``qs [Q]`` in ``(0, 1]``.
    Returns the ``[Q]`` sample quantiles (exact inverse of ``F̂``).
    """
    qs = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    xs, ws = jax.lax.sort(
        (jnp.where(valid, x, _BIG), jnp.where(valid, w, 0.0)),
        num_keys=1, is_stable=True)
    cw = jnp.cumsum(ws)
    total = jnp.maximum(cw[-1], 1e-20)
    idx = jnp.searchsorted(cw, qs * total, side="left")
    return xs[jnp.clip(idx, 0, xs.shape[0] - 1)]


def quantile_refine(view: SampleView, qs: jax.Array, num_bins: int = 32,
                    num_steps: int = 4, use_pallas: bool = False,
                    block_m: int = 256) -> jax.Array:
    """Sort-free histogram-refinement quantile estimator.

    Per refinement round, one fused weighted histogram
    (:func:`repro.kernels.ops.weighted_histogram`) of the whole slot
    buffer over the current bracket locates the bin holding the target
    cumulative weight; the bracket narrows to that bin. The carried
    ``below`` mass keeps the invariant ``below = Ŵ{x < lo}`` exact, so
    the only approximation is the final within-bin interpolation.
    """
    qs = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    x, w, valid, gid = view.flat()
    wv = jnp.where(valid, w, 0.0)
    total = jnp.sum(wv)
    xv = jnp.where(valid, x, _BIG)
    lo0 = jnp.min(xv)
    hi0 = jnp.max(jnp.where(valid, x, -_BIG))
    num_cells = view.values.shape[0]

    def hist(edges):
        whist, _ = ops.weighted_histogram(
            x, gid, w, valid, edges, num_cells,
            use_pallas=use_pallas, block_m=block_m)
        return jnp.sum(whist, axis=0)                        # [B]

    def one_q(q):
        target = q * total

        def step(carry, _):
            lo, hi, below = carry
            span = jnp.maximum(hi - lo, 1e-20)
            edges = lo + span * jnp.linspace(0.0, 1.0, num_bins + 1)
            h = hist(edges)
            cum = below + jnp.cumsum(h)
            b = jnp.searchsorted(cum, target, side="left")
            b = jnp.clip(b, 0, num_bins - 1)
            new_below = below + jnp.where(b > 0, cum[b - 1] - below, 0.0)
            return (edges[b], edges[b + 1], new_below), h[b]

        (lo, hi, below), masses = jax.lax.scan(
            step, (lo0, hi0, 0.0), None, length=num_steps)
        frac = (target - below) / jnp.maximum(masses[-1], 1e-20)
        return jnp.clip(lo + jnp.clip(frac, 0.0, 1.0) * (hi - lo), lo0, hi0)

    return jax.vmap(one_q)(qs)


def cell_counts(view: SampleView, edges: jax.Array,
                use_pallas: bool = False) -> err.Estimate:
    """Per-bin COUNT estimates of a weighted sample (Eq. 6 per bin).

    The single shared entry point behind ``query.query_histogram``,
    ``window.query_histogram`` and ``distributed.global_histogram``: one
    fused ``weighted_histogram`` pass over the flattened slots, then the
    vectorized indicator-variance machinery.
    """
    from repro.kernels import ops
    x, _, valid, gid = view.flat()
    _, n_gb = ops.weighted_histogram(
        x, gid, jnp.ones_like(x), valid, edges, view.values.shape[0],
        use_pallas=use_pallas)
    return err.estimate_counts(n_gb, view.counts, view.taken)


def invert_weighted_cdf(hist: jax.Array, edges: jax.Array,
                        below: jax.Array, targets: jax.Array) -> jax.Array:
    """Invert a binned weighted CDF with within-bin interpolation.

    ``hist [B]`` is the weighted mass per bin of ``edges [B+1]``,
    ``below`` the mass strictly left of ``edges[0]``, ``targets [Q]``
    absolute cumulative-weight targets. Shared by the refinement loop and
    the distributed single-``psum`` quantile merge.
    """
    targets = jnp.atleast_1d(targets)
    cum = below + jnp.cumsum(hist)
    b = jnp.clip(jnp.searchsorted(cum, targets, side="left"),
                 0, hist.shape[0] - 1)
    prev = jnp.where(b > 0, cum[jnp.maximum(b - 1, 0)], below)
    frac = jnp.clip((targets - prev) / jnp.maximum(hist[b], 1e-20),
                    0.0, 1.0)
    return edges[b] + frac * (edges[b + 1] - edges[b])


# ---------------------------------------------------------------------------
# Stratified bootstrap.
# ---------------------------------------------------------------------------

def bootstrap_resample(view: SampleView, key: jax.Array) -> jax.Array:
    """One bootstrap replicate: resample slots within each cell.

    Returns replicate values ``[G, N]``; counts/taken/weights are design
    constants of the replicate (the stratified design is preserved).
    """
    g, n = view.values.shape
    idx = jax.random.randint(key, (g, n), 0,
                             jnp.maximum(view.taken, 1)[:, None])
    return jnp.take_along_axis(view.values, idx, axis=1)


def bootstrap_quantiles(view: SampleView, qs: jax.Array,
                        num_replicates: int, key: jax.Array) -> jax.Array:
    """``[R, Q]`` bootstrap replicates of the weighted quantiles."""
    qs = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    w = jnp.broadcast_to(view.weights()[:, None],
                         view.values.shape).reshape(-1)
    valid = view.slot_mask().reshape(-1)

    def one(k):
        xb = bootstrap_resample(view, k).reshape(-1)
        return weighted_quantile(xb, w, valid, qs)

    return jax.vmap(one)(jax.random.split(key, num_replicates))


# ---------------------------------------------------------------------------
# Public query.
# ---------------------------------------------------------------------------

def query_quantile(source, qs, extract: Extract = lambda v: v,
                   method: str = "sort", num_bins: int = 32,
                   num_steps: int = 4, num_replicates: int = 64,
                   key: Optional[jax.Array] = None,
                   use_pallas: bool = False) -> err.Estimate:
    """Approximate stream quantiles with bootstrap error bounds.

    Args:
      source: an :class:`OASRSState` or a prebuilt :class:`SampleView`.
      qs: ``[Q]`` quantile levels in ``(0, 1]``.
      method: ``"sort"`` (sorted cumulative weights) or ``"hist"``
        (kernel-backed histogram refinement).
      num_replicates: bootstrap replicates for the variance (0 disables
        the bootstrap and reports zero variance).
      key: PRNG key for the bootstrap; defaults to a fold of the state
        key so results are deterministic per ingest history.

    Returns:
      ``Estimate`` with ``value [Q]`` and bootstrap ``variance [Q]``;
      ``interval(0.95)`` is the bootstrap-normal 95% CI.
    """
    if isinstance(source, OASRSState):
        if key is None:
            key = jax.random.fold_in(source.key, 0x51A17)
        view = sample_view(source, extract)
    else:
        view = source
        if key is None and num_replicates > 0:
            raise ValueError("pass key= when querying a bare SampleView")
    qs = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    if method == "sort":
        x, w, valid, _ = view.flat()
        value = weighted_quantile(x, w, valid, qs)
    elif method == "hist":
        value = quantile_refine(view, qs, num_bins=num_bins,
                                num_steps=num_steps, use_pallas=use_pallas)
    else:
        raise ValueError(f"unknown method {method!r}")
    if num_replicates > 0:
        reps = bootstrap_quantiles(view, qs, num_replicates, key)
        variance = jnp.var(reps, axis=0, ddof=1)
    else:
        variance = jnp.zeros_like(value)
    return err.Estimate(value=value, variance=variance)
