"""Standing-query registry: many queries, ONE shared sample pass.

A stream processor serves *standing* queries: registered once, answered
at every emission. Evaluating each query independently would re-project
the window's reservoir ring once per query (the dominant cost — the ring
is ``K·S·N_max`` slots). The registry instead materializes the merged
:class:`~repro.core.quantile.SampleView` and the fused
:class:`~repro.core.error.StratumStats` **once per emission** and lets
every registered query read from that shared pass:

* linear queries (``sum``/``mean``/``count``) consume the shared stats
  (Eqs. 5–9 closed-form bounds);
* ``histogram`` / ``quantile`` / ``heavy_hitters`` / ``distinct`` consume
  the shared view (Eq. 6 per bin / bootstrap bounds, per the README
  query table).

``evaluate`` is pure ``jnp`` end-to-end, so both executors jit it as part
of their emission step, and its results are pytrees (``Estimate`` /
``HeavyHitters``) keyed by query name.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import error as err
from repro.core import quantile as qt
from repro.core import sketches as sk
from repro.core import window as win
from repro.utils import fold_in_str

KINDS = ("sum", "mean", "count", "histogram", "quantile",
         "heavy_hitters", "distinct")

#: Window kinds. ``merged`` is the classic K-interval tumbling window
#: (all cells of the ring, Eq. 5 merge).  ``per_key`` answers per stratum
#: key: each key's cells stay separate, so the result is a VECTOR
#: Estimate ``[S]`` — per-key tumbling windows over the same ring (under
#: watermark-driven emission the evaluation is restricted to the closed
#: interval, i.e. true per-key tumbling panes).  ``session`` answers per
#: key over that key's *current gap-timeout session* (see
#: ``core.window.session_intervals``), also a vector ``[S]``.
WINDOWS = ("merged", "per_key", "session")

#: Kinds evaluable under per-key / session windows: the linear kinds
#: (closed-form Eq. 5–9 per group) plus quantile (per-key stratified
#: bootstrap, vmapped over keys). Heavy hitters / distinct stay
#: merged-only — their sketches have no per-key decomposition here.
GROUPED_KINDS = ("sum", "mean", "count", "quantile")

Result = Union[err.Estimate, sk.HeavyHitters]


@dataclasses.dataclass(frozen=True)
class StandingQuery:
    """One registered query (static spec — hashable, closed over by jit)."""
    name: str
    kind: str
    predicate: Optional[Callable[[jax.Array], jax.Array]] = None  # count
    edges: Optional[tuple] = None          # histogram bin edges
    qs: Optional[tuple] = None             # quantile levels
    k: int = 8                             # heavy hitters
    num_replicates: int = 32               # bootstrap replicates
    method: str = "sort"                   # quantile estimator
    window: str = "merged"                 # merged | per_key | session
    session_gap: Optional[float] = None    # session gap (event-time units)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown query kind {self.kind!r}; "
                             f"one of {KINDS}")
        if self.kind == "count" and self.predicate is None:
            raise ValueError("count query needs predicate=")
        if self.kind == "histogram" and self.edges is None:
            raise ValueError("histogram query needs edges=")
        if self.kind == "quantile" and self.qs is None:
            raise ValueError("quantile query needs qs=")
        if self.window not in WINDOWS:
            raise ValueError(f"unknown window kind {self.window!r}; "
                             f"one of {WINDOWS}")
        if self.window != "merged" and self.kind not in GROUPED_KINDS:
            raise ValueError(
                f"{self.kind!r} queries support only the merged window "
                f"(per_key/session need a per-group estimator; "
                f"available for {GROUPED_KINDS})")
        if self.window == "session" and self.session_gap is None:
            raise ValueError("session window needs session_gap=")
        if self.session_gap is not None and self.session_gap <= 0:
            raise ValueError(
                f"session_gap must be > 0, got {self.session_gap}")


@dataclasses.dataclass
class EmissionContext:
    """Cell-structure context the grouped window kinds evaluate against.

    The merged :class:`~repro.core.quantile.SampleView` flattens the ring
    to anonymous cells; per-key and session windows additionally need to
    know the (shard × interval × stratum) layout, the slots' event
    interval ids and which cells saw traffic.  Executors build one per
    emission from live (traced) state — this is NOT a jit boundary type,
    just a named bundle.

    ``view``/``stats`` here are always the FULL window's shared pass:
    under watermark-driven emission the base view handed to
    ``evaluate_view`` holds only the closed interval's cells, which is
    exactly what per-key tumbling panes want, while session windows keep
    reading the whole ring (a session spans intervals by definition).
    """
    num_intervals: int
    num_strata: int
    num_shards: int
    interval_span: float
    slot_interval: jax.Array     # [K] i32 event interval id per slot
    activity: jax.Array          # [K, S] bool — live cells with items
    view: qt.SampleView          # full merged view (unrestricted)
    stats: err.StratumStats      # full merged stats (unrestricted)

    def gap_intervals(self, session_gap: float) -> int:
        """Event-time gap resolved to ring-interval granularity."""
        import math
        return max(1, int(math.ceil(session_gap / self.interval_span)))

    def key_of_cell(self, num_cells: int) -> jax.Array:
        """``[G]`` stratum key of each flattened cell (shard-tiled)."""
        return jnp.arange(num_cells, dtype=jnp.int32) % self.num_strata

    def tile_cells(self, mask_ks: jax.Array) -> jax.Array:
        """Broadcast a ``[K, S]`` cell mask over shards to ``[G]``."""
        w = self.num_shards
        full = jnp.broadcast_to(mask_ks[None], (w,) + mask_ks.shape)
        return full.reshape(-1)


def _tolist(x):
    a = np.asarray(x)
    return a.item() if a.ndim == 0 else a.tolist()


def _hw95(est) -> object:
    """95% half-width in HOST numpy — the same ``z·sqrt(max(var, 0))``
    as ``Estimate.error_bound(0.95)`` (asserted equal in the obs tests)
    without its per-call jnp dispatches: the telemetry path runs once
    per emission and must stay off the device queue."""
    z = err.Z_FOR_CONFIDENCE[0.95]
    var = np.asarray(est.variance, np.float32)
    return _tolist(z * np.sqrt(np.maximum(var, 0.0)))


def result_summary(results: Dict[str, Result]) -> dict:
    """JSON-serializable view of one emission's answers — value + 95%
    CI half-width per query (vector answers stay vectors).  This is what
    ``obs/events.py`` emission events carry: the accuracy time series is
    readable from the log without unpickling any runtime type.  Blocks
    on the results; called where the emission already synchronized."""
    out = {}
    for name, r in results.items():
        if isinstance(r, sk.HeavyHitters):
            out[name] = {"kind": "heavy_hitters",
                         "keys": _tolist(r.keys),
                         "counts": _tolist(r.estimate.value),
                         "hw95": _hw95(r.estimate)}
        else:
            out[name] = {"kind": "estimate",
                         "value": _tolist(r.value),
                         "hw95": _hw95(r)}
    return out


def describe(registry: "QueryRegistry") -> list:
    """Static query-catalog description (the ``run_meta`` event)."""
    return [{"name": q.name, "kind": q.kind, "window": q.window}
            for q in registry.queries]


class QueryRegistry:
    """Ordered collection of standing queries over one value stream."""

    def __init__(self, queries: Sequence[StandingQuery] = ()):
        self._queries: list[StandingQuery] = list(queries)
        self._frozen = False
        # Rows of the sample view each query's estimator read in the
        # latest trace of ``evaluate_view`` (per key for a per-key or
        # session quantile): python ints from the traced shapes.
        self.rows_fed: Dict[str, int] = {}
        names = [q.name for q in self._queries]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate query names in {names}")

    def register(self, name: str, kind: str, **kw) -> "QueryRegistry":
        """Add a query (chainable). Must happen before an executor is
        built on this registry — executors close over the query list when
        tracing their steps, so a later register() would make emission
        result sets silently inconsistent. Executors freeze the registry
        at construction; register() after that raises."""
        if self._frozen:
            raise ValueError(
                "registry is frozen (an executor traced it); register "
                "every standing query before constructing executors")
        if any(q.name == name for q in self._queries):
            raise ValueError(f"query {name!r} already registered")
        self._queries.append(StandingQuery(name=name, kind=kind, **kw))
        return self

    def freeze(self) -> None:
        """Disallow further register() calls (executors call this)."""
        self._frozen = True

    @property
    def queries(self) -> tuple:
        return tuple(self._queries)

    def __len__(self) -> int:
        return len(self._queries)

    def evaluate(self, window: win.WindowState, key: jax.Array,
                 interval_span: float = 1.0) -> Dict[str, Result]:
        """Answer every registered query from one shared sample pass.

        ``key`` seeds the bootstrap paths (folded per query name so
        adding a query never perturbs another's replicates).  Outside the
        runtime the slots' event interval ids are unknown, so session
        windows fall back to recency ranks (``interval_span`` converts
        the gap); the executors pass real ids via their own context.
        """
        view = win.sample_view(window)                    # THE shared pass
        stats = err.stratum_stats_from_sample(
            view.values, view.counts, view.taken, view.slot_mask())
        k, s = window.intervals.counts.shape
        slot_interval = jnp.mod(
            jnp.arange(k, dtype=jnp.int32) - window.cursor,
            jnp.maximum(k, 1))
        ctx = EmissionContext(
            num_intervals=k, num_strata=s, num_shards=1,
            interval_span=interval_span, slot_interval=slot_interval,
            activity=win.activity_mask(window), view=view, stats=stats)
        return self.evaluate_view(view, stats, key, ctx=ctx)

    def evaluate_view(self, view: qt.SampleView, stats: err.StratumStats,
                      key: jax.Array,
                      ctx: Optional[EmissionContext] = None,
                      ) -> Dict[str, Result]:
        """Answer every query from an already-materialized shared pass.

        The executors call this directly: single-shard emissions pass the
        window's merged view; sharded emissions pass the (shard ×
        interval × stratum) concatenation (the Eq. 5 merge); watermark-
        driven emissions pass the closed interval's ``[W·S]`` rows of it.
        ``ctx`` supplies the cell structure the per-key/session window
        kinds group by — merged-only registries never need it.
        """
        out: Dict[str, Result] = {}
        sharded = ctx is not None and ctx.num_shards > 1
        for q in self._queries:
            if q.window == "merged":
                self.rows_fed[q.name] = view.counts.shape[0]
                out[q.name] = self._eval_merged(q, view, stats, key,
                                                sharded)
            else:
                if ctx is None:
                    raise ValueError(
                        f"query {q.name!r} has window={q.window!r}, which "
                        "needs an EmissionContext (cell structure); "
                        "evaluate through an executor or "
                        "QueryRegistry.evaluate")
                out[q.name] = self._eval_grouped(q, view, key, ctx)
        return out

    def _eval_merged(self, q: StandingQuery, view: qt.SampleView,
                     stats: err.StratumStats, key: jax.Array,
                     sharded: bool = False) -> Result:
        if q.kind == "sum":
            return err.estimate_sum(stats)
        if q.kind == "mean":
            return err.estimate_mean(stats)
        if q.kind == "count":
            ind = q.predicate(view.values).astype(jnp.float32)
            return err.estimate_sum(
                err.stratum_stats_from_sample(
                    ind, view.counts, view.taken, view.slot_mask(),
                    fixed_order=sharded))
        if q.kind == "histogram":
            return qt.cell_counts(view, jnp.asarray(q.edges, jnp.float32))
        if q.kind == "quantile":
            return qt.query_quantile(
                view, jnp.asarray(q.qs, jnp.float32), method=q.method,
                num_replicates=q.num_replicates,
                key=fold_in_str(key, q.name))
        if q.kind == "heavy_hitters":
            return sk.query_heavy_hitters(view, q.k)
        assert q.kind == "distinct"
        return sk.query_distinct(view, num_replicates=q.num_replicates,
                                 key=fold_in_str(key, q.name))

    def _eval_grouped(self, q: StandingQuery, view: qt.SampleView,
                      key: jax.Array, ctx: EmissionContext) -> Result:
        """Per-key / session evaluation: restrict, group by key, estimate.

        Per-key windows group the BASE view's cells by stratum key (under
        watermark emission the base view is already the closed interval —
        per-key tumbling panes). Session windows restrict the FULL ring
        to each key's current session first; the session mask is a pure
        function of ring activity, so nothing beyond the shared pass is
        touched.
        """
        s = ctx.num_strata
        if q.window == "session":
            smask = win.session_intervals(
                ctx.activity, ctx.slot_interval,
                ctx.gap_intervals(q.session_gap))
            base = win.restrict_view(ctx.view, ctx.tile_cells(smask))
        else:
            base = view
        gid = ctx.key_of_cell(base.counts.shape[0])
        sharded = ctx.num_shards > 1
        self.rows_fed[q.name] = base.counts.shape[0]
        gstats = err.stratum_stats_from_sample(
            base.values, base.counts, base.taken, base.slot_mask(),
            fixed_order=sharded)
        if q.kind == "sum":
            return err.estimate_sum_grouped(gstats, gid, s)
        if q.kind == "mean":
            return err.estimate_mean_grouped(gstats, gid, s)
        if q.kind == "count":
            ind = q.predicate(base.values).astype(jnp.float32)
            return err.estimate_sum_grouped(
                err.stratum_stats_from_sample(
                    ind, base.counts, base.taken, base.slot_mask(),
                    fixed_order=sharded),
                gid, s)
        assert q.kind == "quantile"
        # Per-key stratified bootstrap: each key keeps its own cells and
        # replicates (vmapped — one trace for all keys). Cell g belongs
        # to key g mod S, so ``[G, N] → [G/S, S, N]`` puts key k's cells
        # on index k of the middle axis, and each key's estimator reads
        # only its own G/S rows.
        qs = jnp.asarray(q.qs, jnp.float32)
        by_key = jax.tree.map(
            lambda x: x.reshape((-1, s) + x.shape[1:]), base)

        def one(v, kk):
            return qt.query_quantile(v, qs, method=q.method,
                                     num_replicates=q.num_replicates,
                                     key=kk)

        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            fold_in_str(key, q.name), jnp.arange(s))
        self.rows_fed[q.name] = base.counts.shape[0] // s
        return jax.vmap(one, in_axes=(1, 0))(by_key, keys)
