"""Plain numpy reference of the engine's semantics, and the compile clock.

The event-time walk is copied from ``chip_smoke.reference`` (bounded-
lateness watermark with the pre-chunk frontier, a ring of ``K`` live
intervals, on-time / late / dropped accounting) and extended to keep, per
(shard, interval, stratum) cell, every accepted value in arrival order. From
those populations it gives the exact answer of each registered query and
the standard error that a uniform stratified sample at the stated capacity
has. It imports nothing of the program, so a change to the program cannot
move it. ``CompileClock`` is copied from ``chip_smoke.CompileClock``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NEG_TIME = np.float32(-3.0e38)

#: z of the program's 95% bound (the paper's 68-95-99.7 rule).
Z95 = 2.0


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), from its own events, and
    how many backend compiles or cache fetches it made."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[2]:
            self.compiles += 1


class Reference:
    """Walk of the pushed chunks, shard by shard.

    ``chunks`` arrive as numpy ``(values, stratum_ids, times, mask)`` with
    leaves ``[W, M]``. Each shard has its own frontier, open interval and
    counters; an interval closes once the smallest shard frontier, less the
    lateness, passes its end (``host_closed_through`` of the runtime).
    """

    def __init__(self, num_shards: int, num_strata: int, span: float,
                 lateness: float, ring: int):
        self.w, self.s, self.k = num_shards, num_strata, ring
        self.span = np.float32(span)
        self.lateness = np.float32(lateness)
        self.frontier = np.full((num_shards,), NEG_TIME, np.float32)
        self.open = np.zeros((num_shards,), np.int64)
        self.on_time = self.late = self.dropped = 0
        self.per_stratum = {name: np.zeros(num_strata, np.int64) for name in
                            ("ingested", "accepted", "late", "dropped")}
        self.cumulative: List[Tuple[int, int, int]] = []
        self.closes: List[Tuple[int, int]] = []   # (interval, chunk count)
        self.cells: Dict[Tuple[int, int, int], List[np.ndarray]] = {}
        self._emitted = -1

    def feed(self, values, stratum_ids, times, mask) -> None:
        values = np.asarray(values, np.float32).reshape(self.w, -1)
        stratum_ids = np.asarray(stratum_ids).reshape(self.w, -1)
        times = np.asarray(times, np.float32).reshape(self.w, -1)
        mask = np.asarray(mask, bool).reshape(self.w, -1)
        for w in range(self.w):
            self._feed_shard(w, values[w], stratum_ids[w], times[w], mask[w])
        self.cumulative.append((self.on_time, self.late, self.dropped))
        wmark = np.float32(self.frontier.min()) - self.lateness
        closed = int(np.floor(wmark / self.span)) - 1
        while self._emitted < closed:
            self._emitted += 1
            self.closes.append((self._emitted, len(self.cumulative)))

    def _feed_shard(self, w, v, sid, t, m) -> None:
        open_before = int(self.open[w])
        wmark = self.frontier[w] - self.lateness
        tgt = np.floor(t / self.span).astype(np.int64)
        new_open = max(open_before, int(tgt[m].max())) if m.any() \
            else open_before
        accept = m & ~(t < wmark) & ~(tgt < new_open - self.k + 1)
        late = accept & (tgt < open_before)
        self.on_time += int(np.sum(accept & ~late))
        self.late += int(np.sum(late))
        self.dropped += int(np.sum(m & ~accept))
        for name, sel in (("ingested", m), ("accepted", accept),
                          ("late", late), ("dropped", m & ~accept)):
            self.per_stratum[name] += np.bincount(sid[sel],
                                                  minlength=self.s)
        for iv in np.unique(tgt[accept]):
            in_iv = accept & (tgt == iv)
            for st in np.unique(sid[in_iv]):
                sel = in_iv & (sid == st)
                self.cells.setdefault((w, int(iv), int(st)), []).append(
                    v[sel])
        if m.any():
            self.frontier[w] = max(self.frontier[w], t[m].max())
        self.open[w] = new_open

    def population(self, shard: int, interval: int, stratum: int):
        """Accepted values of one cell, in arrival order."""
        parts = self.cells.get((shard, interval, stratum), [])
        return np.concatenate(parts) if parts else np.zeros(0, np.float32)

    def interval_cells(self, interval: int, key: int = None):
        """Populations of an interval's cells (one stratum if ``key``)."""
        strata = range(self.s) if key is None else (key,)
        return [self.population(w, interval, st)
                for w in range(self.w) for st in strata]


# ---------------------------------------------------------------------------
# Exact answers and the standard error of a uniform stratified sample.
# ---------------------------------------------------------------------------

def _moments(cells, capacity: int):
    """``(C_i, n_i, S_i²)`` per cell: arrivals, sample size at the stated
    capacity, and population variance (ddof 1)."""
    c = np.array([len(x) for x in cells], np.float64)
    n = np.minimum(c, capacity)
    s2 = np.array([np.var(x.astype(np.float64), ddof=1) if len(x) > 1
                   else 0.0 for x in cells])
    return c, n, s2


def linear_truth(kind: str, cells, capacity: int):
    """Exact ``sum``/``mean``/``count`` of the cells' union and the standard
    error of the stratified estimator (Eqs. 6 and 9 with the population's
    own variances)."""
    c, n, s2 = _moments(cells, capacity)
    total = c.sum()
    fpc_var = np.where(n > 0, (c - n) * s2 / np.maximum(n, 1), 0.0)
    if kind == "count":
        return total, 0.0
    exact_sum = float(sum(x.astype(np.float64).sum() for x in cells))
    if kind == "sum":
        return exact_sum, math.sqrt(float(np.sum(c * fpc_var)))
    assert kind == "mean"
    if total == 0:
        return 0.0, 0.0
    var = np.sum((c / total) ** 2 * fpc_var / np.maximum(c, 1))
    return exact_sum / total, math.sqrt(float(var))


def rank_band(x: float, cells):
    """``[F(x-), F(x)]`` of the cells' union: where ``x`` lies in the
    exact distribution."""
    allv = np.sort(np.concatenate(cells).astype(np.float32))
    lo = np.searchsorted(allv, np.float32(x), side="left") / len(allv)
    hi = np.searchsorted(allv, np.float32(x), side="right") / len(allv)
    return lo, hi, allv


def quantile_rank_se(x: float, cells, capacity: int) -> float:
    """Standard error of the stratified estimate of ``F(x)``: each cell's
    share below ``x`` sampled without replacement at the stated capacity."""
    c = np.array([len(v) for v in cells], np.float64)
    total = c.sum()
    var = 0.0
    for ci, v in zip(c, cells):
        n = min(ci, capacity)
        if ci < 2 or n >= ci:
            continue
        p = float(np.mean(v <= np.float32(x)))
        var += (ci / total) ** 2 * (ci - n) / (ci - 1) * p * (1 - p) / n
    return math.sqrt(var)


def rank_mean_z(sample: np.ndarray, population: np.ndarray) -> float:
    """``z`` of the mean arrival rank of a reservoir's sample.

    A uniform sample without replacement of ``n`` of ``C`` arrivals has
    mean rank ``(C-1)/2`` with variance ``(C²-1)/12/n·(C-n)/(C-1)``; a
    fold that favours early or late arrivals reads far off. Values that
    occur more than once in the population carry no rank and are left out.
    """
    c = len(population)
    vals, first, counts = np.unique(population, return_index=True,
                                    return_counts=True)
    unique = counts == 1
    pos = np.searchsorted(vals, sample)
    pos = np.clip(pos, 0, len(vals) - 1)
    hit = (vals[pos] == sample) & unique[pos]
    ranks = first[pos[hit]].astype(np.float64)
    n = len(ranks)
    if n < 2 or n >= c:
        return 0.0
    var = (c * c - 1) / 12.0 / n * (c - n) / (c - 1)
    return abs(ranks.mean() - (c - 1) / 2.0) / math.sqrt(var)


def is_submultiset(sample: np.ndarray, population: np.ndarray) -> bool:
    """Every sampled value is an accepted arrival of the cell, and no
    arrival is sampled more often than it arrived."""
    pv, pc = np.unique(population, return_counts=True)
    sv, sc = np.unique(sample, return_counts=True)
    pos = np.clip(np.searchsorted(pv, sv), 0, max(len(pv) - 1, 0))
    if len(sv) and not len(pv):
        return False
    return bool(np.all(pv[pos] == sv) and np.all(sc <= pc[pos]))
