"""Metrics registry: device counters in the ingest + host-side mirrors.

**Device side** — :class:`MetricsState` is a pytree leaf of
:class:`~repro.runtime.executor.RuntimeState` (appended field, so
pre-existing leaf order is untouched).  Its counters are folded by
:func:`ingest_update` INSIDE the already-jitted ingest step of both
executors: a handful of bincounts/min-reductions over arrays the routing
already produced — zero extra dispatches, no host callbacks, and the
counters ride the same donation, checkpointing and crash/restore path as
the reservoirs themselves (bitwise exactly-once, tested against a numpy
oracle in ``tests/test_obs.py``).

Counter semantics (cumulative since ``init``/``executor.reset()``):

* ``ingested[s]``  — masked arrivals routed to stratum ``s``;
* ``accepted[s]``  — arrivals that survived the watermark + ring
  eviction and entered stratum ``s``'s reservoir fold (on-time + late);
* ``late[s]``      — accepted arrivals below the pre-chunk open interval
  (``Σ_s late == wm.late``, and likewise for the other three — the
  per-stratum decomposition of the watermark's scalar accounting);
* ``dropped[s]``   — masked arrivals refused (below watermark/evicted);
* ``replaced[s]``  — arrivals that hit an already-FULL (interval,
  stratum) reservoir cell, i.e. entered Vitter's replacement phase:
  per cell, arrivals minus fill-phase arrivals,
  ``(c₁−c₀) − (min(c₁,cap) − min(c₀,cap))``;
* ``occupancy[s]`` — gauge: items currently resident across stratum
  ``s``'s ring cells, ``Σ_K min(count, capacity)``;
* ``chunks``/``items`` — scalar stream totals.

**Host side** — :class:`Telemetry` mirrors everything that is only
observable where the host already synchronizes (emission, checkpoint and
micro-batch boundaries): step-latency percentiles, watermark lag,
emission staleness, micro-batch size and controller capacity
trajectories.  Attaching a Telemetry is the ONLY on/off switch — the
device counters are unconditionally part of the ingest, which is what
makes the hot-loop jaxpr identical with telemetry on or off.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import bincount, dataclass_pytree


@dataclass_pytree
@dataclasses.dataclass
class MetricsState:
    """Device-resident cumulative counters ([W]-stacked when sharded)."""
    ingested: jax.Array    # [S] i32 — masked arrivals per stratum
    accepted: jax.Array    # [S] i32 — entered the reservoir fold
    late: jax.Array        # [S] i32 — accepted, older than open interval
    dropped: jax.Array     # [S] i32 — refused (watermark / eviction)
    replaced: jax.Array    # [S] i32 — arrivals into full cells
    occupancy: jax.Array   # [S] i32 gauge — resident items per stratum
    chunks: jax.Array      # () i32 — chunks folded
    items: jax.Array       # () i32 — masked items folded


def init(num_strata: int) -> MetricsState:
    # One DISTINCT zeros buffer per field: the executors donate the whole
    # RuntimeState to their compiled steps, and XLA refuses to donate one
    # buffer twice (same reason controller.init copies base_capacity).
    def z(shape=(num_strata,)):
        return jnp.zeros(shape, jnp.int32)
    return MetricsState(ingested=z(), accepted=z(), late=z(), dropped=z(),
                        replaced=z(), occupancy=z(),
                        chunks=z(()), items=z(()))


def _per_stratum(pred: jax.Array, stratum_ids: jax.Array,
                 num_strata: int) -> jax.Array:
    """Count ``pred`` items per stratum — one bincount, excluded items
    routed to a sentinel stratum that is sliced away."""
    sid = jnp.where(pred, stratum_ids, jnp.int32(num_strata))
    return bincount(sid, num_strata + 1)[:num_strata]


def ingest_update(m: MetricsState, num_strata: int,
                  stratum_ids: jax.Array, mask: jax.Array,
                  accept: jax.Array, target_interval: jax.Array,
                  open_before: jax.Array,
                  counts_before: jax.Array, counts_after: jax.Array,
                  capacity: jax.Array) -> MetricsState:
    """Fold one routed chunk's accounting (pure jnp, jit-inlined).

    ``accept`` is the routing verdict; every accepted item's interval is
    live (non-evicted), so its ring slot holds exactly that interval and
    acceptance equals reservoir-fold participation.  ``counts_before``
    is the ``[K, S]`` cell arrival counts AFTER slot reset but BEFORE
    the fold, ``counts_after``/``capacity`` the post-fold cells.
    """
    late = accept & (target_interval < open_before)
    filled0 = jnp.minimum(counts_before, capacity)
    filled1 = jnp.minimum(counts_after, capacity)
    repl = (counts_after - counts_before) - (filled1 - filled0)  # [K, S]
    return MetricsState(
        ingested=m.ingested + _per_stratum(mask, stratum_ids, num_strata),
        accepted=m.accepted + _per_stratum(accept, stratum_ids, num_strata),
        late=m.late + _per_stratum(late, stratum_ids, num_strata),
        dropped=m.dropped + _per_stratum(mask & ~accept, stratum_ids,
                                         num_strata),
        replaced=m.replaced + jnp.sum(repl, axis=0),
        occupancy=jnp.sum(filled1, axis=0),
        chunks=m.chunks + 1,
        items=m.items + jnp.sum(mask.astype(jnp.int32)))


#: Row order of the ``[6, S]`` counter tile the one-shot ingest kernel
#: folds in place (``kernels/reservoir.one_shot_ingest``) — the
#: per-stratum fields of :class:`MetricsState`, scalars excluded.
COUNTER_FIELDS = ("ingested", "accepted", "late", "dropped",
                  "replaced", "occupancy")


def stack_counters(m: MetricsState) -> jax.Array:
    """``[6, S]`` row-stack of the per-stratum counters in
    ``COUNTER_FIELDS`` order — the device tile handed to (and aliased
    inside) the one-shot ingest kernel."""
    return jnp.stack([getattr(m, name) for name in COUNTER_FIELDS])


def unstack_counters(rows: jax.Array, chunks: jax.Array,
                     items: jax.Array) -> MetricsState:
    """Rebuild a :class:`MetricsState` from the kernel's ``[6, S]`` tile
    plus the scalar totals it carries separately. Each row is copied into
    its own buffer (``+ 0``) so the executors' whole-state donation never
    sees two leaves aliasing one allocation."""
    fields = {name: rows[idx] + 0
              for idx, name in enumerate(COUNTER_FIELDS)}
    return MetricsState(chunks=chunks, items=items, **fields)


def export(m: MetricsState) -> dict:
    """Plain-python view (checkpoint manifest / JSON events)."""
    return {f.name: np.asarray(getattr(m, f.name)).tolist()
            for f in dataclasses.fields(MetricsState)}


def from_export(d: dict) -> MetricsState:
    return MetricsState(**{
        f.name: jnp.asarray(d[f.name], jnp.int32)
        for f in dataclasses.fields(MetricsState)})


def counters(m: MetricsState) -> dict:
    """Host numpy snapshot, shard axis (if any) summed away — the global
    per-stratum counters an operator reads.  Blocks on the state; call
    at a boundary that already synchronized."""
    out = {}
    for f in dataclasses.fields(MetricsState):
        a = np.asarray(getattr(m, f.name))
        if f.name in ("chunks", "items"):
            out[f.name] = int(a.sum()) if a.ndim else int(a)
        else:
            out[f.name] = a.sum(axis=0) if a.ndim == 2 else a
    return out


# ---------------------------------------------------------------------------
# Host-side telemetry hub.
# ---------------------------------------------------------------------------

def _percentiles(xs: List[float]) -> dict:
    if not xs:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    a = np.asarray(xs, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99))}


class Telemetry:
    """Host-side observability hub an executor reports into.

    Pass one as ``telemetry=`` when constructing an executor (or via
    ``executor.attach_telemetry``).  Every hook below fires at a point
    that ALREADY synchronized with the device (emission, checkpoint,
    micro-batch flush), so attaching telemetry adds no host sync — and
    no retrace — to the pipelined hot loop (asserted in
    ``tests/test_obs.py``).

    ``log`` is an optional :class:`repro.obs.events.EventLog`; without
    one the hub still maintains the in-memory mirrors (latency
    percentiles, capacity/batch trajectories) behind :meth:`summary`.
    ``strict_retrace`` (default: the ``REPRO_OBS_STRICT`` env var) makes
    the executor's retrace sentinels raise instead of record.
    """

    def __init__(self, log=None, strict_retrace: Optional[bool] = None):
        self.log = log
        self.strict_retrace = strict_retrace
        self.latencies: List[float] = []       # per-emission step latency
        self.batch_sizes: List[int] = []       # batched micro-batch knob
        self.capacity_traj: List[list] = []    # [S] capacity per emission
        self.watermark_lag: List[float] = []   # frontier − watermark
        self.staleness: List[float] = []       # close emissions only
        self.emissions = 0
        self.checkpoint_saves = 0
        self.checkpoint_restores = 0
        self.checkpoint_bytes = 0
        self.last_recovery_s: Optional[float] = None

    # -- executor hooks (each fires at an existing host-sync boundary) --

    def on_run_meta(self, ex) -> None:
        if self.log is None:
            return
        from repro.runtime.registry import describe
        cfg = ex.cfg
        self.log.emit("run_meta", mode=ex.mode,
                      emission=cfg.emission,
                      num_strata=cfg.num_strata,
                      num_intervals=cfg.num_intervals,
                      interval_span=cfg.interval_span,
                      allowed_lateness=cfg.allowed_lateness,
                      num_shards=cfg.num_shards,
                      queries=describe(ex.registry),
                      emit_cells=ex.emit_cells(),
                      gather_words=ex.gather_words())

    def on_emission(self, ex, em) -> None:
        """One emission was recorded (the host just blocked on results)."""
        from repro.runtime import watermark as wmk
        from repro.runtime.registry import result_summary
        self.emissions += 1
        self.latencies.append(float(em.latency_s))
        self.capacity_traj.append(np.asarray(em.capacity).tolist())
        frontier = float(np.max(ex._host_frontier))
        if frontier > float(wmk.NEG_TIME):
            self.watermark_lag.append(frontier - em.watermark)
        stale = None
        if em.interval is not None:
            stale = wmk.staleness(em.watermark, em.interval,
                                  ex.cfg.interval_span)
            self.staleness.append(stale)
        if self.log is None:
            return
        fields = dict(
            index=em.index, interval=em.interval,
            watermark=float(em.watermark),
            open_interval=int(em.open_interval),
            on_time=int(em.on_time), late=int(em.late),
            dropped=int(em.dropped), items=int(em.items),
            latency_s=float(em.latency_s),
            capacity=np.asarray(em.capacity).tolist(),
            results=result_summary(em.results))
        if stale is not None:
            fields["staleness"] = stale
        self.log.emit("emission", **fields)
        if em.interval is not None:
            self.log.emit("watermark_close", interval=int(em.interval),
                          watermark=float(em.watermark), staleness=stale)
        from repro.runtime import controller as ctl
        self.log.emit("controller", **ctl.telemetry(ex.state.ctrl))

    def on_flush(self, ex, batch_chunks: int) -> None:
        """Batched micro-batch boundary (the driver barrier)."""
        if not self.batch_sizes or self.batch_sizes[-1] != batch_chunks:
            if self.log is not None:
                self.log.emit("batch_resize", batch_chunks=batch_chunks)
        self.batch_sizes.append(batch_chunks)

    def on_checkpoint_save(self, stream_offset: int, num_bytes: int,
                           serialize_s: float, drift_chunks: int) -> None:
        self.checkpoint_saves += 1
        self.checkpoint_bytes += num_bytes
        if self.log is not None:
            self.log.emit("checkpoint_save", stream_offset=stream_offset,
                          bytes=num_bytes, serialize_s=serialize_s,
                          drift_chunks=drift_chunks)

    def on_checkpoint_restore(self, stream_offset: int,
                              restore_s: float) -> None:
        self.checkpoint_restores += 1
        self.last_recovery_s = restore_s
        if self.log is not None:
            self.log.emit("checkpoint_restore",
                          stream_offset=stream_offset,
                          restore_s=restore_s)

    def on_retrace(self, name: str, traces: int, allowed: int) -> None:
        if self.log is not None:
            self.log.emit("retrace", step=name, traces=traces,
                          allowed=allowed)

    # -- read side --

    def device_counters(self, ex) -> dict:
        """Global device-counter snapshot (shards summed). Blocks on the
        state — call between steps, like a checkpoint."""
        return counters(ex.state.metrics)

    def summary(self) -> dict:
        """The host mirrors, reduced — what Prometheus exposition and
        ``repro.obs.summarize`` render."""
        return {
            "emissions": self.emissions,
            "latency_s": _percentiles(self.latencies),
            "watermark_lag": _percentiles(self.watermark_lag),
            "staleness": _percentiles(self.staleness),
            "batch_chunks_last": (self.batch_sizes[-1]
                                  if self.batch_sizes else None),
            "capacity_last": (self.capacity_traj[-1]
                              if self.capacity_traj else None),
            "checkpoint_saves": self.checkpoint_saves,
            "checkpoint_restores": self.checkpoint_restores,
            "checkpoint_bytes": self.checkpoint_bytes,
            "last_recovery_s": self.last_recovery_s,
        }
