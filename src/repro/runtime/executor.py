"""Dual-mode streaming runtime: batched + pipelined executors.

The paper's claim is that OASRS is generic across the two prominent
stream-system types; this module *executes* that claim. Both executors
share ONE jitted ingest core (`_ingest_chunk` — watermark routing + a
single route-once reservoir fold over the flattened [K·S] ring×stratum
axis + ring maintenance), so their sampling trajectories are identical
chunk-for-chunk and registered-query answers agree exactly at window
boundaries (property-tested). The compiled steps DONATE their
RuntimeState buffers, so the [K, S, N_max, …] ring is updated in place
rather than re-materialized every chunk. They differ only in *when* the
core runs and *where* the host synchronizes:

* :class:`BatchedExecutor` — micro-batch model (Spark Streaming): chunks
  accumulate host-side; every ``batch_chunks`` arrivals ONE jitted window
  step scans the core over the micro-batch, evaluates every standing
  query from the shared sample pass, and applies the controller. The host
  barrier per window is inherent to the model (the driver heartbeat).
* :class:`PipelinedExecutor` — pipelined model (Flink): every chunk flows
  through the jitted core as it arrives — no window barrier, no host
  sync in the hot path (asserted by trace count in tests). Emissions
  (query evaluation + controller + the only host sync) fire every
  ``emit_every`` chunks.

Sharding (``num_shards > 1``) runs the core per shard, with the ingest
path built on :func:`repro.core.distributed.local_update` (zero
collectives, asserted against the jaxpr) and emissions merging the
per-(shard × interval × stratum) cells (Eq. 5). Two interchangeable
deployments:

* ``placement="vmap"`` (default) — single-device simulation: the core is
  vmapped over the [W]-stacked states and the emission merge is a
  host-side reshape-concat. This is the bitwise ORACLE.
* ``placement="mesh"`` — real scale-out: the SAME vmapped core runs under
  ``shard_map`` on a 1-D ``(shard,)`` device mesh
  (``launch/mesh.make_stream_mesh``), one shard per device, and each
  emission performs exactly ONE tiled all_gather
  (``dist.gather_cells``) to merge the cells — proven bitwise-identical
  to the vmap oracle (emissions, Eq. 5–9 widths, obs counters) in
  ``tests/test_scaleout.py``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as dist
from repro.core import error as err
from repro.core import oasrs
from repro.kernels import ops as kops
from repro.core import quantile as qt
from repro.core import window as win
from repro.obs import metrics as obm
from repro.obs import spans as obs_spans
from repro.obs.sentinel import RetraceSentinel
from repro.runtime import checkpoint as ckp
from repro.runtime import controller as ctl
from repro.runtime import watermark as wmk
from repro.runtime.records import TimestampedChunk
from repro.runtime.registry import QueryRegistry, Result
from repro.utils import dataclass_pytree


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Static description of one runtime instance (hashable, jit-safe)."""
    num_strata: int
    capacity: int                      # per-stratum reservoir capacity N_i
    num_intervals: int = 4             # ring size K (window = K intervals)
    interval_span: float = 1.0         # event-time units per interval
    allowed_lateness: float = 0.5      # watermark lag (event-time units)
    max_capacity: Optional[int] = None  # reservoir allocation N_max
    num_shards: int = 1                # >1: vmap-sharded local states
    placement: str = "vmap"            # "vmap" single-device simulation |
    #   "mesh" — one device per shard via shard_map over a (shard,) mesh
    #   (launch/mesh.make_stream_mesh): ingest runs collective-free per
    #   device, each emission performs exactly ONE all_gather merge
    #   (dist.gather_cells). Bitwise-identical to the vmap oracle.
    controller: ctl.ControllerConfig = ctl.ControllerConfig()
    accuracy_query: Optional[str] = None  # registry name driving feedback
    batch_chunks: int = 4              # batched mode: chunks per window step
    max_batch_chunks: int = 32
    emit_every: int = 4                # pipelined mode: chunks per emission
    backend: Optional[str] = None      # reservoir fold: "jnp"|"pallas"|auto
    ingest: str = "fused"              # "fused" single-pass | "masked"
    #   legacy | "onekernel" — the whole accepted-item path (routing, slot
    #   reset, cell assignment, counter bump, replacement draw, ring
    #   write, obs counters) in ONE Pallas call with the ring pinned in
    #   VMEM (kernels/reservoir.one_shot_ingest; bitwise == "fused").
    emission: str = "cadence"          # "cadence" chunk-count | "watermark"
    #   cadence   — emissions on the driver loop's chunk count (batched:
    #               per micro-batch flush; pipelined: every emit_every).
    #   watermark — emissions are a property of EVENT TIME: interval j's
    #               answers are emitted exactly once, when the watermark
    #               frontier passes its close (j+1)·interval_span — after
    #               every late-but-allowed item has landed in its slot.
    #               Emissions carry Emission.interval and evaluate the
    #               registry on that closed interval's cells (session
    #               windows keep reading the whole ring).


@dataclass_pytree
@dataclasses.dataclass
class RuntimeState:
    """Device-resident runtime state (stacked on a [W] axis when sharded)."""
    window: win.WindowState       # ring of K per-interval OASRS states
    slot_interval: jax.Array      # [K] i32 — event interval held per slot
    open_interval: jax.Array      # () i32 — newest interval seen
    wm: wmk.WatermarkState
    ctrl: ctl.ControllerState
    # Device telemetry counters (appended LAST so the pre-existing leaf
    # order is untouched). Unconditionally part of the ingest — NOT
    # gated on whether a Telemetry is attached — so the hot-loop jaxpr
    # is identical with observability on or off, and the counters ride
    # the same donation/checkpoint/restore path as the reservoirs
    # (bitwise exactly-once, like everything else in this pytree).
    metrics: obm.MetricsState


@dataclasses.dataclass
class Emission:
    """One emission: query answers + watermark accounting + rates."""
    index: int
    results: Dict[str, Result]
    watermark: float
    open_interval: int
    on_time: int
    late: int
    dropped: int
    capacity: np.ndarray          # [S] i32 controller capacity after update
    #                               (host copy — the live state is donated)
    latency_s: float              # measured step latency fed back
    items: int                    # items pushed since previous emission
    interval: Optional[int] = None  # watermark emission: the event-time
    #                                 interval this emission closed
    #                                 (None under cadence emission)


def init_state(cfg: RuntimeConfig, key: jax.Array) -> RuntimeState:
    """Fresh runtime state (per-shard states stacked when sharded)."""
    k = cfg.num_intervals
    cap = jnp.full((cfg.num_strata,), cfg.capacity, jnp.int32)
    if cfg.num_shards > 1:
        # Paper §3.2: each of w workers holds reservoirs of size N_i / w.
        cap = dist.split_capacity(cap, cfg.num_shards)
    max_cap = cfg.max_capacity
    if max_cap is None:
        max_cap = int(cap.max())
        if cfg.controller.budget is not None:
            # The accuracy feedback may raise per-interval capacity up to
            # the budget's per-stratum ceiling; N_max must cover it or
            # reservoir writes would spill into neighboring strata
            # (capacity <= N_max is an OASRSState invariant).
            max_cap = max(max_cap,
                          int(cfg.controller.budget.max_per_stratum))
    spec = jax.ShapeDtypeStruct((), jnp.float32)

    def one(shard_key):
        slots = jnp.arange(k, dtype=jnp.int32)
        return RuntimeState(
            window=win.init(k, cfg.num_strata, cap, spec, shard_key,
                            max_capacity=max_cap),
            slot_interval=-jnp.mod(-slots, k),   # intervals 1-K … 0
            open_interval=jnp.zeros((), jnp.int32),
            wm=wmk.init(),
            ctrl=ctl.init(cap),
            metrics=obm.init(cfg.num_strata),
        )

    if cfg.num_shards == 1:
        return one(key)
    return jax.vmap(one)(jax.random.split(key, cfg.num_shards))


# ---------------------------------------------------------------------------
# The shared jitted core.
# ---------------------------------------------------------------------------

def _route_and_reset(cfg: RuntimeConfig, state: RuntimeState,
                     chunk: TimestampedChunk):
    """Shared ingest prologue: advance the watermark, reassign ring slots.

    Ring maintenance without an explicit slide loop: interval j lives in
    slot j mod K, so each slot's *desired* occupant is the newest live
    interval congruent to it. A slot whose occupant changed is reset
    (counts zeroed — reservoir contents die via slot_mask) and adopts
    the controller's current capacity; live slots keep theirs so the
    Vitter acceptance invariant holds within an interval.
    """
    k = cfg.num_intervals
    r = wmk.route_chunk(state.wm, state.open_interval, chunk.times,
                        chunk.mask, cfg.interval_span, cfg.allowed_lateness,
                        k)
    slots = jnp.arange(k, dtype=jnp.int32)
    desired = r.open_interval - jnp.mod(r.open_interval - slots, k)
    reset = desired != state.slot_interval
    iv = state.window.intervals
    # Adopted capacity is hard-clamped to the reservoir allocation: a
    # controller proposal above N_max would index out of the slot buffer.
    n_max = jax.tree_util.tree_leaves(iv.values)[0].shape[2]  # [K,S,N,…]
    adopt = jnp.minimum(state.ctrl.capacity, jnp.int32(n_max))
    iv = dataclasses.replace(
        iv,
        counts=jnp.where(reset[:, None], 0, iv.counts),
        capacity=jnp.where(reset[:, None], adopt[None, :], iv.capacity))
    return r, iv, desired


def _finish_ingest(cfg: RuntimeConfig, state: RuntimeState, chunk, r, iv,
                   desired, counts_before) -> RuntimeState:
    k = cfg.num_intervals
    window = win.WindowState(
        intervals=iv,
        cursor=jnp.mod(r.open_interval + 1, k),
        filled=jnp.minimum(r.open_interval + 1, k))
    # Device telemetry fold — a few bincounts over arrays the routing
    # already produced, inlined into this same jitted step (zero extra
    # dispatches). ``counts_before`` is the post-reset/pre-fold [K, S]
    # cell counts; against the post-fold counts they yield per-stratum
    # replacement-phase arrivals and the occupancy gauge exactly.
    metrics = obm.ingest_update(
        state.metrics, cfg.num_strata, chunk.stratum_ids, chunk.mask,
        r.accept, r.target_interval, state.open_interval,
        counts_before, iv.counts, iv.capacity)
    return RuntimeState(window=window, slot_interval=desired,
                        open_interval=r.open_interval, wm=r.wm,
                        ctrl=state.ctrl, metrics=metrics)


def _ingest_chunk(cfg: RuntimeConfig, state: RuntimeState,
                  chunk: TimestampedChunk) -> RuntimeState:
    """Fold one chunk: watermark-route items, maintain the interval ring,
    update per-interval reservoirs. Pure jnp — no collectives, no host.

    Single-pass route-once fold: the [K, S] (ring-slot × stratum) space
    is flattened to ONE K·S stratum axis and each accepted item is routed
    once to its (slot, stratum) cell, so an M-item chunk performs one
    reservoir fold instead of K masked ones. Exact sequential Vitter
    semantics are preserved — an item's rank within the combined
    (slot, stratum) cell equals its rank within the stratum of that
    interval, so acceptance probabilities (and hence batched/pipelined
    mode equivalence) are bitwise those of the per-slot fold
    (``_ingest_chunk_masked`` is the proof harness).
    """
    if cfg.ingest == "masked":
        return _ingest_chunk_masked(cfg, state, chunk)
    if cfg.ingest == "onekernel":
        return _ingest_chunk_onekernel(cfg, state, chunk)
    if cfg.ingest != "fused":
        raise ValueError(f"unknown ingest path {cfg.ingest!r}; "
                         "expected 'fused', 'masked' or 'onekernel'")
    k, s_cnt = cfg.num_intervals, cfg.num_strata
    r, iv, desired = _route_and_reset(cfg, state, chunk)
    counts_before = iv.counts

    # Route each accepted item ONCE: slot j = interval mod K owns it, and
    # it survives only if that slot currently holds its interval (an item
    # for an evicted interval whose slot was recycled must not leak into
    # the new occupant).
    tgt_slot = jnp.mod(r.target_interval, k)                     # [M]
    live = r.accept & (desired[tgt_slot] == r.target_interval)
    flat_sid = tgt_slot * s_cnt + chunk.stratum_ids              # [M]

    # One collective-free fold over the flattened K·S stratum axis (the
    # distributed ingest contract), driven by the ring's lead PRNG key.
    flat = oasrs.OASRSState(
        values=jax.tree.map(
            lambda v: v.reshape((k * s_cnt,) + v.shape[2:]), iv.values),
        counts=iv.counts.reshape(-1),
        capacity=iv.capacity.reshape(-1),
        key=iv.key[0])
    flat = dist.local_update(flat, flat_sid, chunk.values, live,
                             backend=cfg.backend)
    iv = dataclasses.replace(
        iv,
        values=jax.tree.map(lambda f, v: f.reshape(v.shape),
                            flat.values, iv.values),
        counts=flat.counts.reshape(k, s_cnt),
        key=iv.key.at[0].set(flat.key))
    return _finish_ingest(cfg, state, chunk, r, iv, desired, counts_before)


def _ingest_chunk_onekernel(cfg: RuntimeConfig, state: RuntimeState,
                            chunk: TimestampedChunk) -> RuntimeState:
    """One-shot Pallas ingest: everything ``_ingest_chunk`` (fused) does
    — watermark routing, slot reset, (slot, stratum) cell assignment,
    counter bump, replacement draw, conditional ring write AND the obs
    counter fold — inside ONE kernel call, with the [K·S, N_max] ring,
    cell counters and counter rows pinned in VMEM across item tiles
    (``kernels/reservoir.one_shot_ingest``).

    Bitwise-interchangeable with the fused path: the uniforms come from
    the SAME ``split(lead_key, 3)`` schedule, the kernel keeps the
    ``floor(u·N_i)`` replacement-slot convention, and the counter rows
    reproduce ``obs/metrics.ingest_update`` — so answers, Eq. 5–9 widths,
    obs counters and crash/restore sweeps are identical (asserted in
    ``tests/test_onekernel.py``).
    """
    k = cfg.num_intervals
    iv = state.window.intervals
    m = chunk.stratum_ids.shape[0]
    key, k_u, k_slot = jax.random.split(iv.key[0], 3)
    u_accept = jax.random.uniform(k_u, (m,))
    u_slot = jax.random.uniform(k_slot, (m,))
    n_max = jax.tree_util.tree_leaves(iv.values)[0].shape[2]
    adopt = jnp.minimum(state.ctrl.capacity, jnp.int32(n_max))
    out = kops.one_shot_ingest(
        chunk.times, chunk.stratum_ids.astype(jnp.int32), chunk.values,
        chunk.mask, u_accept, u_slot,
        max_time=state.wm.max_time, open_interval=state.open_interval,
        on_time=state.wm.on_time, late=state.wm.late,
        dropped=state.wm.dropped, chunks=state.metrics.chunks,
        items=state.metrics.items, slot_interval=state.slot_interval,
        adopt=adopt, counts=iv.counts, capacity=iv.capacity,
        values=iv.values, counters=obm.stack_counters(state.metrics),
        span=cfg.interval_span, allowed_lateness=cfg.allowed_lateness)
    window = win.WindowState(
        intervals=oasrs.OASRSState(
            values=out.values, counts=out.counts, capacity=out.capacity,
            key=iv.key.at[0].set(key)),
        cursor=jnp.mod(out.open_interval + 1, k),
        filled=jnp.minimum(out.open_interval + 1, k))
    wm = wmk.WatermarkState(max_time=out.max_time, on_time=out.on_time,
                            late=out.late, dropped=out.dropped)
    metrics = obm.unstack_counters(out.counters, chunks=out.chunks,
                                   items=out.items)
    return RuntimeState(window=window, slot_interval=out.slot_interval,
                        open_interval=out.open_interval, wm=wm,
                        ctrl=state.ctrl, metrics=metrics)


def _ingest_chunk_masked(cfg: RuntimeConfig, state: RuntimeState,
                         chunk: TimestampedChunk) -> RuntimeState:
    """Pre-fusion reference ingest: fold EVERY ring slot's masked view of
    the chunk — K reservoir folds of M items each (K·M work).

    Kept as the benchmark baseline (``benchmarks/bench_ingest.py``) and
    as the bitwise cross-check of the fused path: the uniforms are drawn
    once from the ring's lead key exactly like the fused fold, and each
    item is masked into exactly one slot, so both paths produce
    IDENTICAL states (asserted in ``tests/test_ingest_fused.py``).
    """
    k = cfg.num_intervals
    m = chunk.stratum_ids.shape[0]
    r, iv, desired = _route_and_reset(cfg, state, chunk)
    counts_before = iv.counts

    slot_masks = r.accept[None, :] & (
        r.target_interval[None, :] == desired[:, None])          # [K, M]
    key, k_u, k_slot = jax.random.split(iv.key[0], 3)
    u_accept = jax.random.uniform(k_u, (m,))
    u_slot = jax.random.uniform(k_slot, (m,))
    folded = jax.vmap(
        lambda st, mk: oasrs.apply_chunk_uniforms(
            st, chunk.stratum_ids, chunk.values, mk, u_accept, u_slot),
        in_axes=(0, 0))(iv, slot_masks)
    iv = dataclasses.replace(folded, key=iv.key.at[0].set(key))
    return _finish_ingest(cfg, state, chunk, r, iv, desired, counts_before)


@dataclass_pytree
@dataclasses.dataclass
class _GatherAux:
    """Per-shard structure that rides the mesh emission's single
    all_gather (``dist.gather_cells`` aux payload): everything the
    emission needs from OTHER shards besides the sample cells, so the
    merge stays at exactly one collective."""
    lead_key: jax.Array       # [2] u32 — shard 0's interval-0 ring key
    slot_interval: jax.Array  # [W, K] i32 — every shard's slot→interval
    live: jax.Array           # [W, K] bool — every shard's ring liveness
    counts_pos: jax.Array     # [W, K, S] bool — raw cell counts > 0


def _pack_aux(cfg: RuntimeConfig, state: RuntimeState,
              window0: win.WindowState) -> jax.Array:
    """Flatten this device's aux words (u32) for ``gather_cells``."""
    lead = state.window.intervals.key[0, 0].astype(jnp.uint32)   # [2]
    slot = jax.lax.bitcast_convert_type(
        state.slot_interval[0], jnp.uint32)                      # [K]
    live = win._live_mask(window0).astype(jnp.uint32)            # [K]
    pos = (window0.intervals.counts > 0).astype(
        jnp.uint32).reshape(-1)                                  # [K·S]
    return jnp.concatenate([lead, slot, live, pos])


def _unpack_aux(cfg: RuntimeConfig, aux_all: jax.Array) -> _GatherAux:
    k, s = cfg.num_intervals, cfg.num_strata
    return _GatherAux(
        lead_key=aux_all[0, :2],
        slot_interval=jax.lax.bitcast_convert_type(
            aux_all[:, 2:2 + k], jnp.int32),
        live=aux_all[:, 2 + k:2 + 2 * k].astype(jnp.bool_),
        counts_pos=aux_all[:, 2 + 2 * k:].reshape(
            aux_all.shape[0], k, s).astype(jnp.bool_))


def _merged_view(cfg: RuntimeConfig, state: RuntimeState,
                 axis: Optional[str] = None):
    """Shared sample pass: merged SampleView + StratumStats (+ mesh aux).

    Single shard: the window's (interval × stratum) cells. Sharded: the
    (shard × interval × stratum) cells — the same Eq. 5 concatenation the
    single-psum merges in ``core/distributed.py`` compute collectively.
    ``axis`` set means we are INSIDE shard_map: each device computes its
    local view and ONE tiled all_gather concatenates the shards in shard
    order — bitwise the vmap oracle's reshape-concat.

    Returns ``(view, stats, aux)`` — ``aux`` is ``None`` off-mesh.
    """
    if axis is not None:
        window0 = jax.tree.map(lambda x: x[0], state.window)
        local = win.sample_view(window0)
        view, aux_all = dist.gather_cells(
            local, _pack_aux(cfg, state, window0), axis, cfg.num_shards)
        aux = _unpack_aux(cfg, aux_all)
    elif cfg.num_shards == 1:
        view, aux = win.sample_view(state.window), None
    else:
        views = jax.vmap(win.sample_view)(state.window)
        n = views.values.shape[-1]
        view = qt.SampleView(values=views.values.reshape(-1, n),
                             counts=views.counts.reshape(-1),
                             taken=views.taken.reshape(-1))
        aux = None
    if cfg.num_shards > 1:
        # Both placements hand the estimators the same [W·K·S] view.
        # Without the barrier XLA folds the vmap path's reshape into the
        # reductions that follow (summing [W, K, S] cells, or the mesh's
        # [W, K·S] gather rows), and on a TPU a reduction of another
        # shape rounds f32 sums differently.
        view = jax.lax.optimization_barrier(view)
    stats = err.stratum_stats_from_sample(
        view.values, view.counts, view.taken, view.slot_mask(),
        fixed_order=cfg.num_shards > 1)
    return view, stats, aux


def _emission_key(cfg: RuntimeConfig, state: RuntimeState,
                  aux: Optional[_GatherAux] = None) -> jax.Array:
    if aux is not None:
        # Mesh: each device only holds its OWN shard's ring keys; the
        # gathered aux carries shard 0's lead key so every device folds
        # the SAME key the vmap oracle uses.
        return jax.random.fold_in(aux.lead_key, 0xE717)
    keys = state.window.intervals.key    # [K, 2] (or [W, K, 2] sharded)
    return jax.random.fold_in(keys.reshape(-1, keys.shape[-1])[0], 0xE717)


def _window_ctx(cfg: RuntimeConfig, state: RuntimeState, view, stats,
                aux: Optional[_GatherAux] = None):
    """EmissionContext for the grouped (per-key / session) window kinds.

    Sharded states hold identical slot assignments on every shard (all
    shards consume the same event-time ramp — the ``stamp_sharded``
    contract), so the slot/interval structure comes from shard 0 while
    per-key activity pools counts over shards (a key's traffic is spread
    across them).  On the mesh the same shard-0 structure and pooled
    activity come from the gathered aux — bitwise the vmap expressions.
    """
    from repro.runtime.registry import EmissionContext
    if aux is not None:
        slot_interval = aux.slot_interval[0]
        activity = aux.live[0][:, None] & jnp.any(aux.counts_pos, axis=0)
    elif cfg.num_shards == 1:
        slot_interval = state.slot_interval
        activity = win.activity_mask(state.window)
    else:
        window = jax.tree.map(lambda x: x[0], state.window)
        slot_interval = state.slot_interval[0]
        counts_any = jnp.any(state.window.intervals.counts > 0, axis=0)
        activity = win._live_mask(window)[:, None] & counts_any
    return EmissionContext(
        num_intervals=cfg.num_intervals, num_strata=cfg.num_strata,
        num_shards=cfg.num_shards, interval_span=cfg.interval_span,
        slot_interval=slot_interval, activity=activity,
        view=view, stats=stats)


def _evaluate(cfg: RuntimeConfig, registry: QueryRegistry,
              state: RuntimeState, axis: Optional[str] = None):
    view, stats, aux = _merged_view(cfg, state, axis)
    ctx = _window_ctx(cfg, state, view, stats, aux)
    results = registry.evaluate_view(view, stats,
                                     _emission_key(cfg, state, aux),
                                     ctx=ctx)
    return results, stats


def _slot_holds(cfg: RuntimeConfig, state: RuntimeState, interval: jax.Array,
                aux: Optional[_GatherAux] = None) -> jax.Array:
    """``[W]`` — whether each shard's slot ``interval mod K`` still HOLDS
    ``interval`` (a recycled slot must never leak its new occupant into an
    older interval's emission — the host guards eviction with a named
    error, this is the in-graph belt)."""
    k = cfg.num_intervals
    slot_interval = (aux.slot_interval if aux is not None
                     else state.slot_interval.reshape(-1, k))    # [W, K]
    return slot_interval[:, jnp.mod(interval, k)] == interval


def _closed_view(cfg: RuntimeConfig, view: qt.SampleView,
                 interval: jax.Array, holds: jax.Array) -> qt.SampleView:
    """The closed interval's rows of the merged view: ``[W·K·S, N] →
    [W·S, N]``, shard-major like the merged view.

    Interval ``j`` lives in slot ``j mod K``, so its cells are one index
    of the view's K axis; the estimators then work on the cells that
    carry the answer's weight instead of the whole ring with the rest
    zeroed. A shard whose slot no longer holds ``j`` contributes zero
    counts, exactly as a masked cell would.
    """
    w, k, s = cfg.num_shards, cfg.num_intervals, cfg.num_strata
    slot = jnp.mod(interval, k)

    def rows(x):
        x = x.reshape((w, k, s) + x.shape[1:])
        x = jax.lax.dynamic_index_in_dim(x, slot, axis=1, keepdims=False)
        return x.reshape((w * s,) + x.shape[2:])

    keep = jnp.repeat(holds, s)                                  # [W·S]
    return qt.SampleView(values=rows(view.values),
                         counts=jnp.where(keep, rows(view.counts), 0),
                         taken=jnp.where(keep, rows(view.taken), 0))


def _evaluate_interval(cfg: RuntimeConfig, registry: QueryRegistry,
                       state: RuntimeState, interval: jax.Array,
                       base_key: jax.Array, axis: Optional[str] = None):
    """Watermark-driven emission body: answer every standing query on the
    CLOSED interval's cells (merged kinds and per-key panes read its
    compacted view; session windows read the full ring via the context).

    ``base_key`` seeds the bootstrap paths, folded with the interval id —
    NOT with the ring's evolving lead key, whose fold count depends on
    how many chunks each executor mode had ingested at emission time.
    A chunk-count-independent key is what makes the two modes' emitted
    (interval, answer, bounds) sequences bitwise identical.
    """
    view, stats, aux = _merged_view(cfg, state, axis)
    ctx = _window_ctx(cfg, state, view, stats, aux)
    # Session windows at a close emission cover only CLOSED intervals
    # (ids <= the closing one): open intervals are still accumulating,
    # and an emission must answer over final data.  Note their support
    # is still the ring's CURRENT retention — an executor that ingested
    # further before emitting (a batched flush) may have evicted older
    # closed intervals — so session answers are reproducible per mode
    # (crash recovery is bitwise) but cross-mode bitwise only when the
    # emission points align; the merged/per-key per-interval answers
    # below are cadence-independent unconditionally.
    ctx.activity = ctx.activity & (ctx.slot_interval <= interval)[:, None]
    iview = _closed_view(cfg, view, interval,
                         _slot_holds(cfg, state, interval, aux))
    if cfg.num_shards > 1:
        # As in ``_merged_view``: both placements reduce the same
        # materialized [W·S] rows, so their f32 sums round alike.
        iview = jax.lax.optimization_barrier(iview)
    istats = err.stratum_stats_from_sample(
        iview.values, iview.counts, iview.taken, iview.slot_mask(),
        fixed_order=cfg.num_shards > 1)
    key = jax.random.fold_in(base_key, interval)
    results = registry.evaluate_view(iview, istats, key, ctx=ctx)
    return results, istats


def _apply_controller(cfg: RuntimeConfig, state: RuntimeState,
                      results, stats, latency_s,
                      intervals: Optional[int] = None,
                      axis: Optional[str] = None) -> RuntimeState:
    realized = (results[cfg.accuracy_query] if cfg.accuracy_query
                else err.estimate_mean(stats))
    k = cfg.num_intervals if intervals is None else intervals
    if cfg.num_shards > 1:
        # Per-shard controllers see their local stats but share the global
        # realized width and the (replicated) latency signal.
        def per_shard(c, s):
            return ctl.update(c, cfg.controller, s, realized, latency_s,
                              intervals=k)
        pooled = _pooled_stats(cfg, stats)
        if axis is not None:
            # Mesh: the gathered stats are replicated [W·K·S]; this
            # device's controller consumes its OWN shard's pooled row —
            # bitwise the vmap oracle's row i.
            i = jax.lax.axis_index(axis)
            pooled = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, 0),
                pooled)
        ctrl = jax.vmap(per_shard)(state.ctrl, pooled)
        return dataclasses.replace(state, ctrl=ctrl)
    ctrl = ctl.update(state.ctrl, cfg.controller, _pooled_stats(cfg, stats),
                      realized, latency_s, intervals=k)
    return dataclasses.replace(state, ctrl=ctrl)


def _pooled_stats(cfg: RuntimeConfig, stats: err.StratumStats):
    """Pool the merged (shard ×) interval × stratum cells per stratum.

    The controller's Neyman allocation is per *stratum* (capacity is a
    ``[S]`` knob); the emission's shared stats are per cell. Moments sum
    across a stratum's interval cells: the whole ring's, or at a close the
    closed interval's alone (``[W·S]``). Sharded: ``→ [W, S]`` so each
    shard's controller sees its local window.
    """
    w, s = cfg.num_shards, cfg.num_strata

    def pool(leaf):
        if w > 1:
            return leaf.reshape(w, -1, s).sum(axis=1)
        return leaf.reshape(-1, s).sum(axis=0)

    return err.StratumStats(
        counts=pool(stats.counts), taken=pool(stats.taken),
        sums=pool(stats.sums), sumsqs=pool(stats.sumsqs))


def _stack(chunks: List[TimestampedChunk]) -> TimestampedChunk:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *chunks)


# ---------------------------------------------------------------------------
# Executors.
# ---------------------------------------------------------------------------

class _ExecutorBase:
    """Shared plumbing: state, emission bookkeeping, ad-hoc queries."""

    mode = "base"

    def __init__(self, cfg: RuntimeConfig, registry: QueryRegistry,
                 key: jax.Array,
                 checkpointer: Optional[ckp.Checkpointer] = None,
                 telemetry: Optional[obm.Telemetry] = None):
        if len(registry) == 0:
            raise ValueError("register at least one standing query")
        if cfg.emission not in ("cadence", "watermark"):
            raise ValueError(
                f"unknown emission mode {cfg.emission!r}; expected "
                "'cadence' or 'watermark'")
        if cfg.placement not in ("vmap", "mesh"):
            raise ValueError(
                f"unknown placement {cfg.placement!r}; expected "
                "'vmap' or 'mesh'")
        self._mesh = None
        self._axis: Optional[str] = None
        if cfg.placement == "mesh":
            if cfg.num_shards < 2:
                raise ValueError(
                    "placement='mesh' deploys one device per shard; it "
                    f"needs num_shards > 1 (got {cfg.num_shards}) — use "
                    "the default placement='vmap' for single-shard runs")
            from repro.launch import mesh as lmesh
            self._mesh = lmesh.make_stream_mesh(cfg.num_shards)
            self._axis = lmesh.STREAM_AXIS
        if cfg.emission == "watermark" and (
                cfg.allowed_lateness
                >= (cfg.num_intervals - 1) * cfg.interval_span):
            raise ValueError(
                "emission='watermark' needs allowed_lateness < "
                "(num_intervals - 1) * interval_span "
                f"(got lateness={cfg.allowed_lateness} vs "
                f"{(cfg.num_intervals - 1) * cfg.interval_span}): an "
                "interval must close — the watermark must pass its end — "
                "while its slot is still in the ring, or its answers "
                "would be evicted before they could ever be emitted")
        if cfg.accuracy_query is not None:
            match = [q for q in registry.queries
                     if q.name == cfg.accuracy_query]
            if not match:
                raise ValueError(
                    f"accuracy_query {cfg.accuracy_query!r} is not "
                    "registered")
            if match[0].kind not in ("sum", "mean", "count"):
                raise ValueError(
                    f"accuracy_query {cfg.accuracy_query!r} has kind "
                    f"{match[0].kind!r}; the controller's feedback needs "
                    "a scalar linear estimate (sum/mean/count)")
            if match[0].window != "merged":
                raise ValueError(
                    f"accuracy_query {cfg.accuracy_query!r} has window "
                    f"{match[0].window!r}; the controller's feedback "
                    "needs a SCALAR estimate (per-key/session answers "
                    "are per-key vectors)")
        self.cfg = cfg
        self.registry = registry
        registry.freeze()     # traced steps close over the query list
        self.state = self._place_state(init_state(cfg, key))
        self.checkpointer = checkpointer
        # Host-side observability. The device counters in state.metrics
        # are unconditional; the Telemetry (event log + host mirrors) is
        # the only on/off switch, and every hook it owns fires at a
        # boundary that already synchronized — attaching one changes
        # neither the hot-loop jaxpr nor its trace count (tested).
        self.telemetry: Optional[obm.Telemetry] = None
        # One retrace sentinel per compiled step: the expected traces
        # are declared as budgets (the batched window step raises its
        # budget per new micro-batch shape); anything beyond is the
        # hot loop silently paying trace+compile per call — logged, or
        # raised under REPRO_OBS_STRICT=1 / Telemetry(strict_retrace=).
        self._sentinels: Dict[str, RetraceSentinel] = {}
        self.emissions: List[Emission] = []
        self.chunks_pushed = 0        # stream offset: chunks accepted so far
        self._emission_cursor = 0     # monotonic Emission.index (survives
        #                               restore — the answers cursor a
        #                               downstream dedupes re-emissions by)
        self._items_since_emit = 0
        self._last_latency = 0.0
        # Watermark-driven emission state (host side). The frontier
        # MIRROR tracks the device frontier from chunk times alone —
        # reading an input chunk never blocks on the in-flight step, so
        # the emit/don't-emit decision adds no host sync to the
        # pipelined hot loop. The base key makes per-interval bootstrap
        # draws a function of the interval id, not of how many chunks
        # either executor mode had folded by emission time.
        self._emit_base_key = jax.random.fold_in(key, 0xE31)
        self._host_frontier = np.full((cfg.num_shards,), wmk.NEG_TIME,
                                      np.float32)
        self._emitted_through = -1    # newest interval already emitted
        axis = self._axis
        if cfg.emission == "watermark":
            emit_sentinel = self._sentinel("emit_interval", allowed=1)

            def emit_body(state, interval, base_key, latency_s):
                results, istats = _evaluate_interval(
                    cfg, registry, state, interval, base_key, axis=axis)
                # Per-window pressure: the realized widths fed back are
                # the closed interval's own, and the Neyman allocation
                # is already per interval (intervals=1) — each newly
                # opened interval adopts a capacity sized for ONE pane.
                state = _apply_controller(cfg, state, results, istats,
                                          latency_s, intervals=1,
                                          axis=axis)
                return state, results

            emit_inner = self._shard_wrap(
                emit_body, n_sharded=1, n_replicated=3, out_replicated=1)

            def emit_iv(state, interval, base_key, latency_s):
                emit_sentinel.trace()          # TRACE time only
                return emit_inner(state, interval, base_key, latency_s)

            self._emit_interval_fn = jax.jit(emit_iv, donate_argnums=0)
        query_sentinel = self._sentinel("query", allowed=1)
        query_inner = self._shard_wrap(
            lambda st: _evaluate(cfg, registry, st, axis=axis)[0],
            n_sharded=1, n_replicated=0, out_sharded=0, out_replicated=1)

        def query_fn(st):
            query_sentinel.trace()
            return query_inner(st)

        self._query_fn = jax.jit(query_fn)
        if telemetry is not None:
            self.attach_telemetry(telemetry)

    def _place_state(self, state: RuntimeState) -> RuntimeState:
        """Commit a (host- or single-device-built) state to this
        executor's placement: under ``placement="mesh"`` every leaf's
        leading ``[W]`` axis is sharded one-shard-per-device; otherwise
        the default device.  Checkpoint restore funnels through here so
        a deserialized state lands exactly where a fresh one would."""
        if self._mesh is None:
            return jax.device_put(state)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(
            state, NamedSharding(self._mesh, P(self._axis)))

    def _shard_wrap(self, fn, n_sharded: int, n_replicated: int,
                    out_sharded: int = 1, out_replicated: int = 1):
        """Wrap ``fn`` in shard_map on the stream mesh (identity off-mesh).

        Arguments are ``n_sharded`` leading-[W]-sharded pytrees followed
        by ``n_replicated`` replicated ones; outputs likewise.
        ``check_vma=False``: the replicated outputs are replicated by
        construction (every device merges the same gathered cells), which
        the varying-axes check cannot infer statically.
        """
        if self._mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P
        a = P(self._axis)
        in_specs = (a,) * n_sharded + (P(),) * n_replicated
        outs = (a,) * out_sharded + (P(),) * out_replicated
        return jax.shard_map(fn, mesh=self._mesh, in_specs=in_specs,
                             out_specs=outs[0] if len(outs) == 1 else outs,
                             check_vma=False)

    def _sentinel(self, name: str, allowed: int) -> RetraceSentinel:
        s = RetraceSentinel(f"{self.mode}.{name}", allowed=allowed,
                            on_violation=self._on_retrace)
        # Subclasses create sentinels AFTER super().__init__ has already
        # attached telemetry — honor its strictness override here too.
        if (self.telemetry is not None
                and self.telemetry.strict_retrace is not None):
            s.strict = self.telemetry.strict_retrace
        self._sentinels[name] = s
        return s

    def _on_retrace(self, name: str, traces: int, allowed: int) -> None:
        if self.telemetry is not None:
            self.telemetry.on_retrace(name, traces, allowed)

    def attach_telemetry(self, telemetry: obm.Telemetry) -> None:
        """Attach (or swap) the host-side telemetry hub; logs one
        ``run_meta`` event describing this executor. Benchmarks attach
        a FRESH Telemetry after ``reset()`` so the warm run's events
        don't pollute the timed run's log."""
        self.telemetry = telemetry
        if telemetry.strict_retrace is not None:
            for s in self._sentinels.values():
                s.strict = telemetry.strict_retrace
        telemetry.on_run_meta(self)

    def emit_cells(self) -> dict:
        """Rows of the sample view each standing query's estimator reads
        per emission (``kept``; one key's rows for a per-key or session
        quantile), against the ring's ``W·K·S`` cells (``ring``).

        Python ints from the shapes of an abstract trace of the emission
        (``jax.eval_shape``): no compile, no device work, no retrace."""
        cfg, registry = self.cfg, self.registry
        if cfg.emission == "watermark":
            def body(st):
                return _evaluate_interval(cfg, registry, st, jnp.int32(0),
                                          self._emit_base_key)[0]
        else:
            def body(st):
                return _evaluate(cfg, registry, st)[0]
        registry.rows_fed.clear()
        # Unsharded shapes: the per-shard slicing this path traces is
        # refused on the mesh's [W]-sharded leaves; the rows are the same.
        jax.eval_shape(body, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state))
        return {"ring": int(self.state.window.intervals.counts.size),
                "kept": dict(registry.rows_fed)}

    def gather_words(self) -> int:
        """u32 words one device contributes to the mesh emission's
        all_gather (``dist.gather_cells``): its ``[K·S, N+2]`` cell rows
        plus the aux rows; 0 off the mesh. From the shapes of an
        abstract trace of the packing: no compile, no device work."""
        if self._mesh is None:
            return 0
        cfg = self.cfg

        def local(state):
            window0 = jax.tree.map(lambda x: x[0], state.window)
            return (win.sample_view(window0).values,
                    _pack_aux(cfg, state, window0))

        # One device's block of the [W]-sharded state, as shard_map
        # hands it to the emission.
        block = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape[1:], x.dtype),
            self.state)
        values, aux = jax.eval_shape(local, block)
        return dist.gather_words(*values.shape, aux.shape[0])

    @property
    def emit_trace_count(self) -> int:
        """Traces of the per-interval-close emission step (watermark
        mode) — 1 after warmup, forever."""
        s = self._sentinels.get("emit_interval")
        return 0 if s is None else s.traces

    def query(self) -> Dict[str, Result]:
        """Evaluate every standing query on the current state (ad hoc —
        no controller feedback, no emission record)."""
        return self._query_fn(self.state)

    def reset(self, key: jax.Array) -> None:
        """Restart on a fresh stream, KEEPING compiled steps.

        Benchmarks warm an executor on a stream prefix, reset, then time
        the real run — the jitted steps are instance closures, so timing
        a second instance would re-pay trace+compile inside the timed
        region.
        """
        self.state = self._place_state(init_state(self.cfg, key))
        self.emissions = []
        self.chunks_pushed = 0
        self._emission_cursor = 0
        self._items_since_emit = 0
        self._last_latency = 0.0
        self._emit_base_key = jax.random.fold_in(key, 0xE31)
        self._host_frontier = np.full((self.cfg.num_shards,), wmk.NEG_TIME,
                                      np.float32)
        self._emitted_through = -1
        if self.checkpointer is not None:
            # New stream ⇒ the old run's snapshots must not survive as
            # recovery candidates (offset-dedupe would even skip
            # re-saving over them).
            self.checkpointer.clear()

    def snapshot(self) -> ckp.RuntimeCheckpoint:
        """Capture a complete, serializable checkpoint of this executor
        (state pytree + host cursors). Host-synchronizing — call at
        chunk boundaries, like an emission."""
        return ckp.capture(self)

    def restore(self, ckpt):
        """Restore a checkpoint (a :class:`RuntimeCheckpoint` or its
        serialized bytes), KEEPING compiled steps warm. Replay the
        stream suffix from ``ckpt.stream_offset`` afterwards; the
        continuation is bitwise-identical to an uninterrupted run.
        Returns the (deserialized) checkpoint."""
        t0 = time.perf_counter()
        if isinstance(ckpt, (bytes, bytearray)):
            ckpt = ckp.from_bytes(bytes(ckpt), self.state)
        ckp.restore_into(self, ckpt)
        if self.telemetry is not None:
            self.telemetry.on_checkpoint_restore(
                ckpt.stream_offset, time.perf_counter() - t0)
        return ckpt

    def run(self, chunks: Iterable[TimestampedChunk]) -> List[Emission]:
        for c in chunks:
            self.push(c)
        return self.finalize()

    def push(self, chunk: TimestampedChunk) -> None:
        raise NotImplementedError

    def finalize(self) -> List[Emission]:
        raise NotImplementedError

    def _wm_totals(self, state: RuntimeState):
        wm = state.wm
        if self.cfg.num_shards > 1:
            return (float(jnp.min(wmk.watermark(
                        wm, self.cfg.allowed_lateness))),
                    int(jnp.max(state.open_interval)),
                    int(jnp.sum(wm.on_time)), int(jnp.sum(wm.late)),
                    int(jnp.sum(wm.dropped)))
        return (float(wmk.watermark(wm, self.cfg.allowed_lateness)),
                int(state.open_interval), int(wm.on_time),
                int(wm.late), int(wm.dropped))

    def _advance_frontier(self, chunk: TimestampedChunk) -> None:
        """Advance the host frontier mirror (chunk buffers only — never
        blocks on the in-flight ingest step)."""
        self._host_frontier = wmk.host_frontier(
            self._host_frontier, chunk.times, chunk.mask)

    def _closed_through(self) -> int:
        return wmk.host_closed_through(
            self._host_frontier, self.cfg.allowed_lateness,
            self.cfg.interval_span)

    def _emit_closed(self, latency_s: float) -> int:
        """Emit every newly closed interval, oldest first — the
        watermark-driven emission loop both executors share.

        Exactly-once is the host cursor ``_emitted_through``: each close
        fires one emission with a monotonic ``Emission.index``, and a
        restored executor resumes the cursor from its checkpoint so a
        replayed suffix re-fires the same (interval, index) pairs."""
        cfg = self.cfg
        closed = self._closed_through()
        open_iv = wmk.host_open_interval(self._host_frontier,
                                         cfg.interval_span)
        emitted = 0
        while self._emitted_through < closed:
            j = self._emitted_through + 1
            if j <= open_iv - cfg.num_intervals:
                raise RuntimeError(
                    f"interval {j} left the ring before the watermark "
                    f"closed it (open interval {open_iv}, ring holds "
                    f"{cfg.num_intervals}): one arrival unit advanced "
                    "the frontier across a whole window, so the closed "
                    "interval's sample was recycled unemitted — grow "
                    "num_intervals or shorten the chunk/micro-batch "
                    "event span")
            with obs_spans.span(obs_spans.EMIT):
                self.state, results = self._emit_interval_fn(
                    self.state, jnp.int32(j), self._emit_base_key,
                    jnp.float32(latency_s))
                jax.block_until_ready(results)
                self._record(results, latency_s, interval=j)
            self._emitted_through = j
            emitted += 1
        return emitted

    def _record(self, results, latency_s: float,
                interval: Optional[int] = None) -> Emission:
        with obs_spans.span(obs_spans.READBACK):
            wmark, open_iv, on_time, late, dropped = self._wm_totals(
                self.state)
            cap = self.state.ctrl.capacity
            if self.cfg.num_shards > 1:
                cap = jnp.sum(cap, axis=0)  # global capacity = Σ shard caps
            # Materialize: the recorded capacity must not reference the
            # live state buffer — the next compiled step DONATES the
            # state, which would delete the emission's array out from
            # under the consumer. (Emissions are host records; this is
            # the host sync boundary.)
            cap = np.asarray(cap)
        # The index comes from the monotonic cursor, NOT len(emissions):
        # a restored executor's emissions list restarts empty but its
        # cursor continues from the checkpoint, so re-emitted suffix
        # answers carry the same indices as the uninterrupted run
        # (exactly-once output under index-dedupe).
        em = Emission(index=self._emission_cursor, results=results,
                      watermark=wmark, open_interval=open_iv,
                      on_time=on_time, late=late, dropped=dropped,
                      capacity=cap, latency_s=latency_s,
                      items=self._items_since_emit, interval=interval)
        self.emissions.append(em)
        self._emission_cursor += 1
        self._items_since_emit = 0
        if self.telemetry is not None:
            # Emission IS the host-sync boundary — the results were just
            # blocked on, so sampling/logging here adds no new sync.
            self.telemetry.on_emission(self, em)
        return em


class BatchedExecutor(_ExecutorBase):
    """Micro-batch executor (Spark Streaming analog).

    ONE jitted step per window: scan the shared core over the accumulated
    micro-batch, evaluate the registry from the shared sample pass, apply
    the controller (fed the *previous* step's measured latency — one-step
    -delayed feedback keeps the step pure). The controller's pressure
    signal resizes the micro-batch host-side between windows, quantized
    to powers of two so retracing stays bounded.
    """

    mode = "batched"

    def __init__(self, cfg: RuntimeConfig, registry: QueryRegistry,
                 key: jax.Array,
                 checkpointer: Optional[ckp.Checkpointer] = None,
                 telemetry: Optional[obm.Telemetry] = None):
        super().__init__(cfg, registry, key, checkpointer, telemetry)
        self.batch_chunks = cfg.batch_chunks
        self._pending: List[TimestampedChunk] = []
        self._step_cache: dict = {}
        # Budget starts at 0: each NEW micro-batch shape declares its
        # compile via allow(1) in _window_step, so a RE-trace of an
        # already-seen shape is a violation.
        self._step_sentinel = self._sentinel("window_step", allowed=0)

    def reset(self, key: jax.Array) -> None:
        super().reset(key)
        self.batch_chunks = self.cfg.batch_chunks
        self._pending = []

    def _window_step(self, num_chunks: int, state, stacked, latency_prev):
        """AOT-compiled window step per micro-batch size.

        Compilation happens HERE, outside the timed region of ``_flush``
        — otherwise every pressure-triggered batch resize would measure
        trace+compile of the new scan shape as step latency, re-spiking
        the pressure signal and cascading resizes to the maximum.

        The state argument is DONATED: the [K, S, N_max, …] ring is
        updated in place instead of re-materialized every window (the
        previous ``self.state`` buffer is dead the moment the step runs;
        checkpoints copy out via ``capture`` BETWEEN steps, never across
        one).
        """
        fn = self._step_cache.get(num_chunks)
        if fn is None:
            self._step_sentinel.allow(1)      # declared compile: new shape
            sentinel = self._step_sentinel
            cfg, registry, axis = self.cfg, self.registry, self._axis
            ingest = _ingest_chunk
            if cfg.num_shards > 1:
                ingest = jax.vmap(_ingest_chunk, in_axes=(None, 0, 0))

            if cfg.emission == "watermark":
                # Under watermark-driven emission the micro-batch step is
                # ingest-only: evaluation + controller move to the
                # per-interval-close emissions AFTER the flush, so the
                # emitted answers are a property of event time, not of
                # where the driver drew its batch boundaries.
                def body_fn(state, stacked, latency_prev):
                    def body(st, ch):
                        return ingest(cfg, st, ch), None
                    state, _ = jax.lax.scan(body, state, stacked)
                    return state, None
            else:
                def body_fn(state, stacked, latency_prev):
                    def body(st, ch):
                        return ingest(cfg, st, ch), None
                    state, _ = jax.lax.scan(body, state, stacked)
                    results, stats = _evaluate(cfg, registry, state,
                                               axis=axis)
                    state = _apply_controller(cfg, state, results, stats,
                                              latency_prev, axis=axis)
                    return state, results

            inner = body_fn
            if self._mesh is not None:
                # Stacked micro-batch leaves are [B, W, M]: the scan axis
                # stays whole, the shard axis splits one row per device.
                from jax.sharding import PartitionSpec as P
                a = P(self._axis)
                inner = jax.shard_map(
                    body_fn, mesh=self._mesh,
                    in_specs=(a, P(None, self._axis), P()),
                    out_specs=(a, P()), check_vma=False)

            def step(state, stacked, latency_prev):
                sentinel.trace()
                return inner(state, stacked, latency_prev)

            fn = jax.jit(step, donate_argnums=0).lower(
                state, stacked, latency_prev).compile()
            self._step_cache[num_chunks] = fn
        return fn

    def push(self, chunk: TimestampedChunk) -> None:
        self._pending.append(chunk)
        self._items_since_emit += int(chunk.values.size)
        self.chunks_pushed += 1
        if len(self._pending) >= self.batch_chunks:
            self._flush()
        if self.checkpointer is not None:
            # After the (possible) flush, so a cadence-aligned snapshot
            # sees the freshest incorporated state. Snapshots between
            # flushes snap to the last flush boundary — pending chunks
            # are recovered by replay, not serialized.
            self.checkpointer.maybe(self)

    def _flush(self) -> None:
        if not self._pending:
            return
        stacked = _stack(self._pending)
        if self._mesh is not None:
            from repro.runtime import records
            with obs_spans.span(obs_spans.PLACE):
                stacked = records.place_sharded(stacked, self._mesh,
                                                leading_batch=True)
        pending, n = self._pending, len(self._pending)
        self._pending = []
        lat = jnp.float32(self._last_latency)
        fn = self._window_step(n, self.state, stacked, lat)
        t0 = time.perf_counter()
        self.state, results = fn(self.state, stacked, lat)
        if self.cfg.emission == "watermark":
            jax.block_until_ready(self.state)    # the micro-batch barrier
            self._last_latency = time.perf_counter() - t0
            for c in pending:
                self._advance_frontier(c)
            closes = self._emit_closed(self._last_latency)
            if self.cfg.controller.latency_budget_s is not None:
                self.batch_chunks = ctl.next_batch_chunks(
                    self.batch_chunks,
                    float(jnp.max(self.state.ctrl.pressure)),
                    self.cfg.max_batch_chunks, closes_per_batch=closes)
            if self.telemetry is not None:
                self.telemetry.on_flush(self, self.batch_chunks)
            return
        jax.block_until_ready(results)    # the micro-batch barrier
        self._last_latency = time.perf_counter() - t0
        self._record(results, self._last_latency)
        if self.cfg.controller.latency_budget_s is not None:
            self.batch_chunks = ctl.next_batch_chunks(
                self.batch_chunks,
                float(jnp.max(self.state.ctrl.pressure)),
                self.cfg.max_batch_chunks)
        if self.telemetry is not None:
            self.telemetry.on_flush(self, self.batch_chunks)

    def finalize(self) -> List[Emission]:
        self._flush()
        return self.emissions


class PipelinedExecutor(_ExecutorBase):
    """Pipelined executor (Flink analog).

    Every chunk flows through the jitted core on arrival — incremental
    reservoir + watermark updates with NO window barrier and NO host sync
    in the hot loop (``push`` only dispatches; ``trace_count`` stays 1
    regardless of how many chunks flow, asserted in tests). Standing
    queries are answered continuously: every ``emit_every`` chunks an
    emission evaluates the registry and feeds the controller the measured
    per-chunk latency since the previous emission.
    """

    mode = "pipelined"

    def __init__(self, cfg: RuntimeConfig, registry: QueryRegistry,
                 key: jax.Array,
                 checkpointer: Optional[ckp.Checkpointer] = None,
                 telemetry: Optional[obm.Telemetry] = None):
        super().__init__(cfg, registry, key, checkpointer, telemetry)
        step_sentinel = self._sentinel("step", allowed=1)
        axis = self._axis
        ingest = _ingest_chunk
        if cfg.num_shards > 1:
            ingest = jax.vmap(_ingest_chunk, in_axes=(None, 0, 0))
        step_inner = self._shard_wrap(
            lambda st, ch: ingest(cfg, st, ch),
            n_sharded=2, n_replicated=0, out_sharded=1, out_replicated=0)

        def core(state, chunk):
            step_sentinel.trace()          # fires at TRACE time only
            return step_inner(state, chunk)

        # donate_argnums=0: the ring buffer is updated in place every
        # chunk — the hot loop never re-materializes [K, S, N_max, …].
        # Safe because `push` immediately rebinds self.state to the step
        # output and snapshots copy out (capture/device_get) between
        # pushes, never holding the donated device buffer.
        self._step = jax.jit(core, donate_argnums=0)

        emit_sentinel = self._sentinel("emit", allowed=1)

        def emit_body(state, latency_s):
            results, stats = _evaluate(cfg, registry, state, axis=axis)
            state = _apply_controller(cfg, state, results, stats,
                                      latency_s, axis=axis)
            return state, results

        emit_inner = self._shard_wrap(emit_body, n_sharded=1,
                                      n_replicated=1, out_replicated=1)

        def emit(state, latency_s):
            emit_sentinel.trace()
            return emit_inner(state, latency_s)

        self._emit = jax.jit(emit, donate_argnums=0)
        self._chunks_since_emit = 0
        self._emit_t0 = time.perf_counter()

    @property
    def trace_count(self) -> int:
        """Traces of the per-chunk hot-loop step — 1 after warmup,
        forever (the sync-free contract; guarded by the sentinel)."""
        return self._sentinels["step"].traces

    def reset(self, key: jax.Array) -> None:
        super().reset(key)
        self._chunks_since_emit = 0
        self._emit_t0 = time.perf_counter()

    def push(self, chunk: TimestampedChunk) -> None:
        with obs_spans.span(obs_spans.PUSH):
            if self._chunks_since_emit == 0:
                # The emission period's latency clock starts at its FIRST
                # arrival — idle wall time between periods (or before the
                # first chunk ever) must not read as processing latency.
                self._emit_t0 = time.perf_counter()
            with obs_spans.span(obs_spans.DISPATCH):
                if self._mesh is not None:
                    from repro.runtime import records
                    with obs_spans.span(obs_spans.PLACE):
                        chunk = records.place_sharded(chunk, self._mesh)
                self.state = self._step(self.state, chunk)  # async dispatch
            self._items_since_emit += int(chunk.values.size)
            self._chunks_since_emit += 1
            self.chunks_pushed += 1
            if self.cfg.emission == "watermark":
                # The emit decision reads ONLY the chunk's own buffers
                # (host frontier mirror) — between closes the loop stays
                # dispatch-only, no sync on the in-flight state.
                with obs_spans.span(obs_spans.FRONTIER):
                    self._advance_frontier(chunk)
                    closing = self._closed_through() > self._emitted_through
                if closing:
                    jax.block_until_ready(self.state)  # emission boundary
                    elapsed = time.perf_counter() - self._emit_t0
                    per_chunk = elapsed / max(self._chunks_since_emit, 1)
                    self._last_latency = per_chunk
                    self._emit_closed(per_chunk)
                    self._chunks_since_emit = 0
                    self._emit_t0 = time.perf_counter()
            elif self._chunks_since_emit >= self.cfg.emit_every:
                self._emit_now()
            if self.checkpointer is not None:
                # Cadence boundary only: capture() blocks on the state,
                # but the per-push hot path above stays dispatch-only
                # (trace count and jaxpr asserted unchanged in tests).
                self.checkpointer.maybe(self)

    def _emit_now(self) -> None:
        # Emission boundary — the ONLY place the pipeline touches host.
        jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - self._emit_t0
        per_chunk = elapsed / max(self._chunks_since_emit, 1)
        self._last_latency = per_chunk
        with obs_spans.span(obs_spans.EMIT):
            self.state, results = self._emit(self.state,
                                             jnp.float32(per_chunk))
            jax.block_until_ready(results)
            self._record(results, per_chunk)
        self._chunks_since_emit = 0
        self._emit_t0 = time.perf_counter()

    def finalize(self) -> List[Emission]:
        if self.cfg.emission == "watermark":
            # Watermark emission fires exactly at frontier closes, never
            # at end-of-stream: intervals the watermark hasn't passed
            # stay unemitted (their provisional answers are available
            # via ad-hoc ``query()``), so a resumed stream can still
            # close them exactly once.
            return self.emissions
        if self._chunks_since_emit:
            self._emit_now()
        return self.emissions


Executor = Union[BatchedExecutor, PipelinedExecutor]
