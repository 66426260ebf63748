"""Pallas TPU kernel: OASRS reservoir fold — the ingest-path hot loop.

Folds a chunk of ``M`` records into ``S`` per-stratum reservoirs of width
``N`` with *exact sequential* Vitter semantics (Algorithm 1 per stratum).

TPU layout: the reservoirs stay **resident in VMEM across grid steps**
while item tiles stream in from HBM — the classic stationary-accumulator
layout. The per-item dependency chain (counter → acceptance → slot) is
inherently sequential, so the inner body is a ``fori_loop`` on the scalar
unit: item tiles and the per-stratum counters live in SMEM, where a
scalar may be read and written at a dynamic index. VMEM admits only
vector accesses at tile-aligned offsets, so an accepted item is written
by a read-select-write of the native ``(8, 128)`` tile that holds its
slot (:func:`_write_slot`); the wrapper pads the reservoir rows and slots
to whole tiles so that tile never leaves the buffer. Randomness
(acceptance uniforms and replacement-slot uniforms) is precomputed
outside with counter-based PRNG so the kernel itself is deterministic and
replayable.

The grid walks item tiles; reservoir/counter blocks use constant index
maps (revisited blocks persist — TPU grids are sequential on a core).
Interpret mode is chosen by the caller (``kernels/ops.interpret_mode``).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _tile_rows(dtype) -> int:
    """Sublanes of one native VMEM tile for ``dtype`` (8 for 32-bit)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _pad_to_tiles(values: jax.Array) -> jax.Array:
    """Pad a ``[R, N]`` buffer to whole native tiles (no-op if aligned)."""
    r, n = values.shape
    rows = _tile_rows(values.dtype)
    pad_r, pad_n = (-r) % rows, (-n) % _LANES
    if pad_r or pad_n:
        values = jnp.pad(values, ((0, pad_r), (0, pad_n)))
    return values


def _write_slot(ref, row, col, value) -> None:
    """``ref[row, col] = value`` for a VMEM ref, as a read-select-write of
    the aligned native tile holding the cell (Mosaic stores no scalars to
    VMEM and loads no unaligned dynamic windows)."""
    rows = _tile_rows(ref.dtype)
    r0 = pl.multiple_of((row // rows) * rows, rows)
    c0 = pl.multiple_of((col // _LANES) * _LANES, _LANES)
    win = (pl.ds(r0, rows), pl.ds(c0, _LANES))
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    hit = (sub == row - r0) & (lane == col - c0)
    ref[win] = jnp.where(hit, value, ref[win])


def _copy_smem(dst, src, rows: int, cols: int) -> None:
    """Element-wise copy between two ``[rows, cols]`` SMEM refs."""
    def body(c, _):
        dst[c // cols, c % cols] = src[c // cols, c % cols]
        return ()
    jax.lax.fori_loop(0, rows * cols, body, ())


def _vitter_step(c, cap, u, u_slot):
    """One arrival's Algorithm-1 decision: the ``c``-th arrival of a cell
    with capacity ``cap`` is accepted while filling, else with
    probability ``cap / c`` into slot ``floor(u_slot · cap)``."""
    filling = c <= cap
    cap_f = cap.astype(jnp.float32)
    take = filling | (u * c.astype(jnp.float32) < cap_f)
    rslot = jnp.clip(jnp.floor(u_slot * cap_f).astype(jnp.int32),
                     0, jnp.maximum(cap - 1, 0))
    return take, jnp.where(filling, c - 1, rslot)


def _fold_kernel(sid_ref, pay_ref, u_ref, uslot_ref, mask_ref,
                 counts_in_ref, cap_ref, values_in_ref,
                 values_ref, counts_ref, *, block_m: int, num_strata: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        values_ref[...] = values_in_ref[...]
        _copy_smem(counts_ref, counts_in_ref, 1, num_strata)

    def body(j, _):
        s = sid_ref[0, j]
        live = mask_ref[0, j] != 0
        c = counts_ref[0, s] + 1
        take, slot = _vitter_step(c, cap_ref[0, s], u_ref[0, j],
                                  uslot_ref[0, j])

        @pl.when(live & take)
        def _store():
            _write_slot(values_ref, s, slot, pay_ref[0, j])

        counts_ref[0, s] = jnp.where(live, c, c - 1)
        return ()

    jax.lax.fori_loop(0, block_m, body, ())


@functools.partial(jax.jit,
                   static_argnames=("block_m", "interpret"))
def reservoir_fold(stratum_ids: jax.Array, payload: jax.Array,
                   u_accept: jax.Array, u_slot: jax.Array,
                   mask: jax.Array, counts: jax.Array, capacity: jax.Array,
                   values: jax.Array, block_m: int = 512,
                   interpret: bool = False):
    """Fold a chunk into reservoirs (exact sequential semantics).

    Args:
      stratum_ids: ``[M]`` int32.
      payload: ``[M]`` item payloads (float32 values or int32 indices).
      u_accept / u_slot: ``[M]`` float32 uniforms in [0, 1).
      mask: ``[M]`` bool.
      counts: ``[S]`` int32 running ``C_i``.
      capacity: ``[S]`` int32 ``N_i``.
      values: ``[S, N_max]`` current reservoir payloads.
      block_m: item tile; a multiple of 128 when compiled for TPU.

    Returns:
      ``(new_values [S, N_max], new_counts [S])``. The reservoir and
      counter inputs are aliased to the outputs (``input_output_aliases``)
      so a donated, tile-aligned reservoir is updated in place; an
      unaligned one is padded to whole tiles first.
    """
    m = stratum_ids.shape[0]
    s, n_max = values.shape
    mask = mask.astype(jnp.int32)
    if m % block_m != 0:
        pad = block_m - m % block_m
        stratum_ids = jnp.pad(stratum_ids, (0, pad))
        payload = jnp.pad(payload, (0, pad))
        u_accept = jnp.pad(u_accept, (0, pad))
        u_slot = jnp.pad(u_slot, (0, pad))
        mask = jnp.pad(mask, (0, pad))
        m = stratum_ids.shape[0]
    padded = _pad_to_tiles(values)
    grid = (m // block_m,)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    item = lambda: smem((1, block_m), lambda i: (0, i))
    full_vec = smem((1, s), lambda i: (0, 0))
    full_res = pl.BlockSpec(padded.shape, lambda i: (0, 0))
    kernel = functools.partial(_fold_kernel, block_m=block_m, num_strata=s)
    new_values, new_counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[item(), item(), item(), item(), item(),
                  full_vec, full_vec, full_res],
        out_specs=[full_res, full_vec],
        out_shape=[jax.ShapeDtypeStruct(padded.shape, values.dtype),
                   jax.ShapeDtypeStruct((1, s), jnp.int32)],
        # In-place hot path: reservoirs (input 7) and counters (input 5)
        # alias their outputs, composing with the executors' donated
        # step buffers — the ring is mutated, never re-allocated.
        input_output_aliases={7: 0, 5: 1},
        interpret=interpret,
    )(stratum_ids[None, :], payload[None, :], u_accept[None, :],
      u_slot[None, :], mask[None, :], counts[None, :], capacity[None, :],
      padded)
    return new_values[:s, :n_max], new_counts[0]


# ---------------------------------------------------------------------------
# One-shot ingest: the ENTIRE accepted-item path in a single kernel.
# ---------------------------------------------------------------------------

class OneShotResult(NamedTuple):
    """Everything the runtime needs back from one ingest call."""
    values: Any            # pytree of [K, S, N_max] ring payloads
    counts: jax.Array      # [K, S] i32 cell arrival counts
    capacity: jax.Array    # [K, S] i32 cell capacities (post slot reset)
    slot_interval: jax.Array   # [K] i32 — interval now held per ring slot
    max_time: jax.Array    # () f32 — event-time frontier after the chunk
    open_interval: jax.Array   # () i32 — newest interval after the chunk
    on_time: jax.Array     # () i32 cumulative watermark accounting
    late: jax.Array        # () i32
    dropped: jax.Array     # () i32
    chunks: jax.Array      # () i32 — chunks folded (obs)
    items: jax.Array       # () i32 — masked items folded (obs)
    counters: jax.Array    # [6, S] i32 obs rows: ingested/accepted/late/
    #                        dropped/replaced/occupancy (metrics layout)


def _one_shot_kernel(*refs, block_m: int, n_pay: int, k: int, s: int,
                     span: float, lateness: float):
    """Two-phase grid over item tiles; everything else pinned on chip.

    Phase 0 scans the time/mask tiles to land the post-chunk frontier
    (``max_time``/``open_interval``) — the chunk-level max must be known
    before item 0's eviction verdict, so one pass cannot work. Phase 1
    resets recycled ring slots (tile 0), then streams item tiles through
    the sequential Vitter fold (the per-item routing → counter →
    acceptance → slot chain), folding the per-stratum obs counter rows
    and the watermark totals item by item; the final tile derives the
    replacement/occupancy rows from the pre/post cell counts. Item
    tiles, cell counters, watermark scalars and obs rows live in SMEM
    (scalar reads and writes at dynamic indices); the ring lives in VMEM
    and takes tile-aligned vector writes (:func:`_write_slot`). All
    carried blocks use constant index maps — revisited blocks persist
    across the whole grid (TPU grids are sequential on a core) and alias
    their outputs, so the [K·S, N_max] ring never round-trips to HBM
    mid-chunk.
    """
    times_ref, sid_ref = refs[0], refs[1]
    pay_refs = refs[2:2 + n_pay]
    (ua_ref, us_ref, mask_ref, tin_ref, iin_ref, siv_ref, adopt_ref,
     cin_ref, capin_ref) = refs[2 + n_pay:11 + n_pay]
    vin_refs = refs[11 + n_pay:11 + 2 * n_pay]
    min_ref = refs[11 + 2 * n_pay]
    vout_refs = refs[12 + 2 * n_pay:12 + 3 * n_pay]
    (cnt_ref, cap_ref, des_ref, sf_ref, si_ref,
     mout_ref) = refs[12 + 3 * n_pay:]

    phase = pl.program_id(0)
    i = pl.program_id(1)
    n_tiles = pl.num_programs(1)
    span_f = jnp.float32(span)
    i32 = jnp.int32

    def interval_of(t):
        return jnp.floor(t / span_f).astype(i32)

    @pl.when((phase == 0) & (i == 0))
    def _seed_frontier():
        sf_ref[0, 0] = tin_ref[0, 0]
        _copy_smem(si_ref, iin_ref, 1, 8)

    @pl.when(phase == 0)
    def _scan_frontier():
        def body(j, carry):
            tmax, imax = carry
            t = times_ref[0, j]
            live = mask_ref[0, j] != 0
            return (jnp.where(live, jnp.maximum(tmax, t), tmax),
                    jnp.where(live, jnp.maximum(imax, interval_of(t)), imax))

        tmax, imax = jax.lax.fori_loop(0, block_m, body,
                                       (sf_ref[0, 0], si_ref[0, 0]))
        sf_ref[0, 0] = tmax
        si_ref[0, 0] = imax

    @pl.when(phase == 1)
    def _fold():
        new_open = si_ref[0, 0]
        open_before = iin_ref[0, 0]
        wmark = tin_ref[0, 0] - jnp.float32(lateness)  # PRE-chunk watermark
        oldest_live = new_open - jnp.int32(k) + 1

        def desired(slot):
            # Slot j's desired occupant: the newest live interval
            # congruent to it mod K.
            return new_open - jnp.mod(new_open - slot, k)

        def pre_fold_count(c):
            # A recycled slot zeroes its counts (read from the pristine
            # input block, so the final tile can re-derive it too).
            reset = desired(c // s) != siv_ref[0, c // s]
            return reset, jnp.where(reset, 0, cin_ref[0, c])

        @pl.when(i == 0)
        def _reset_ring():
            def reset_cell(c, _):
                reset, c0 = pre_fold_count(c)
                cnt_ref[0, c] = c0
                # A reset slot adopts the controller capacity
                # (precomputed, N_max-clamped).
                cap_ref[0, c] = jnp.where(reset, adopt_ref[0, c % s],
                                          capin_ref[0, c])
                return ()

            jax.lax.fori_loop(0, k * s, reset_cell, ())

            def set_slot(j, _):
                des_ref[0, j] = desired(j)
                return ()

            jax.lax.fori_loop(0, k, set_slot, ())
            for vo, vi in zip(vout_refs, vin_refs):
                vo[...] = vi[...]
            _copy_smem(mout_ref, min_ref, 6, s)
            si_ref[0, 5] = si_ref[0, 5] + 1          # obs: chunks folded

        def body(j, totals):
            # Watermark verdict (per item, as route_chunk) → obs rows →
            # sequential Vitter fold of the (slot, stratum) cell.
            on_time, late, dropped, items = totals
            t = times_ref[0, j]
            sid = sid_ref[0, j]
            mk = mask_ref[0, j] != 0
            tgt = interval_of(t)
            acc = mk & ~(t < wmark) & ~(tgt < oldest_live)
            late_v = acc & (tgt < open_before)
            drop = mk & ~acc
            for row, pred in enumerate((mk, acc, late_v, drop)):
                mout_ref[row, sid] = mout_ref[row, sid] + pred.astype(i32)

            cell = jnp.mod(tgt, k) * s + sid
            c = cnt_ref[0, cell] + 1
            take, slot = _vitter_step(c, cap_ref[0, cell], ua_ref[0, j],
                                      us_ref[0, j])

            @pl.when(acc & take)
            def _store():
                for vo, po in zip(vout_refs, pay_refs):
                    _write_slot(vo, cell, slot, po[0, j])

            cnt_ref[0, cell] = jnp.where(acc, c, c - 1)
            return (on_time + (acc & ~late_v).astype(i32),
                    late + late_v.astype(i32), dropped + drop.astype(i32),
                    items + mk.astype(i32))

        totals = jax.lax.fori_loop(
            0, block_m, body,
            (si_ref[0, 1], si_ref[0, 2], si_ref[0, 3], si_ref[0, 4]))
        for col, total in enumerate(totals, start=1):
            si_ref[0, col] = total

        @pl.when(i == n_tiles - 1)
        def _finalize_counters():
            # replaced[s] = arrivals that hit a FULL cell; occupancy[s] =
            # Σ_K min(count, cap) — both from the pre/post-fold counts.
            def zero(st, _):
                mout_ref[5, st] = 0
                return ()

            jax.lax.fori_loop(0, s, zero, ())

            def fold_cell(c, _):
                _, c0 = pre_fold_count(c)
                c1 = cnt_ref[0, c]
                cp = cap_ref[0, c]
                f0 = jnp.minimum(c0, cp)
                f1 = jnp.minimum(c1, cp)
                st = c % s
                mout_ref[4, st] = mout_ref[4, st] + (c1 - c0) - (f1 - f0)
                mout_ref[5, st] = mout_ref[5, st] + f1
                return ()

            jax.lax.fori_loop(0, k * s, fold_cell, ())


@functools.partial(
    jax.jit,
    static_argnames=("span", "allowed_lateness", "block_m", "interpret"))
def one_shot_ingest(times: jax.Array, stratum_ids: jax.Array, payload,
                    mask: jax.Array, u_accept: jax.Array,
                    u_slot: jax.Array, *,
                    max_time: jax.Array, open_interval: jax.Array,
                    on_time: jax.Array, late: jax.Array,
                    dropped: jax.Array, chunks: jax.Array,
                    items: jax.Array, slot_interval: jax.Array,
                    adopt: jax.Array, counts: jax.Array,
                    capacity: jax.Array, values, counters: jax.Array,
                    span: float, allowed_lateness: float,
                    block_m: int = 256,
                    interpret: bool = False) -> OneShotResult:
    """ONE Pallas call for the whole accepted-item ingest path.

    Fuses watermark routing → interval-ring slot reset → (slot, stratum)
    cell assignment → per-cell counter bump → replacement draw →
    conditional ring write → obs counter fold for an M-item chunk, with
    item tiles double-buffered from HBM into SMEM, the [K·S, N_max] ring
    pinned in VMEM and the counters + accounting pinned in SMEM across
    tiles (constant index maps + ``input_output_aliases``, extending the
    ``reservoir_fold`` aliasing so the ring never round-trips).

    Bitwise contract: identical to the runtime's fused-jnp path —
    routing is ``watermark.route_chunk``'s arithmetic (f32 frontier max,
    pre-chunk watermark, ring eviction), the fold is ``reservoir_fold``'s
    exact sequential Vitter semantics with the same ``floor(u·N_i)``
    replacement-slot convention, and the counter rows reproduce
    ``obs/metrics.ingest_update``. The uniforms are drawn OUTSIDE
    (counter-based PRNG) so the kernel is deterministic and replayable.

    Args:
      times / stratum_ids / mask / u_accept / u_slot: ``[M]`` item tiles.
      payload: pytree of ``[M]`` leaves (scalar payloads; int leaves ride
        along — heavy-hitter keys), structure matching ``values``.
      max_time, open_interval, on_time, late, dropped: pre-chunk
        watermark scalars (``WatermarkState`` + open interval).
      chunks, items: pre-chunk obs scalar totals.
      slot_interval: ``[K]`` i32 — interval currently held per ring slot.
      adopt: ``[S]`` i32 — capacity a reset slot adopts (already clamped
        to ``N_max`` by the caller).
      counts / capacity: ``[K, S]`` i32 cell counters.
      values: pytree of ``[K, S, N_max]`` ring payloads.
      counters: ``[6, S]`` i32 obs rows (``obs.metrics.stack_counters``).
      span / allowed_lateness: static event-time geometry.

    Returns:
      :class:`OneShotResult` — the post-chunk ring, watermark scalars and
      obs counters (the full ``RuntimeState`` delta minus the PRNG key,
      which the caller advances with the same split schedule as the
      fused path).
    """
    pay_leaves, pay_def = jax.tree_util.tree_flatten(payload)
    val_leaves, val_def = jax.tree_util.tree_flatten(values)
    if pay_def != val_def:
        raise ValueError(
            f"payload structure {pay_def} != values structure {val_def}")
    n_pay = len(pay_leaves)
    k = slot_interval.shape[0]
    if counts.shape[0] != k:
        raise ValueError(f"counts {counts.shape} vs K={k} ring")
    s = counts.shape[1]
    n_max = val_leaves[0].shape[-1]
    m = times.shape[0]
    for pv, vv in zip(pay_leaves, val_leaves):
        if vv.shape != (k, s, n_max):
            raise ValueError(
                "one_shot_ingest handles scalar payload layouts only "
                f"([M] items into [K, S, N_max] rings); got values leaf "
                f"{vv.shape}")
        if pv.shape != (m,) or pv.dtype != vv.dtype:
            raise ValueError(
                f"payload leaf {pv.shape}/{pv.dtype} does not match "
                f"items [{m}] / values dtype {vv.dtype}")

    mask = mask.astype(jnp.int32)
    pad = (-m) % block_m
    if pad:
        times = jnp.pad(times, (0, pad))
        stratum_ids = jnp.pad(stratum_ids, (0, pad))
        pay_leaves = [jnp.pad(p, (0, pad)) for p in pay_leaves]
        mask = jnp.pad(mask, (0, pad))          # pad 0: inert items
        u_accept = jnp.pad(u_accept, (0, pad))
        u_slot = jnp.pad(u_slot, (0, pad))
    n_tiles = (m + pad) // block_m
    grid = (2, n_tiles)

    i32 = jnp.int32
    z = jnp.zeros((), i32)
    ints_in = jnp.stack([
        jnp.asarray(open_interval, i32), jnp.asarray(on_time, i32),
        jnp.asarray(late, i32), jnp.asarray(dropped, i32),
        jnp.asarray(items, i32), jnp.asarray(chunks, i32), z, z])[None, :]
    tin = jnp.asarray(max_time, jnp.float32).reshape(1, 1)
    cin = counts.reshape(1, k * s)
    capin = capacity.reshape(1, k * s)
    vflat = [_pad_to_tiles(v.reshape(k * s, n_max)) for v in val_leaves]

    # Item tiles needed in BOTH phases stream (0, i); fold-only tiles pin
    # to block 0 during phase 0 so the frontier scan fetches no dead DMA.
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    stream = lambda: smem((1, block_m), lambda p, i: (0, i))
    foldonly = lambda: smem((1, block_m), lambda p, i: (0, i * p))

    def pinned(*shape, space=pltpu.SMEM):
        return pl.BlockSpec(shape, lambda p, i: (0,) * len(shape),
                            memory_space=space)

    rings = [pinned(*v.shape, space=pltpu.VMEM) for v in vflat]
    in_specs = ([stream(), foldonly()]
                + [foldonly() for _ in range(n_pay)]
                + [foldonly(), foldonly(), stream(),
                   pinned(1, 1), pinned(1, 8), pinned(1, k),
                   pinned(1, s), pinned(1, k * s), pinned(1, k * s)]
                + rings + [pinned(6, s)])
    out_specs = (rings
                 + [pinned(1, k * s), pinned(1, k * s), pinned(1, k),
                    pinned(1, 1), pinned(1, 8), pinned(6, s)])
    out_shape = ([jax.ShapeDtypeStruct(v.shape, v.dtype) for v in vflat]
                 + [jax.ShapeDtypeStruct((1, k * s), i32),
                    jax.ShapeDtypeStruct((1, k * s), i32),
                    jax.ShapeDtypeStruct((1, k), i32),
                    jax.ShapeDtypeStruct((1, 1), jnp.float32),
                    jax.ShapeDtypeStruct((1, 8), i32),
                    jax.ShapeDtypeStruct((6, s), i32)])
    # In-place hot path, extending reservoir_fold's aliasing to EVERY
    # carried block: ring leaves, cell counters/capacities, watermark
    # scalars and obs rows all mutate their (donated) input buffers.
    aliases = {11 + n_pay + j: j for j in range(n_pay)}     # ring leaves
    aliases[9 + n_pay] = n_pay                              # counts
    aliases[10 + n_pay] = n_pay + 1                         # capacity
    aliases[5 + n_pay] = n_pay + 3                          # frontier f32
    aliases[6 + n_pay] = n_pay + 4                          # scalars i32
    aliases[11 + 2 * n_pay] = n_pay + 5                     # obs rows

    kernel = functools.partial(_one_shot_kernel, block_m=block_m,
                               n_pay=n_pay, k=k, s=s, span=span,
                               lateness=allowed_lateness)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(times[None, :], stratum_ids[None, :],
      *[p[None, :] for p in pay_leaves],
      u_accept[None, :], u_slot[None, :], mask[None, :],
      tin, ints_in, slot_interval.astype(i32)[None, :],
      adopt.astype(i32)[None, :], cin, capin, *vflat, counters)

    vout = outs[:n_pay]
    cnt, cap, des, sf, si, mrows = outs[n_pay:]
    return OneShotResult(
        values=jax.tree_util.tree_unflatten(
            val_def, [o[:k * s, :n_max].reshape(k, s, n_max)
                      for o in vout]),
        counts=cnt.reshape(k, s), capacity=cap.reshape(k, s),
        slot_interval=des[0], max_time=sf[0, 0],
        open_interval=si[0, 0], on_time=si[0, 1], late=si[0, 2],
        dropped=si[0, 3], items=si[0, 4], chunks=si[0, 5],
        counters=mrows)
