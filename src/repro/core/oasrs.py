"""Online Adaptive Stratified Reservoir Sampling (OASRS) — paper §3.2.

The state is a pure pytree so it can be carried through ``jax.lax.scan``,
``shard_map`` and checkpoints. Two ingestion modes mirror the paper's two
stream-processing models:

* ``update_chunk``   — *batched* model (Spark Streaming): folds a whole
  micro-batch into the reservoirs in one vectorized step. The per-item
  acceptance probabilities are the exact sequential reservoir probabilities
  (``N_i / c`` for the item with running stratum count ``c``), realized by
  ranking items within their stratum inside the chunk. Slot collisions are
  resolved *last-write-wins*, identical to processing the chunk item by item.
  Two bitwise-interchangeable backends: the pure-jnp rank/scatter fold and
  the ``kernels/reservoir.py`` Pallas kernel (``backend="pallas"``, the
  TPU default) — both consume the same per-chunk uniform draws.
* ``update_stream``  — *pipelined* model (Flink): a ``lax.scan`` folding one
  item (or one small vector lane) at a time, i.e. Algorithm 1 of the paper
  applied per stratum.

Both modes produce samples that are distributionally indistinguishable from
the textbook item-at-a-time algorithm (property-tested in
``tests/test_oasrs_stats.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import reservoir as _rk
from repro.utils import (Pytree, bincount, dataclass_pytree,
                         rank_within_stratum, tree_leading_dim)


@dataclass_pytree
@dataclasses.dataclass
class OASRSState:
    """Per-window sampling state.

    Attributes:
      values:   pytree; each leaf ``[S, N_max, ...]`` — reservoir payloads.
      counts:   ``[S]`` int32 — ``C_i``: arrivals per stratum this window.
      capacity: ``[S]`` int32 — ``N_i``: per-stratum reservoir capacity
                (``<= N_max``); the *adaptive* knob set by the cost function.
      key:      PRNG key, advanced on every update.
    """
    values: Pytree
    counts: jax.Array
    capacity: jax.Array
    key: jax.Array

    @property
    def num_strata(self) -> int:
        return self.counts.shape[0]

    @property
    def max_capacity(self) -> int:
        leaf = jax.tree_util.tree_leaves(self.values)[0]
        return leaf.shape[1]

    def taken(self) -> jax.Array:
        """``Y_i = min(C_i, N_i)`` — number of sampled items per stratum."""
        return jnp.minimum(self.counts, self.capacity)

    def weights(self) -> jax.Array:
        """Eq. 1: ``W_i = C_i/N_i`` if ``C_i > N_i`` else 1."""
        c = self.counts.astype(jnp.float32)
        n = jnp.maximum(self.capacity, 1).astype(jnp.float32)
        return jnp.where(self.counts > self.capacity, c / n, 1.0)

    def slot_mask(self) -> jax.Array:
        """``[S, N_max]`` bool — which reservoir slots hold sampled items."""
        slots = jnp.arange(self.max_capacity, dtype=jnp.int32)[None, :]
        return slots < self.taken()[:, None]


def init(
    num_strata: int,
    capacity,
    payload_spec: Pytree,
    key: jax.Array,
    max_capacity: Optional[int] = None,
) -> OASRSState:
    """Create an empty OASRS state.

    Args:
      num_strata: ``S`` — number of strata (sub-streams). Static.
      capacity: int or ``[S]`` int array — per-stratum ``N_i``.
      payload_spec: pytree of ``jax.ShapeDtypeStruct`` describing ONE item's
        payload (e.g. ``ShapeDtypeStruct((), f32)`` for scalar records).
      max_capacity: reservoir allocation ``N_max`` (defaults to
        ``max(capacity)``); lets the adaptive controller grow ``N_i`` later
        without reallocating.
    """
    if max_capacity is None:
        try:
            import numpy as _np
            max_capacity = int(_np.max(_np.asarray(capacity)))
        except Exception as e:
            raise ValueError(
                "capacity is traced; pass static max_capacity=") from e
    # broadcast_to is a no-op view when capacity is already a [S] i32 jax
    # array — materialize a FRESH buffer so a donated step can never
    # delete the caller's array (PR-7 shared-constant aliasing class).
    capacity = jnp.broadcast_to(
        jnp.asarray(capacity, jnp.int32), (num_strata,)) + 0
    values = jax.tree.map(
        lambda s: jnp.zeros((num_strata, max_capacity) + tuple(s.shape),
                            s.dtype),
        payload_spec)
    return OASRSState(
        values=values,
        counts=jnp.zeros((num_strata,), jnp.int32),
        capacity=capacity,
        key=key,
    )


def reset_window(state: OASRSState) -> OASRSState:
    """Start a new window: zero the counters (reservoir contents are dead
    because ``slot_mask`` derives from counts)."""
    return dataclasses.replace(
        state, counts=jnp.zeros_like(state.counts))


# ---------------------------------------------------------------------------
# Batched-model ingestion (Spark-Streaming analog).
# ---------------------------------------------------------------------------

def default_backend() -> str:
    """Chunk-fold backend when the caller passes ``backend=None``: the
    compiled Pallas kernel on TPU, the pure-jnp fold everywhere else —
    the interpreted kernel never lands in the hot path by default."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pallas_eligible(state: OASRSState, payload: Pytree) -> bool:
    """The reservoir kernel handles the scalar-payload layout only:
    a single ``[M]`` payload leaf folding into ``[S, N_max]`` values."""
    return (isinstance(payload, jax.Array) and payload.ndim == 1
            and isinstance(state.values, jax.Array)
            and state.values.ndim == 2)


def apply_chunk_uniforms(
    state: OASRSState,
    stratum_ids: jax.Array,
    payload: Pytree,
    mask: jax.Array,
    u_accept: jax.Array,
    u_slot: jax.Array,
) -> OASRSState:
    """The pure chunk fold given pre-drawn uniforms (key handling is the
    caller's job — the returned state carries ``state.key`` unchanged).

    Bit-identical to folding the chunk item-at-a-time through Algorithm 1
    with the same uniforms (``kernels/ref.reservoir_fold_ref`` is the
    oracle): item ``j`` of stratum ``s`` is the ``counts[s] + rank_j +
    1``-th arrival of that stratum, is accepted with the Vitter
    probability, and later chunk items overwrite earlier ones on slot
    collision (last-write-wins). Exposed separately so callers that fan
    one chunk across several masked folds (the legacy ring-ingest
    reference path) can share ONE uniform draw with the fused fold.
    """
    m = stratum_ids.shape[0]
    s_cnt = state.num_strata
    n_max = state.max_capacity

    # Invalid items are routed to a sentinel stratum S (never queried).
    sid = jnp.where(mask, stratum_ids, s_cnt).astype(jnp.int32)

    occ = rank_within_stratum(sid)                       # rank inside chunk
    c = state.counts[jnp.minimum(sid, s_cnt - 1)] + occ + 1  # arrival index
    cap = state.capacity[jnp.minimum(sid, s_cnt - 1)]

    # Replacement slot = floor(u·N_i), exactly the kernel's arithmetic, so
    # the jnp and Pallas backends are bitwise-interchangeable.
    rand_slot = jnp.clip(
        jnp.floor(u_slot * cap.astype(u_slot.dtype)).astype(jnp.int32),
        0, jnp.maximum(cap - 1, 0))

    filling = c <= cap
    accept_replace = u_accept * c.astype(u_accept.dtype) < \
        cap.astype(u_accept.dtype)
    accept = mask & (filling | accept_replace)
    slot = jnp.where(filling, c - 1, rand_slot)

    # Last-write-wins collision resolution: for each (stratum, slot) cell the
    # *latest* accepted chunk item survives — identical to sequential order.
    flat = sid * n_max + slot                            # [M] cell index
    flat = jnp.where(accept, flat, s_cnt * n_max)        # park rejects
    order = jnp.arange(m, dtype=jnp.int32)
    winner = jnp.full((s_cnt * n_max + 1,), -1, jnp.int32)
    winner = winner.at[flat].max(order)                  # latest j per cell
    winner = winner[: s_cnt * n_max].reshape(s_cnt, n_max)
    has_write = winner >= 0
    src = jnp.maximum(winner, 0)

    def write(res_leaf, pay_leaf):
        new = jnp.take(pay_leaf, src.reshape(-1), axis=0).reshape(
            (s_cnt, n_max) + pay_leaf.shape[1:])
        keep = has_write.reshape(
            (s_cnt, n_max) + (1,) * (pay_leaf.ndim - 1))
        return jnp.where(keep, new, res_leaf)

    values = jax.tree.map(write, state.values, payload)
    counts = state.counts + bincount(
        jnp.where(mask, sid, s_cnt), s_cnt + 1)[:s_cnt]
    return OASRSState(values=values, counts=counts,
                      capacity=state.capacity, key=state.key)


def update_chunk(
    state: OASRSState,
    stratum_ids: jax.Array,
    payload: Pytree,
    mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    block_m: int = 512,
) -> OASRSState:
    """Fold a micro-batch of ``M`` items into the reservoirs.

    Exact sequential semantics (see :func:`apply_chunk_uniforms`); the
    PRNG key is split once per chunk and both uniform vectors (acceptance
    + replacement slot) are drawn up front, so every backend consumes the
    identical random stream.

    Args:
      stratum_ids: ``[M]`` int32 in ``[0, S)``.
      payload: pytree of ``[M, ...]`` leaves.
      mask: optional ``[M]`` bool; ``False`` items are ignored (used for
        ragged tails and for straggler-dropped lanes).
      backend: ``"jnp"`` (vectorized rank/scatter fold), ``"pallas"``
        (the ``kernels/reservoir.py`` hot-path kernel — scalar payloads
        only, VMEM-resident reservoirs across item tiles), or ``None``
        to pick :func:`default_backend` (Pallas on TPU, jnp elsewhere).
        Both backends are bitwise-identical given the same state.
      block_m: item-tile size for the Pallas backend.
    """
    m = stratum_ids.shape[0]
    if mask is None:
        mask = jnp.ones((m,), jnp.bool_)

    key, k_u, k_slot = jax.random.split(state.key, 3)
    u_accept = jax.random.uniform(k_u, (m,))
    u_slot = jax.random.uniform(k_slot, (m,))

    if backend is None or backend == "auto":
        backend = default_backend() if _pallas_eligible(state, payload) \
            else "jnp"
    if backend == "pallas":
        if not _pallas_eligible(state, payload):
            raise ValueError(
                "backend='pallas' needs a single scalar payload leaf "
                "([M] items into [S, N_max] reservoirs); got payload "
                f"{jax.tree_util.tree_structure(payload)}")
        # Imported here: kernels/ops imports this module at top level.
        from repro.kernels.ops import interpret_mode
        new_values, new_counts = _rk.reservoir_fold(
            stratum_ids.astype(jnp.int32), payload, u_accept, u_slot,
            mask, state.counts, state.capacity, state.values,
            block_m=block_m, interpret=interpret_mode())
        return OASRSState(values=new_values, counts=new_counts,
                          capacity=state.capacity, key=key)
    if backend != "jnp":
        raise ValueError(f"unknown backend {backend!r}; "
                         "expected 'jnp', 'pallas' or None")
    out = apply_chunk_uniforms(state, stratum_ids, payload, mask,
                               u_accept, u_slot)
    return dataclasses.replace(out, key=key)


# ---------------------------------------------------------------------------
# Pipelined-model ingestion (Flink analog).
# ---------------------------------------------------------------------------

def update_item(
    state: OASRSState,
    stratum_id: jax.Array,
    payload: Pytree,
    mask: jax.Array | bool = True,
) -> OASRSState:
    """Algorithm 1 applied to one arriving item (pipelined operator)."""
    key, k_u, k_slot = jax.random.split(state.key, 3)
    s = stratum_id.astype(jnp.int32)
    c = state.counts[s] + 1
    cap = state.capacity[s]
    filling = c <= cap
    u = jax.random.uniform(k_u, ())
    accept = jnp.asarray(mask) & (
        filling | (u * c.astype(u.dtype) < cap.astype(u.dtype)))
    slot = jnp.where(
        filling, c - 1,
        jax.random.randint(k_slot, (), 0, jnp.maximum(cap, 1), jnp.int32))

    def write(res_leaf, pay_leaf):
        old = res_leaf[s, slot]
        return res_leaf.at[s, slot].set(jnp.where(accept, pay_leaf, old))

    values = jax.tree.map(write, state.values, payload)
    counts = state.counts.at[s].add(
        jnp.asarray(mask).astype(jnp.int32))
    return OASRSState(values=values, counts=counts,
                      capacity=state.capacity, key=key)


def update_stream(
    state: OASRSState,
    stratum_ids: jax.Array,
    payload: Pytree,
    mask: Optional[jax.Array] = None,
) -> OASRSState:
    """Pipelined ingestion of ``T`` items via ``lax.scan`` (one at a time).

    This is the Flink-mode operator: each item flows through the sampler as
    it arrives; no batch is formed first.
    """
    t = stratum_ids.shape[0]
    if mask is None:
        mask = jnp.ones((t,), jnp.bool_)

    def body(st, xs):
        sid, pay, mk = xs
        return update_item(st, sid, pay, mk), None

    state, _ = jax.lax.scan(body, state, (stratum_ids, payload, mask))
    return state


def update_pipelined_chunks(
    state: OASRSState,
    stratum_ids: jax.Array,
    payload: Pytree,
    lane: int = 64,
    mask: Optional[jax.Array] = None,
) -> OASRSState:
    """Pipelined ingestion with small vector lanes (TPU-friendly Flink mode).

    TPU adaptation note (DESIGN.md §2): a literal item-at-a-time scan wastes
    the VPU; instead the stream is folded ``lane`` items at a time — small
    enough to bound ingest latency, wide enough to vectorize. Semantics are
    identical to ``update_stream``.
    """
    t = stratum_ids.shape[0]
    if t % lane != 0:
        raise ValueError(f"stream length {t} not divisible by lane {lane}")
    if mask is None:
        mask = jnp.ones((t,), jnp.bool_)
    ids = stratum_ids.reshape(t // lane, lane)
    pays = jax.tree.map(
        lambda x: x.reshape((t // lane, lane) + x.shape[1:]), payload)
    masks = mask.reshape(t // lane, lane)

    def body(st, xs):
        sid, pay, mk = xs
        return update_chunk(st, sid, pay, mk), None

    state, _ = jax.lax.scan(body, state, (ids, pays, masks))
    return state


# ---------------------------------------------------------------------------
# Sample extraction.
# ---------------------------------------------------------------------------

def sample_with_weights(
    state: OASRSState,
    extract: Callable[[Pytree], jax.Array] = lambda p: p,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Return ``(x, w, valid)`` flattened over all reservoir slots.

    ``x[k]`` is the extracted scalar of slot ``k``; ``w[k]`` its stratum
    weight ``W_i``; ``valid[k]`` whether the slot holds a sampled item.
    """
    xs = extract(state.values)                     # [S, N_max]
    w = jnp.broadcast_to(state.weights()[:, None], xs.shape)
    valid = state.slot_mask()
    return xs.reshape(-1), w.reshape(-1), valid.reshape(-1)
