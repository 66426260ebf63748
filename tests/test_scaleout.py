"""Scale-out tests: mesh deployment vs the vmap oracle, jaxpr collective
contracts, restore-time elastic rescale (checkpoint.migrate), the
sharding-table duplicate guard, and the donation-aliasing regression.

The mesh cases need ``len(jax.devices()) >= 8``; ``tests/conftest.py``
forces ``--xla_force_host_platform_device_count=8`` before the first
jax import, so the whole file runs on the CPU container.
"""
import dataclasses
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import oasrs
from repro.distributed import sharding as sh
from repro.launch import mesh as lmesh
from repro.runtime import (BatchedExecutor, PipelinedExecutor,
                           QueryRegistry, RuntimeConfig,
                           controller as ctl, init_state)
from repro.runtime import checkpoint as ckp
from repro.stream import GaussianSource, StreamAggregator
from repro.stream.replay import ReplayableStream

from harness_rescale import (run_schedule, segment_bounds,
                             sweep_rescale_crash_points)

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def _registry():
    return (QueryRegistry()
            .register("total", "sum")
            .register("avg", "mean")
            .register("p", "quantile", qs=(0.5, 0.9), num_replicates=4)
            .register("top", "heavy_hitters", k=3)
            .register("bykey", "sum", window="per_key")
            .register("sess", "sum", window="session", session_gap=0.75))


def _cfg(w, placement="vmap", emission="cadence", **kw):
    base = dict(num_strata=3, capacity=8, num_intervals=4,
                interval_span=1.0, allowed_lateness=0.5,
                num_shards=w, placement=placement,
                batch_chunks=2, emit_every=2, emission=emission)
    base.update(kw)
    return RuntimeConfig(**base)


def _stream(w, disorder=0.0, seed=7):
    return ReplayableStream(
        aggregator=StreamAggregator(GaussianSource(), seed=seed),
        chunk_size=32, rate=64.0, num_shards=w,
        disorder=disorder, disorder_seed=3)


def _fingerprint(emissions):
    """Everything an emission carries, as comparable host values."""
    out = []
    for e in emissions:
        row = [e.index, e.interval, e.watermark, e.open_interval,
               e.on_time, e.late, e.dropped, e.items,
               np.asarray(e.capacity).tolist()]
        for name, r in sorted(e.results.items()):
            if hasattr(r, "estimate"):      # HeavyHitters
                row.append((name, np.asarray(r.keys).tolist(),
                            np.asarray(r.estimate.value).tolist(),
                            np.asarray(r.estimate.variance).tolist()))
            else:
                row.append((name, np.asarray(r.value).tolist(),
                            np.asarray(r.variance).tolist(),
                            np.asarray(r.error_bound(0.95)).tolist()))
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# Mesh deployment == vmap oracle, bitwise.
# ---------------------------------------------------------------------------

_SWEEP = [
    (PipelinedExecutor, "cadence", 0.0, False),
    (PipelinedExecutor, "watermark", 0.3, False),
    (BatchedExecutor, "cadence", 0.3, False),
    (BatchedExecutor, "watermark", 0.0, False),
    (PipelinedExecutor, "cadence", 0.3, True),
    (PipelinedExecutor, "watermark", 0.0, True),
    (BatchedExecutor, "cadence", 0.0, True),
    (BatchedExecutor, "watermark", 0.3, True),
]


@pytest.mark.parametrize(
    "exec_cls,emission,disorder",
    [pytest.param(c, e, d, marks=[pytest.mark.slow] if slow else [],
                  id=f"{c.mode}-{e}-disorder{d}")
     for c, e, d, slow in _SWEEP])
def test_mesh_matches_vmap_oracle(exec_cls, emission, disorder, key):
    """placement='mesh' on 4 real devices is bitwise-identical to the
    vmapped single-device oracle: every emission field, the Eq. 5–9
    widths, per-key/session answers, and the device obs counters."""
    runs = {}
    for placement in ("vmap", "mesh"):
        ex = exec_cls(_cfg(4, placement, emission), _registry(), key)
        runs[placement] = (ex.run(_stream(4, disorder).prefix(12)), ex)
    ems_v, ex_v = runs["vmap"]
    ems_m, ex_m = runs["mesh"]
    assert len(ems_v) == len(ems_m) and len(ems_v) > 0
    assert _fingerprint(ems_v) == _fingerprint(ems_m)
    # Device telemetry counters ride the same sharded state.
    mv = jax.device_get(ex_v.state.metrics)
    mm = jax.device_get(ex_m.state.metrics)
    for la, lb in zip(jax.tree_util.tree_leaves(mv),
                      jax.tree_util.tree_leaves(mm)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_mesh_ad_hoc_query_matches_vmap(key):
    """query() (ad hoc, no emission) agrees bitwise across placements."""
    outs = {}
    for placement in ("vmap", "mesh"):
        ex = PipelinedExecutor(_cfg(4, placement), _registry(), key)
        for c in _stream(4).prefix(5):
            ex.push(c)
        outs[placement] = ex.query()
    for name in outs["vmap"]:
        ra, rb = outs["vmap"][name], outs["mesh"][name]
        va = ra.estimate.value if hasattr(ra, "estimate") else ra.value
        vb = rb.estimate.value if hasattr(rb, "estimate") else rb.value
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


# ---------------------------------------------------------------------------
# Collective contracts (jaxpr level).
# ---------------------------------------------------------------------------

def test_mesh_ingest_is_collective_free(key):
    """The mesh hot loop must never synchronize shards: the per-chunk
    ingest jaxpr contains NO collective primitives."""
    ex = PipelinedExecutor(_cfg(4, "mesh"), _registry(), key)
    chunk = _stream(4).chunk_at(0)
    jaxpr = str(jax.make_jaxpr(lambda s, c: ex._step(s, c))(
        ex.state, chunk))
    for prim in ("all_gather", "psum", "all_reduce", "ppermute",
                 "all_to_all"):
        assert prim not in jaxpr, f"collective {prim} in mesh ingest!"


def test_mesh_emission_single_gather(key):
    """Each mesh emission performs exactly ONE collective: the tiled
    all_gather in dist.gather_cells (samples + aux ride together), of
    u32 words — a TPU may rewrite integer bit patterns packed as f32."""
    ex = PipelinedExecutor(_cfg(4, "mesh"), _registry(), key)
    jaxpr = str(jax.make_jaxpr(
        lambda s, t: ex._emit(s, t))(ex.state, jnp.float32(0.01)))
    assert jaxpr.count("all_gather[") == 1, "emission must merge once"
    assert re.search(r":u32\[[\d,]*\] = all_gather\[", jaxpr), (
        "the gather must move u32 words")
    for prim in ("psum", "all_reduce", "ppermute", "all_to_all"):
        assert prim not in jaxpr, f"extra collective {prim} in emission"


def test_mesh_placement_validation(key):
    with pytest.raises(ValueError, match="num_shards"):
        PipelinedExecutor(_cfg(1, "mesh"), _registry(), key)
    with pytest.raises(ValueError, match="placement"):
        PipelinedExecutor(_cfg(2, "spmd"), _registry(), key)


def test_make_stream_mesh_validates():
    with pytest.raises(ValueError, match=">= 1"):
        lmesh.make_stream_mesh(0)
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        lmesh.make_stream_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# Restore-time elastic rescale (checkpoint.migrate).
# ---------------------------------------------------------------------------

def _capture_after(w, num_chunks, key, capacity=32):
    ex = PipelinedExecutor(_cfg(w, capacity=capacity), _registry(), key)
    for c in _stream(w).prefix(num_chunks):
        ex.push(c)
    return ckp.capture(ex)


def _tree_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("w_new,n_new", [(2, 16), (3, 11), (8, 4)])
def test_migrate_preserves_totals_and_invariants(w_new, n_new, key):
    """Rescaling 4 shards to ``w_new`` preserves per-cell arrival counts
    exactly (the Eq. 5 C_i sums), keeps ``taken = min(counts, capacity)``
    derivable, clamps every adopted capacity to the new slot buffer, and
    re-pools watermark/metrics totals losslessly."""
    snap = _capture_after(4, 6, key)
    mig = ckp.migrate(snap, w_new, new_max_capacity=n_new)
    assert mig.config["num_shards"] == w_new
    old, new = snap.state, mig.state
    iv_o, iv_n = old.window.intervals, new.window.intervals

    # Same canonical ring on every new shard.
    desired = np.asarray(new.slot_interval)
    assert (desired == desired[0]).all()
    assert int(np.max(new.open_interval)) == int(np.max(old.open_interval))

    # Per-cell arrival totals preserved over participating shards.
    part = np.asarray(old.slot_interval) == desired[0][None, :]  # [W, K]
    c_old = np.where(part[:, :, None], np.asarray(iv_o.counts), 0)
    np.testing.assert_array_equal(c_old.sum(axis=0),
                                  np.asarray(iv_n.counts).sum(axis=0))

    # Satellite-3 clamp: adopted capacity never exceeds the slot buffer.
    assert int(np.max(iv_n.capacity)) <= n_new
    leaf = jax.tree_util.tree_leaves(iv_n.values)[0]
    assert leaf.shape[:4] == (w_new, 4, 3, n_new)

    # Sample conservation: per cell, the new taken prefixes are a
    # sub-multiset of the old pooled live samples (equal when the pool
    # covers the re-split demand).
    t_old = np.minimum(np.asarray(iv_o.counts), np.asarray(iv_o.capacity))
    t_old = np.where(part[:, :, None], t_old, 0)
    t_new = np.minimum(np.asarray(iv_n.counts), np.asarray(iv_n.capacity))
    v_old = np.asarray(jax.tree_util.tree_leaves(iv_o.values)[0])
    v_new = np.asarray(leaf)
    for kk in range(4):
        for ss in range(3):
            pool = np.concatenate(
                [v_old[w, kk, ss, :t_old[w, kk, ss]] for w in range(4)])
            got = np.concatenate(
                [v_new[j, kk, ss, :t_new[j, kk, ss]]
                 for j in range(w_new)])
            assert len(got) <= len(pool)
            ps, gs = np.sort(pool), np.sort(got)
            # sub-multiset check on exact float bits
            i = 0
            for g in gs:
                while i < len(ps) and ps[i] != g:
                    i += 1
                assert i < len(ps), (kk, ss, g)
                i += 1

    # Watermark: frontier pools to the min; totals are lossless.
    np.testing.assert_array_equal(
        np.asarray(new.wm.max_time),
        np.full((w_new,), np.min(np.asarray(old.wm.max_time)), np.float32))
    for f in ("on_time", "late", "dropped"):
        assert int(np.sum(np.asarray(getattr(new.wm, f)))) == \
            int(np.sum(np.asarray(getattr(old.wm, f))))

    # Metrics: cumulative counters lossless; occupancy recomputed.
    for f in ("ingested", "accepted", "late", "dropped", "replaced",
              "chunks", "items"):
        assert np.sum(np.asarray(getattr(new.metrics, f))) == \
            np.sum(np.asarray(getattr(old.metrics, f)))
    np.testing.assert_array_equal(
        np.asarray(new.metrics.occupancy),
        np.minimum(np.asarray(iv_n.counts),
                   np.asarray(iv_n.capacity)).sum(axis=1))

    # Deterministic: migrating the same snapshot twice is bitwise.
    _tree_equal(mig.state, ckp.migrate(snap, w_new,
                                       new_max_capacity=n_new).state)


def test_migrate_to_single_shard_squeezes(key):
    """W' = 1 drops the leading shard axis entirely (the unsharded
    runtime layout) and still preserves the arrival totals."""
    snap = _capture_after(4, 6, key)
    mig = ckp.migrate(snap, 1, new_max_capacity=48)
    iv = mig.state.window.intervals
    assert np.asarray(iv.counts).shape == (4, 3)
    assert np.asarray(mig.state.open_interval).shape == ()
    part = np.asarray(snap.state.slot_interval) == \
        np.asarray(mig.state.slot_interval)[None, :]
    c_old = np.where(part[:, :, None],
                     np.asarray(snap.state.window.intervals.counts), 0)
    np.testing.assert_array_equal(c_old.sum(axis=0), np.asarray(iv.counts))


def test_migrate_validates_args(key):
    snap = _capture_after(2, 2, key, capacity=8)
    with pytest.raises(ValueError, match="new_num_shards"):
        ckp.migrate(snap, 0)
    with pytest.raises(ValueError, match="new_max_capacity"):
        ckp.migrate(snap, 2, new_max_capacity=0)


def test_migrate_overflow_clamp_nmax7(key):
    """The satellite geometry: global capacity 7 over 2 shards allocates
    ceil(7/2)=4 per shard; rescaling to 3 shards must clamp the ceil
    re-split (ceil(8/3)=3 per shard, 9 > 7 global) to the new slot
    buffer — and the rescaled state must actually restore and run."""
    key2 = jax.random.fold_in(key, 1)
    snap = _capture_after(2, 4, key2, capacity=7)
    n_old = jax.tree_util.tree_leaves(
        snap.state.window.intervals.values)[0].shape[3]
    assert n_old == 4                      # ceil(7/2)
    mig = ckp.migrate(snap, 3, new_max_capacity=3)   # ceil(7/3)
    iv = mig.state.window.intervals
    assert int(np.max(np.asarray(iv.capacity))) <= 3
    assert int(np.max(np.minimum(np.asarray(iv.counts),
                                 np.asarray(iv.capacity)))) <= 3
    # End-to-end: a 2 -> 3 rescale under traffic on this geometry.
    executors = {w: PipelinedExecutor(_cfg(w, capacity=7), _registry(),
                                      jax.random.fold_in(key2, w))
                 for w in (2, 3)}
    streams = {w: _stream(w) for w in (2, 3)}
    ref = run_schedule(executors, streams, [(2, 4), (3, 4)], key2)
    assert [e.index for e in ref] == list(range(len(ref)))
    sweep_rescale_crash_points(executors, streams, [(2, 4), (3, 4)],
                               key2, every_chunks=2, crash_points=[2, 4, 6],
                               reference=ref)


# ---------------------------------------------------------------------------
# Rescale crash sweeps: exactly-once across 4 -> 8 -> 4.
# ---------------------------------------------------------------------------

SEGMENTS = [(4, 4), (8, 4), (4, 4)]


def test_rescale_4_8_4_crash_sweep_mesh(key):
    """Grow 4->8 and shrink 8->4 under sustained out-of-order traffic on
    the real device mesh, killing after EVERY chunk (including exactly at
    both rescale boundaries): the deduped output is bitwise the
    uninterrupted schedule's."""
    executors = {w: PipelinedExecutor(_cfg(w, "mesh"), _registry(),
                                      jax.random.fold_in(key, w))
                 for w in (4, 8)}
    streams = {w: _stream(w, disorder=0.3) for w in (4, 8)}
    total = segment_bounds(SEGMENTS)[-1][2]
    sweep_rescale_crash_points(executors, streams, SEGMENTS, key,
                               every_chunks=2,
                               crash_points=list(range(total + 1)))


@pytest.mark.slow
@pytest.mark.parametrize("exec_cls", [PipelinedExecutor, BatchedExecutor])
@pytest.mark.parametrize("placement", ["vmap", "mesh"])
def test_rescale_crash_sweep_watermark(exec_cls, placement, key):
    """The watermark-driven emission mode across both placements and
    executors: every-chunk kill sweep over the 4->8->4 schedule."""
    executors = {w: exec_cls(_cfg(w, placement, "watermark"),
                             _registry(), jax.random.fold_in(key, w))
                 for w in (4, 8)}
    streams = {w: _stream(w, disorder=0.3) for w in (4, 8)}
    total = segment_bounds(SEGMENTS)[-1][2]
    sweep_rescale_crash_points(executors, streams, SEGMENTS, key,
                               every_chunks=2,
                               crash_points=list(range(total + 1)))


# ---------------------------------------------------------------------------
# Sharding-rules table: duplicate-key guard.
# ---------------------------------------------------------------------------

def test_rules_builder_rejects_duplicates():
    with pytest.raises(ValueError, match="duplicate sharding rule"):
        sh._rules(("kv_seq", None), ("mlp", "model"), ("kv_seq", "model"))


def test_default_rules_kv_seq_resolution():
    """The table holds ONE kv_seq entry (local by default); build_rules
    flips it to "model" exactly in the flash-decode TP modes (2/3) and
    keeps it local in head-sharded mode 1."""
    assert sh.DEFAULT_RULES["kv_seq"] is None
    mesh = jax.make_mesh((2,), ("model",))
    mode1 = sh.build_rules(SimpleNamespace(num_kv_heads=2, num_heads=4),
                           mesh)
    assert mode1["kv_heads"] == "model" and mode1["kv_seq"] is None
    mode2 = sh.build_rules(SimpleNamespace(num_kv_heads=1, num_heads=4),
                           mesh)
    assert mode2["q_group"] == "model" and mode2["kv_seq"] == "model"
    mode3 = sh.build_rules(SimpleNamespace(num_kv_heads=1, num_heads=3),
                           mesh)
    assert mode3["attn_seq"] == "model" and mode3["kv_seq"] == "model"


# ---------------------------------------------------------------------------
# Donation-aliasing regression (constructor audit).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2])
def test_init_state_leaves_are_distinct_buffers(w, key):
    """Every leaf of a fresh RuntimeState must own a DISTINCT device
    buffer: the executors donate the whole pytree to their compiled
    steps, and XLA refuses (or corrupts, backend-dependent) donating one
    buffer twice.  Shared-constant init leaves are exactly the aliasing
    class this pins down."""
    st = init_state(_cfg(w) if w > 1 else
                    RuntimeConfig(num_strata=3, capacity=8,
                                  num_intervals=4), key)
    ptrs = [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree_util.tree_leaves(st)]
    assert len(set(ptrs)) == len(ptrs), "aliased state buffers at init"


def test_controller_init_copies_caller_array(key):
    """ctl.init must not adopt the CALLER's buffer as donated state:
    after a donated step consumes the state, the caller's array (and a
    re-init from it) must still be intact."""
    cap = jnp.full((3,), 16, jnp.int32)
    st = ctl.init(cap)
    assert st.capacity.unsafe_buffer_pointer() != \
        cap.unsafe_buffer_pointer()
    assert st.capacity.unsafe_buffer_pointer() != \
        st.base_capacity.unsafe_buffer_pointer()
    jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
            donate_argnums=0)(st)
    np.testing.assert_array_equal(np.asarray(cap), 16)
    st2 = ctl.init(cap)          # re-init after donation must succeed
    np.testing.assert_array_equal(np.asarray(st2.capacity), 16)


def test_oasrs_init_copies_caller_array(key):
    cap = jnp.full((3,), 8, jnp.int32)
    st = oasrs.init(3, cap, SPEC, key)
    assert st.capacity.unsafe_buffer_pointer() != \
        cap.unsafe_buffer_pointer()
    jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
            donate_argnums=0)(st)
    np.testing.assert_array_equal(np.asarray(cap), 8)


def test_executor_reinit_after_donated_run(key):
    """init -> donated steps -> reset -> donated steps: the aliasing
    class breaks exactly this sequence (reset rebuilds state from
    constants a donated step may have consumed)."""
    ex = PipelinedExecutor(_cfg(2), _registry(), key)
    for c in _stream(2).prefix(4):
        ex.push(c)
    ex.reset(jax.random.fold_in(key, 9))
    ems = ex.run(_stream(2).prefix(4))
    assert ems
