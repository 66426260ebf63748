"""Distributed OASRS tests: no-sync ingestion, single-psum merge,
straggler reweighting (DESIGN.md §2/§3.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as dist
from repro.core import error as err
from repro.core import oasrs, query

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def test_ingest_path_has_no_collectives(key):
    """The paper's central systems claim: sampling needs NO worker sync.
    Check the jaxpr of the (shard_mappable) local update for collectives."""
    sid = jnp.zeros((64,), jnp.int32)
    x = jnp.ones((64,))
    st_ = oasrs.init(2, 8, SPEC, key)
    jaxpr = jax.make_jaxpr(dist.local_update)(st_, sid, x)
    text = str(jaxpr)
    for prim in ("psum", "all_gather", "all_reduce", "ppermute",
                 "all_to_all"):
        assert prim not in text, f"collective {prim} in ingest path!"


def test_sts_pass1_has_collective(key):
    """Contrast: the STS baseline's pass 1 IS a synchronization."""
    def counts_fn(sid):
        local = jnp.zeros((4,), jnp.int32).at[sid].add(1)
        return dist.sts_global_counts(local, "data")

    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(counts_fn, mesh=mesh, in_specs=P("data"),
                       out_specs=P())
    jaxpr = str(jax.make_jaxpr(fn)(jnp.zeros((16,), jnp.int32)))
    assert "psum" in jaxpr


def _simulate_workers(key, num_workers, m_per, cap):
    """vmap-simulated shard_map: per-worker local states + stream."""
    keys = jax.random.split(key, num_workers)

    def worker(k):
        k1, k2, k3 = jax.random.split(k, 3)
        sid = jax.random.choice(k1, 3, (m_per,),
                                p=jnp.array([0.6, 0.3, 0.1]))
        x = jnp.array([10.0, 100.0, 1000.0])[sid] + \
            jax.random.normal(k2, (m_per,))
        st_ = oasrs.init(3, cap, SPEC, k3)
        st_ = dist.local_update(st_, sid.astype(jnp.int32), x)
        return query.stats(st_), jnp.sum(x)

    return jax.vmap(worker)(keys)


def test_distributed_merge_equals_sum_of_locals(key):
    stats, true_sums = _simulate_workers(key, 4, 2048, 64)
    # merge as concatenated strata (Eq. 5)
    merged = err.StratumStats(
        counts=stats.counts.reshape(-1), taken=stats.taken.reshape(-1),
        sums=stats.sums.reshape(-1), sumsqs=stats.sumsqs.reshape(-1))
    est = err.estimate_sum(merged)
    true = float(jnp.sum(true_sums))
    assert abs(float(est.value) - true) < 3 * float(
        jnp.sqrt(est.variance)) + 1e-3


def test_straggler_drop_unbiased(key):
    """Dropping one of w exchangeable workers and inflating by w/(w−1)
    stays unbiased (averaged over seeds)."""
    w = 4
    ests, trues = [], []
    for t in range(30):
        stats, true_sums = _simulate_workers(
            jax.random.fold_in(key, t), w, 1024, 64)
        # drop worker 0
        per_worker = [err.estimate_sum(
            err.StratumStats(counts=stats.counts[i], taken=stats.taken[i],
                             sums=stats.sums[i], sumsqs=stats.sumsqs[i]))
            for i in range(w)]
        alive_vals = sum(float(per_worker[i].value) for i in range(1, w))
        ests.append(alive_vals * w / (w - 1))
        trues.append(float(jnp.sum(true_sums)))
    rel = abs(np.mean(ests) - np.mean(trues)) / np.mean(trues)
    assert rel < 0.03, f"straggler-inflated estimator bias {rel}"


def test_merge_partials_inflation_math():
    """_merge_partials under shard_map with an alive mask."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))

    def body(val, alive):
        local = err.Estimate(value=val[0], variance=jnp.float32(1.0))
        out = dist._merge_partials(local, "data", alive[0])
        return jnp.stack([out.value, out.variance])

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("data"), P("data")), out_specs=P())
    out = fn(jnp.array([5.0]), jnp.array([1.0]))
    assert float(out[0]) == 5.0 and float(out[1]) == 1.0


def test_split_capacity():
    cap = jnp.array([64, 7, 1], jnp.int32)
    per = dist.split_capacity(cap, 4)
    np.testing.assert_array_equal(np.asarray(per), [16, 2, 1])
