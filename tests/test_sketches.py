"""Sketch tests: heavy-hitter recall on Zipf streams, distinct counts,
single-psum key-count merge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core import oasrs, quantile as qt, query, sketches as sk, window

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def _zipf_stream(key, m, num_keys=200, alpha=1.2):
    ranks = jnp.arange(1, num_keys + 1, dtype=jnp.float32)
    p = 1.0 / ranks ** alpha
    keys = jax.random.choice(key, num_keys, (m,), p=p / jnp.sum(p))
    return keys.astype(jnp.float32)


def test_heavy_hitters_exact_on_full_take(key):
    x = _zipf_stream(key, 4096)
    sid = jnp.zeros((4096,), jnp.int32)
    st = oasrs.update_chunk(oasrs.init(1, 4096, SPEC, key), sid, x)
    hh = query.query_heavy_hitters(st, 5)
    true = np.bincount(np.asarray(x).astype(int), minlength=200)
    want_keys = np.argsort(true)[::-1][:5]
    np.testing.assert_array_equal(
        np.sort(np.asarray(hh.keys)), np.sort(want_keys.astype(np.float32)))
    got = {float(k): float(v) for k, v in zip(hh.keys, hh.estimate.value)}
    for wk in want_keys:
        assert got[float(wk)] == true[wk]
    # full take → zero variance
    np.testing.assert_allclose(np.asarray(hh.estimate.variance), 0.0,
                               atol=1e-3)


def test_heavy_hitter_recall_on_sampled_zipf(key):
    """Top-5 recall >= 0.8 (avg over seeds) at ~4% sampling fraction."""
    m, cap, k_top = 50_000, 2048, 5
    recalls = []
    for t in range(5):
        kk = jax.random.fold_in(key, t)
        x = _zipf_stream(kk, m)
        sid = jnp.zeros((m,), jnp.int32)
        st = oasrs.update_chunk(
            oasrs.init(1, cap, SPEC, jax.random.fold_in(kk, 1)), sid, x)
        hh = query.query_heavy_hitters(st, k_top)
        true = np.bincount(np.asarray(x).astype(int), minlength=200)
        want = set(np.argsort(true)[::-1][:k_top].tolist())
        got = set(np.asarray(hh.keys).astype(int).tolist())
        recalls.append(len(want & got) / k_top)
    assert np.mean(recalls) >= 0.8, f"recall {recalls}"


def test_heavy_hitter_estimates_near_truth(key):
    m, cap = 50_000, 2048
    x = _zipf_stream(key, m)
    sid = jnp.zeros((m,), jnp.int32)
    st = oasrs.update_chunk(oasrs.init(1, cap, SPEC, key), sid, x)
    hh = query.query_heavy_hitters(st, 3)
    true = np.bincount(np.asarray(x).astype(int), minlength=200)
    for kf, est, var in zip(hh.keys, hh.estimate.value,
                            hh.estimate.variance):
        bound = 3 * np.sqrt(max(float(var), 0.0))
        assert abs(float(est) - true[int(kf)]) < bound + 0.05 * true[int(kf)]


def test_key_counts_are_linear_queries(key):
    """key_counts == query_count on the same indicator, key by key."""
    m = 3000
    x = _zipf_stream(key, m, num_keys=20)
    sid = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, 2)
    st = oasrs.update_chunk(oasrs.init(2, 256, SPEC, key), sid, x)
    keys = jnp.array([0.0, 1.0, 5.0])
    est = sk.key_counts(qt.sample_view(st), keys)
    for i, kf in enumerate(keys):
        ref = query.query_count(st, lambda v: v == kf)
        np.testing.assert_allclose(float(est.value[i]), float(ref.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(est.variance[i]),
                                   float(ref.variance), rtol=1e-4,
                                   atol=1e-5)


def test_distinct_exact_when_no_singletons(key):
    """Chao1 reduces to the plain distinct count when f1 = 0."""
    x = jnp.repeat(jnp.arange(32, dtype=jnp.float32), 8)   # every key ×8
    sid = jnp.zeros((256,), jnp.int32)
    st = oasrs.update_chunk(oasrs.init(1, 256, SPEC, key), sid, x)
    est = query.query_distinct(st, num_replicates=0)
    assert float(est.value) == 32.0


def test_distinct_estimates_undercount_bounded(key):
    m, cap, nk = 50_000, 2048, 200
    x = _zipf_stream(key, m, num_keys=nk)
    sid = jnp.zeros((m,), jnp.int32)
    st = oasrs.update_chunk(oasrs.init(1, cap, SPEC, key), sid, x)
    est = query.query_distinct(st, num_replicates=32)
    true_d = len(np.unique(np.asarray(x)))
    # Chao1 is a lower-bound-style estimator: sane range, not wild
    assert 0.5 * true_d <= float(est.value) <= 1.5 * true_d
    assert float(est.variance) >= 0


def test_window_heavy_hitters(key):
    w = window.init(2, 1, 4096, SPEC, key)
    allx = []
    for e in range(2):
        kk = jax.random.fold_in(key, e)
        x = _zipf_stream(kk, 2000)
        allx.append(np.asarray(x))
        fresh = oasrs.update_chunk(
            oasrs.init(1, 4096, SPEC, jax.random.fold_in(kk, 1)),
            jnp.zeros((2000,), jnp.int32), x)
        w = window.slide(w, fresh)
    hh = window.query_heavy_hitters(w, 3)
    true = np.bincount(np.concatenate(allx).astype(int), minlength=200)
    want = np.sort(np.argsort(true)[::-1][:3].astype(np.float32))
    np.testing.assert_array_equal(np.sort(np.asarray(hh.keys)), want)
    for kf, v in zip(hh.keys, hh.estimate.value):
        assert float(v) == true[int(kf)]


def test_global_key_counts_single_psum_matches_local(key):
    m = 4096
    x = _zipf_stream(key, m, num_keys=50)
    sid = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, 2)
    keys = jnp.array([0.0, 1.0, 2.0])
    mesh = jax.make_mesh((1,), ("data",))

    def shard_fn(sid, x):
        st = oasrs.init(2, 128, SPEC, jax.random.PRNGKey(3))
        st = dist.local_update(st, sid, x)
        est = dist.global_key_counts(qt.sample_view(st), keys, "data")
        return est.value, est.variance

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=P(), check_vma=False)
    v, var = jax.jit(fn)(sid, x)
    st = oasrs.update_chunk(oasrs.init(2, 128, SPEC, jax.random.PRNGKey(3)),
                            sid, x)
    ref = sk.key_counts(qt.sample_view(st), keys)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ref.value),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(ref.variance),
                               rtol=1e-4, atol=1e-5)
    # exactly one psum in the whole query program
    text = str(jax.make_jaxpr(fn)(sid, x))
    assert text.count("psum") == 1, f"{text.count('psum')} psums"


def test_global_histogram_matches_local(key):
    m = 4096
    sid = jax.random.randint(key, (m,), 0, 3)
    x = jax.random.uniform(jax.random.fold_in(key, 1), (m,)) * 10
    edges = jnp.linspace(0.0, 10.0, 9)
    mesh = jax.make_mesh((1,), ("data",))

    def shard_fn(sid, x):
        st = oasrs.init(3, 128, SPEC, jax.random.PRNGKey(5))
        st = dist.local_update(st, sid, x)
        est = dist.global_histogram(qt.sample_view(st), edges, "data")
        return est.value, est.variance

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=P(), check_vma=False)
    v, var = jax.jit(fn)(sid, x)
    st = oasrs.update_chunk(oasrs.init(3, 128, SPEC, jax.random.PRNGKey(5)),
                            sid, x)
    ref = query.query_histogram(st, edges)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ref.value),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(ref.variance),
                               rtol=1e-4, atol=1e-4)
