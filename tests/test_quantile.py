"""Quantile-engine tests: estimators, bootstrap coverage, distributed merge.

The nonlinear acceptance bar: on heavy-tailed synthetic streams the
bootstrap 95% CI covers the exact quantile in >= 90% of seeded trials,
and the sharded single-psum path matches the single-shard result while
the ingest program stays free of collectives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core import oasrs, quantile as qt, query, window

SPEC = jax.ShapeDtypeStruct((), jnp.float32)
QS = jnp.array([0.5, 0.9, 0.99])


def _heavy_tailed_state(key, m=60_000, cap=1024):
    k1, k2, k3 = jax.random.split(key, 3)
    sid = jax.random.randint(k1, (m,), 0, 3)
    x = jnp.exp(jax.random.normal(k2, (m,)) * 1.4
                + sid.astype(jnp.float32))
    st = oasrs.update_chunk(oasrs.init(3, cap, SPEC, k3), sid, x)
    return st, x


def test_weighted_quantile_exact_on_uniform_weights(key):
    x = jax.random.normal(key, (4001,))
    w = jnp.ones_like(x)
    valid = jnp.ones(x.shape, jnp.bool_)
    got = qt.weighted_quantile(x, w, valid, QS)
    want = np.quantile(np.asarray(x), np.asarray(QS), method="inverted_cdf")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


def test_weighted_quantile_respects_weights(key):
    # value 0 with weight 9, value 10 with weight 1 → p50 = 0, p95 = 10
    x = jnp.array([0.0, 10.0])
    w = jnp.array([9.0, 1.0])
    valid = jnp.ones((2,), jnp.bool_)
    got = qt.weighted_quantile(x, w, valid, jnp.array([0.5, 0.95]))
    np.testing.assert_allclose(np.asarray(got), [0.0, 10.0])


def test_invert_weighted_cdf_interpolates():
    hist = jnp.array([1.0, 1.0, 2.0])
    edges = jnp.array([0.0, 1.0, 2.0, 3.0])
    got = qt.invert_weighted_cdf(hist, edges, jnp.float32(0.0),
                                 jnp.array([1.0, 2.0, 3.0, 4.0]))
    np.testing.assert_allclose(np.asarray(got), [1.0, 2.0, 2.5, 3.0])


def test_sort_and_hist_methods_agree(key):
    st, x = _heavy_tailed_state(key)
    est_sort = query.query_quantile(st, QS, num_replicates=0)
    est_hist = query.query_quantile(st, QS, method="hist",
                                    num_replicates=0, num_steps=5)
    np.testing.assert_allclose(np.asarray(est_hist.value),
                               np.asarray(est_sort.value), rtol=2e-2)


def test_hist_method_kernel_backed_matches(key):
    st, _ = _heavy_tailed_state(key, m=20_000, cap=256)
    jnp_path = qt.quantile_refine(qt.sample_view(st), QS, use_pallas=False)
    pallas_path = qt.quantile_refine(qt.sample_view(st), QS,
                                     use_pallas=True)
    np.testing.assert_allclose(np.asarray(pallas_path),
                               np.asarray(jnp_path), rtol=1e-4)


def test_quantile_close_to_exact(key):
    """Fast-lane coverage check over a FEW seeds (majority vote): any
    single sample path can land outside a 99.7% interval by draw luck —
    the statistical acceptance bar is the slow 100-trial coverage test
    below; this guards against gross estimator breakage."""
    covered = 0
    for s in range(3):
        st, x = _heavy_tailed_state(jax.random.fold_in(key, s))
        est = query.query_quantile(st, QS, num_replicates=48)
        exact = np.quantile(np.asarray(x), np.asarray(QS))
        lo, hi = est.interval(0.997)
        covered += bool(np.all(np.asarray(lo) <= exact)
                        and np.all(exact <= np.asarray(hi)))
    assert covered >= 2, f"covered in {covered}/3 seeded trials"


@pytest.mark.slow
def test_bootstrap_ci_coverage_1m_stream():
    """Acceptance bar: >= 90/100 seeded trials covered on a 10^6 stream."""
    m = 1_000_000

    @jax.jit
    def trial(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        sid = jax.random.randint(k1, (m,), 0, 3)
        x = jnp.exp(jax.random.normal(k2, (m,)) * 1.4
                    + sid.astype(jnp.float32))
        st = oasrs.update_chunk(oasrs.init(3, 1024, SPEC, k3), sid, x)
        est = qt.query_quantile(st, QS, num_replicates=64, key=k4)
        lo, hi = est.interval(0.95)
        exact = jnp.quantile(x, QS)
        return (lo <= exact) & (exact <= hi)

    covered = np.zeros(QS.shape[0])
    for t in range(100):
        covered += np.asarray(trial(jax.random.PRNGKey(t)))
    assert np.all(covered >= 90), f"coverage per quantile: {covered}/100"


def test_window_quantile_merges_intervals(key):
    w = window.init(3, 2, 4096, SPEC, key)
    xs = []
    for e in range(3):
        k = jax.random.fold_in(key, e)
        sid = jax.random.randint(k, (2000,), 0, 2)
        x = jax.random.normal(jax.random.fold_in(k, 1), (2000,)) + e * 1.0
        xs.append(np.asarray(x))
        fresh = oasrs.update_chunk(
            oasrs.init(2, 4096, SPEC, jax.random.fold_in(k, 2)), sid, x)
        w = window.slide(w, fresh)
    est = window.query_quantile(w, jnp.array([0.5]), num_replicates=0)
    exact = np.quantile(np.concatenate(xs), 0.5)
    # full-take window → weighted sample quantile == exact within grid step
    np.testing.assert_allclose(float(est.value[0]), exact, atol=5e-2)


def test_distributed_quantile_matches_single_shard(key):
    m = 8192
    sid = jax.random.randint(key, (m,), 0, 3)
    x = jnp.exp(jax.random.normal(jax.random.fold_in(key, 1), (m,)))
    mesh = jax.make_mesh((1,), ("data",))

    def shard_fn(sid, x):
        st = oasrs.init(3, 256, SPEC, jax.random.PRNGKey(7))
        st = dist.local_update(st, sid, x)
        est = dist.global_quantile(qt.sample_view(st), QS, (0.0, 50.0),
                                   "data", num_replicates=16,
                                   key=jax.random.PRNGKey(9))
        return est.value, est.variance

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=P(), check_vma=False)
    v, var = jax.jit(fn)(sid, x)
    # single-shard reference: identical state (same key), sort estimator
    st = oasrs.update_chunk(oasrs.init(3, 256, SPEC, jax.random.PRNGKey(7)),
                            sid, x)
    ref = qt.query_quantile(st, QS, num_replicates=0)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ref.value),
                               rtol=2e-2)
    assert np.all(np.asarray(var) >= 0)


def test_ingest_hlo_still_collective_free(key):
    """The new query surface must not leak collectives into ingestion."""
    sid = jnp.zeros((64,), jnp.int32)
    x = jnp.ones((64,))
    st = oasrs.init(2, 8, SPEC, key)
    text = str(jax.make_jaxpr(dist.local_update)(st, sid, x))
    for prim in ("psum", "all_gather", "all_reduce", "ppermute",
                 "all_to_all"):
        assert prim not in text, f"collective {prim} in ingest path!"


def test_query_quantile_deterministic(key):
    st, _ = _heavy_tailed_state(key, m=10_000, cap=128)
    a = query.query_quantile(st, QS, num_replicates=32)
    b = query.query_quantile(st, QS, num_replicates=32)
    np.testing.assert_array_equal(np.asarray(a.value), np.asarray(b.value))
    np.testing.assert_array_equal(np.asarray(a.variance),
                                  np.asarray(b.variance))
