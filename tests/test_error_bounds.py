"""Error-estimation tests: Eq. 6/7/9 formulas + CI coverage (§3.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import error as err
from repro.core import oasrs, query

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def test_var_formulas_against_numpy():
    counts = jnp.array([100, 50], jnp.int32)
    taken = jnp.array([10, 50], jnp.int32)
    rng = np.random.default_rng(0)
    x0 = rng.normal(5, 2, 10).astype(np.float32)
    x1 = rng.normal(-1, 3, 50).astype(np.float32)
    stats = err.StratumStats(
        counts=counts, taken=taken,
        sums=jnp.array([x0.sum(), x1.sum()]),
        sumsqs=jnp.array([(x0 ** 2).sum(), (x1 ** 2).sum()]))
    s0 = x0.var(ddof=1)
    expected = 100 * (100 - 10) * s0 / 10    # stratum 1 fully taken → 0
    np.testing.assert_allclose(err.var_sum(stats), expected, rtol=1e-4)
    # Eq 9
    omega0, omega1 = 100 / 150, 50 / 150
    exp_mean = omega0 ** 2 * s0 / 10 * (90 / 100)
    np.testing.assert_allclose(err.var_mean(stats), exp_mean, rtol=1e-4)


@pytest.mark.parametrize("width", [1, 7, 64, 1000])
@pytest.mark.parametrize("fixed_order", [False, True])
def test_stratum_moments_match_float64(width, fixed_order):
    """Both row-sum orders give the masked moments of a float64 sum."""
    rng = np.random.default_rng(width)
    xs = rng.normal(3, 5, (4, width)).astype(np.float32)
    taken = np.array([width, width // 2, 1, 0], np.int32)
    mask = np.arange(width)[None, :] < taken[:, None]
    stats = err.stratum_stats_from_sample(
        jnp.asarray(xs), jnp.full((4,), 2 * width, jnp.int32),
        jnp.asarray(taken), jnp.asarray(mask), fixed_order=fixed_order)
    x64 = np.where(mask, xs, 0.0).astype(np.float64)
    np.testing.assert_allclose(stats.sums, x64.sum(1), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(stats.sumsqs, (x64 ** 2).sum(1), rtol=1e-5)


def test_full_take_is_exact(key):
    """C_i <= N_i ⇒ estimator equals the exact value, variance 0."""
    sid = jax.random.randint(key, (100,), 0, 4)
    x = jax.random.normal(jax.random.fold_in(key, 1), (100,)) * 7
    st_ = oasrs.update_chunk(oasrs.init(4, 128, SPEC, key), sid, x)
    est = query.query_sum(st_)
    np.testing.assert_allclose(est.value, jnp.sum(x), rtol=1e-5)
    assert float(est.variance) == 0.0


def test_error_bound_confidence_levels():
    e = err.Estimate(value=jnp.float32(10.0), variance=jnp.float32(4.0))
    assert float(e.error_bound(0.68)) == pytest.approx(2.0)
    assert float(e.error_bound(0.95)) == pytest.approx(4.0)
    assert float(e.error_bound(0.997)) == pytest.approx(6.0)
    lo, hi = e.interval(0.95)
    assert float(lo) == pytest.approx(6.0) and float(hi) == pytest.approx(14.0)
    with pytest.raises(ValueError):
        e.error_bound(0.5)


def test_ci_coverage_sum():
    """95% CI covers the true SUM in ≥ ~90% of windows (statistical)."""
    m, s, n = 4096, 3, 64
    cover = 0
    trials = 120
    fold = jax.jit(oasrs.update_chunk)
    qsum = jax.jit(query.query_sum)
    for t in range(trials):
        k = jax.random.PRNGKey(t)
        k1, k2 = jax.random.split(k)
        sid = jax.random.choice(k1, s, (m,),
                                p=jnp.array([0.7, 0.25, 0.05]))
        mu = jnp.array([10.0, 100.0, 1000.0])[sid]
        x = mu + jax.random.normal(k2, (m,)) * mu * 0.1
        # sampler key must be independent of the data key (correlated keys
        # correlate acceptance uniforms with values → bias)
        st_ = fold(oasrs.init(s, n, SPEC, jax.random.fold_in(k, 7919)),
                   sid.astype(jnp.int32), x)
        est = qsum(st_)
        lo, hi = est.interval(0.95)
        if float(lo) <= float(jnp.sum(x)) <= float(hi):
            cover += 1
    assert cover / trials >= 0.88, f"coverage {cover / trials}"


def test_merge_stats_adds_variance(key):
    sid = jax.random.randint(key, (500,), 0, 2)
    x = jax.random.normal(jax.random.fold_in(key, 3), (500,)) * 5 + 10
    st1 = oasrs.update_chunk(oasrs.init(2, 16, SPEC, key), sid, x)
    st2 = oasrs.update_chunk(
        oasrs.init(2, 16, SPEC, jax.random.fold_in(key, 9)), sid, x)
    s1, s2 = query.stats(st1), query.stats(st2)
    merged = err.merge_stats(s1, s2)
    np.testing.assert_allclose(
        err.var_sum(merged), err.var_sum(s1) + err.var_sum(s2), rtol=1e-5)
    np.testing.assert_allclose(
        err.estimate_sum(merged).value,
        err.estimate_sum(s1).value + err.estimate_sum(s2).value, rtol=1e-5)


def test_required_sample_size_neyman():
    counts = jnp.array([1000, 1000], jnp.int32)
    s2 = jnp.array([100.0, 1.0])
    alloc = err.required_sample_size_mean(counts, s2, 0.5, z=2.0,
                                          min_per_stratum=1)
    # Neyman: allocation proportional to C_i·s_i → 10:1
    assert float(alloc[0]) / float(alloc[1]) > 5.0
    # tighter target → larger sample
    alloc2 = err.required_sample_size_mean(counts, s2, 0.1, z=2.0,
                                           min_per_stratum=1)
    assert int(alloc2.sum()) >= int(alloc.sum())
