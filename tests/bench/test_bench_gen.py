"""The benchmark's generator: repeatable per seed, bounded disorder, and
open-loop due times; and the tail statistic over every sample."""
import numpy as np
import pytest

import _bench_paths as bp

import gen
import stats


def _gen(seed, traffic="sat", config="caida-netflow"):
    cfg, tr = bp.shrink(bp.load("configs", config), bp.load("traffic",
                                                             traffic))
    return gen.Generator(cfg, tr, seed), cfg, tr


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**33 + 1])
def test_same_seed_same_stream(seed):
    a, _, _ = _gen(seed)
    b, _, _ = _gen(seed)
    for off in (0, 1, gen.POOL - 1, gen.POOL + 3, 10_000):
        for x, y in zip(a.at(off), b.at(off)):
            np.testing.assert_array_equal(x, y)


def test_seeds_differ():
    a, _, _ = _gen(1)
    b, _, _ = _gen(2)
    assert not np.array_equal(a.at(0)[0], b.at(0)[0])


def test_times_ramp_and_bounded_disorder():
    g, cfg, tr = _gen(3)
    span = tr["chunk"] / cfg["events_per_interval"]
    for off in (0, 5, 999):
        values, sids, times, mask = g.at(off)
        assert values.dtype == np.float32 and sids.dtype == np.int32
        assert mask.all() and times.shape == (tr["chunk"],)
        ramp = np.float32(off * span) + np.arange(
            tr["chunk"], dtype=np.float32) / np.float32(
                cfg["events_per_interval"])
        shift = ramp - times
        assert shift.min() >= -1e-4
        assert shift.max() <= tr["disorder"] + 1e-4
        assert set(np.unique(sids)) <= set(range(cfg["num_strata"]))


def test_sharded_chunks_have_one_row_per_shard():
    cfg, tr = bp.shrink(bp.load("configs", "caida-netflow"),
                        bp.load("traffic", "sat"))
    cfg["num_shards"] = 4
    g = gen.Generator(cfg, tr, 4)
    values, sids, times, mask = g.at(2)
    assert values.shape == (4, tr["chunk"]) == times.shape == mask.shape
    assert not np.array_equal(values[0], values[1])


def test_open_loop_due_times():
    g, cfg, tr = _gen(5, traffic="rate-taxi", config="debs15-taxi")
    period = tr["chunk"] / tr["rate_events_per_s"]
    assert g.due(0) == 0.0
    assert g.due(10) == pytest.approx(10 * period)
    closed, _, _ = _gen(5)
    assert closed.due(10) is None


def test_tail_is_over_every_sample():
    # 200 closes: 190 fast, 10 slow. A median of per-group p95s would
    # hide the slow ones; the p95 over all samples sits between them.
    lat = [100.0] * 190 + [400.0] * 10
    assert stats.percentile(lat, 95) == pytest.approx(
        np.percentile(lat, 95))
    assert stats.percentile(lat, 95) > 100.0
    groups = [stats.percentile(lat[i:i + 20], 95) for i in range(0, 200, 20)]
    assert np.median(groups) == 100.0
    assert stats.percentile(lat, 50) == 100.0


@pytest.mark.parametrize("bursts", [
    [{"seconds": 2.0, "rate_factor": 1.5},
     {"seconds": 2.0, "rate_factor": 0.0}],
    [{"seconds": 1.0, "rate_factor": 0.0}],
])
def test_bursts_are_refused(bursts):
    # No cell offers bursts yet; a traffic file that asks for them is
    # refused rather than run as steady load.
    cfg, tr = bp.shrink(bp.load("configs", "debs15-taxi"),
                        bp.load("traffic", "rate-taxi"))
    tr["bursts"] = bursts
    with pytest.raises(ValueError, match="bursts"):
        gen.Generator(cfg, tr, 1)

