"""The comparison that decides ``correct``, driven through a whole run at a
small size on the CPU: sound runs of every configuration agree with the
numpy reference, and the control and each fault the cell can have make
``correct`` come out false."""
import pytest

import _bench_paths as bp

import faults
import run

# (configuration, traffic, shrink factor): each as small as leaves the
# control's reading above its limit (a keep-first fold's rank z grows
# with the square root of the reservoir).
CELLS = {"netflow": ("caida-netflow", "sat", 32),
         "taxi": ("debs15-taxi", "sat", 8),
         "netflow4w": ("caida-netflow", "sat", 32)}

#: The network deployment as the paper's 4 workers, one shard per
#: (virtual) device.
FOUR_WORKERS = {"num_shards": 4, "placement": "mesh", "chips": 4}

EXACT = ("closes_wrong", "accounting_wrong", "count_wrong", "sample_wrong",
         "retraces_in_window", "failed_closes")


def _run(cell, hooks=None, seed=2**31 + 99):
    name, traffic, by = CELLS[cell]
    # Long enough for five closes at this size on the CPU.
    seconds = 5.0 if cell == "netflow4w" else 2.0
    config, traffic = bp.shrink(bp.load("configs", name),
                                bp.load("traffic", traffic), by)
    if cell == "netflow4w":
        config.update(FOUR_WORKERS)
    with (hooks or faults.Hooks()) as h:
        result, rows = run.measure({"name": cell}, config, traffic, [], [],
                                   seed, seconds, False, hooks=h,
                                   log=lambda *a, **k: None)
    return result, {n: (v, lim) for n, v, lim in rows}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_agrees_with_reference(cell):
    result, checks = _run(cell)
    assert result["attempted"] >= 5 and result["failed"] == 0
    for name in EXACT:
        assert checks[name][0] == 0, (name, checks[name])
    assert result["correct"], checks


# (cell, fault, a number it must fail)
FAULTS = [
    ("netflow", "keep_first", "fold_rank_z"),
    ("taxi", "keep_first", "fold_rank_z"),
    ("netflow", "state_unchanged", "count_wrong"),
    ("taxi", "state_unchanged", "sample_wrong"),
    ("netflow", "half_batch", "count_wrong"),
    ("taxi", "half_batch", "accounting_wrong"),
    ("taxi", "half_batch", "exact_rel_err"),
    ("netflow", "answers_altered", "bound_low"),
    ("taxi", "answers_altered", "quantile_z"),
    ("netflow4w", "no_exchange", "count_wrong"),
    ("netflow4w", "half_batch", "count_wrong"),
]


@pytest.mark.parametrize("cell,fault,number", FAULTS)
def test_fault_makes_correct_false(cell, fault, number):
    result, checks = _run(cell, faults.ALL[fault]())
    value, limit = checks[number]
    assert value > limit, (number, checks)
    assert not result["correct"]
