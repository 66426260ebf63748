"""The comparison that decides ``correct``.

It holds what the window produced (every emission's answers, read back to
the host; the reservoir ring, the device counters and the retrace count at
the window's end) against ``reference.Reference`` fed with the same pushed
chunks. Each number compared has its limit in the configuration file under
``limits``; an exact comparison has the limit 0. ``PERF.md`` gives the
readings each limit was set from.

Numbers, by layer:

* watermark routing and accounting — ``closes_wrong`` (emitted interval
  sequence and the chunk that fired each, against the reference's closes),
  ``accounting_wrong`` (on-time/late/dropped at each emission, and the
  per-stratum device counters at the end), ``count_wrong`` (every count
  answer against the exact accepted count);
* reservoir fold — ``sample_wrong`` (ring cells whose arrival count,
  capacity or sample is not ``min(C, capacity)`` distinct accepted
  arrivals of that cell) and ``fold_rank_z`` (largest ``|z|`` of a full
  cell's mean arrival rank: a uniform sample sits at the middle);
* emitted estimates and bounds — ``estimate_z`` (largest error of a
  sampled ``sum``/``mean`` answer in units of the standard error a uniform
  sample at the stated capacity has), ``exact_rel_err`` (largest relative
  error of a ``sum``/``mean`` answer over fully taken cells, which has no
  sampling error), ``quantile_z`` (largest distance of a
  quantile answer's exact rank from its level, in units of that rank's
  standard error), ``bound_low`` (largest shortfall, as ``-log``, of a
  query's mean reported 95% bound under the reference's);
* the window itself — ``retraces_in_window`` (compilations the retrace
  sentinels saw after set-up).
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import reference as ref

NUMBERS = ("closes_wrong", "accounting_wrong", "count_wrong",
           "sample_wrong", "fold_rank_z", "estimate_z", "exact_rel_err",
           "quantile_z", "bound_low", "retraces_in_window")


def _split(value):
    return np.ravel(np.asarray(value, np.float64))


def compare(config: dict, reference: ref.Reference, emissions: List[dict],
            ring, counters: Dict[str, np.ndarray],
            retraces: int) -> Dict[str, float]:
    """Readings of every number in :data:`NUMBERS`."""
    cap = int(config["capacity_per_stratum"])
    out = {"retraces_in_window": float(retraces)}

    want = reference.closes
    got = [(e["interval"], e["pushed"]) for e in emissions]
    out["closes_wrong"] = float(
        sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want)))

    wrong = sum((e["on_time"], e["late"], e["dropped"])
                != reference.cumulative[e["pushed"] - 1] for e in emissions)
    for name, arr in reference.per_stratum.items():
        wrong += int(np.sum(np.asarray(counters[name]) != arr))
    out["accounting_wrong"] = float(wrong)

    count_wrong = 0
    est_z = exact_rel = q_z = 0.0
    ratios: Dict[str, List[float]] = {}
    queries = config["queries"]
    for e in emissions:
        iv = e["interval"]
        for q in queries:
            r = e["results"][q["name"]]
            values, variances = _split(r.value), _split(r.variance)
            per_key = q.get("window", "merged") == "per_key"
            keys = range(config["num_strata"]) if per_key else (None,)
            for i, key in enumerate(keys):
                cells = reference.interval_cells(iv, key)
                if sum(len(c) for c in cells) == 0:
                    continue
                hw = ref.Z95 * math.sqrt(max(variances[i], 0.0))
                if q["kind"] == "quantile":
                    x = values[i]
                    qz, ratio = _quantile(x, float(q["qs"][0]), hw, cells,
                                          cap)
                    q_z = max(q_z, qz)
                else:
                    truth, se = ref.linear_truth(q["kind"], cells, cap)
                    if q["kind"] == "count":
                        count_wrong += int(round(values[i]) != truth)
                        continue
                    err = abs(values[i] - truth)
                    if se > 0:
                        est_z = max(est_z, err / se)
                        ratio = hw / (ref.Z95 * se)
                    else:
                        # Every cell fully taken: the answer is exact up
                        # to float32 summation.
                        exact_rel = max(exact_rel, err / abs(truth))
                        ratio = None
                if ratio is not None:
                    ratios.setdefault(q["name"], []).append(ratio)
    out["count_wrong"] = float(count_wrong)
    out["estimate_z"] = est_z
    out["exact_rel_err"] = exact_rel
    out["quantile_z"] = q_z
    low = 0.0
    for rs in ratios.values():
        m = float(np.mean(rs))
        low = max(low, -math.log(m) if m > 0 else math.inf)
    out["bound_low"] = low

    values, counts, capacity, slot_interval = ring
    sample_wrong, rank_z = 0, 0.0
    for w in range(values.shape[0]):
        for k in range(values.shape[1]):
            iv = int(slot_interval[w, k])
            for st in range(values.shape[2]):
                pop = reference.population(w, iv, st)
                c = int(counts[w, k, st])
                n = min(c, cap)
                sample = values[w, k, st, :n]
                if (c != len(pop) or int(capacity[w, k, st]) != cap
                        or not ref.is_submultiset(sample, pop)):
                    sample_wrong += 1
                    continue
                if c > cap:
                    rank_z = max(rank_z, ref.rank_mean_z(sample, pop))
    out["sample_wrong"] = float(sample_wrong)
    out["fold_rank_z"] = rank_z
    return out


def _quantile(x: float, level: float, hw: float, cells, cap: int):
    """``(z, bound ratio)`` of one quantile answer ``x`` at ``level``."""
    lo, hi, allv = ref.rank_band(x, cells)
    dist = max(lo - level, level - hi, 0.0)
    se = ref.quantile_rank_se(x, cells, cap)
    z = dist / (se + 1.0 / len(allv))
    if se <= 0:
        return z, None
    below = np.searchsorted(allv, np.float32(x - hw), side="left")
    above = np.searchsorted(allv, np.float32(x + hw), side="right")
    width = (above - below) / len(allv)
    return z, width / (2 * ref.Z95 * se)


def verdict(readings: Dict[str, float], limits: Dict[str, float]):
    """``(correct, [(name, reading, limit)])``. A number without a limit
    is held to 0, as an exact comparison is."""
    rows = [(n, float(readings[n]), float(limits.get(n, 0.0)))
            for n in NUMBERS]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
