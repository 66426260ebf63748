"""Puts the benchmark (``bench/``) and the program on ``sys.path`` for the
benchmark's tests, and shrinks a configuration to a size a test holds."""
from __future__ import annotations

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(BENCH, "metrics"), BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def shrink(config: dict, traffic: dict, by: int = 32):
    """The same deployment and traffic, ``by`` times fewer events per
    interval and per chunk, and reservoirs cut alike."""
    config, traffic = dict(config), dict(traffic)
    config["events_per_interval"] //= by
    config["capacity_per_stratum"] = math.ceil(
        config["capacity_per_stratum"] / by)
    traffic["chunk"] //= by
    if traffic.get("rate_events_per_s"):
        traffic["rate_events_per_s"] /= by
    return config, traffic
