"""Reduction of a profiler trace (``.xplane.pb``) to device time.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
operation, named by its HLO text ``%<op> = <shape> ...``), and a host plane
(``/host:CPU``) with one line per thread: the Python thread's line (named
after the process) holds the benchmark's ``TraceAnnotation`` spans
(``bench.push``, ``bench.read``) and the runtime's own
(``PjitFunction(<function>)``, ``DevicePut``, ``np.asarray(jax.Array)``),
on the same clock as the device.

The window is from the first ``bench.push`` to the end of the last device
operation. A device is busy where one of its operations runs (the union of
the ``XLA Ops`` intervals); ``busy_s`` is that union, averaged over the
devices used. Readers ask for module or operation time by name pattern;
the names each pattern matched are kept in ``matched``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
BENCH_SPANS = ("bench.push", "bench.read")


def op_name(hlo_text: str) -> str:
    """``%fusion.5 = f32[...] ...`` -> ``fusion.5``."""
    head = hlo_text.split(" ", 1)[0]
    return head.lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_core(5916387662564026451)`` -> ``jit_core``."""
    return event_name.split("(", 1)[0]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` outside the
    intervals."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """Device time of one traced window, in seconds, per device used."""

    def __init__(self, devices: Dict[int, dict], spans: List[tuple],
                 num_devices: int):
        self.devices = {d: v for d, v in devices.items() if d < num_devices}
        self.spans = spans          # (name, start_s, end_s) host spans
        bench = [(s, e) for n, s, e in spans if n in BENCH_SPANS]
        ends = [e for v in self.devices.values() for _, _, e in v["ops"]]
        self.t0 = min(s for s, _ in bench) if bench else 0.0
        self.t1 = max(ends + [e for _, e in bench]) if bench or ends else 0.0
        self.window_s = self.t1 - self.t0
        n = max(len(self.devices), 1)
        self.busy_s = sum(
            union_length(self._clip(v["ops"])) for v in self.devices.values()
        ) / n
        self.matched: Dict[str, List[str]] = {}

    def _clip(self, evs):
        return [(max(s, self.t0), min(e, self.t1)) for _, s, e in evs
                if e > self.t0 and s < self.t1]

    def _select(self, kind: str, patterns, label: str):
        n = max(len(self.devices), 1)
        runs, secs, names = 0, 0.0, set()
        for v in self.devices.values():
            for name, s, e in v[kind]:
                if s >= self.t0 and any(re.search(p, name) for p in patterns):
                    runs += 1
                    secs += e - s
                    names.add(name)
        self.matched[label] = sorted(names)
        return runs / n, secs / n

    def modules(self, *patterns, label: str = "") -> Tuple[float, float]:
        """``(runs, seconds)`` per device of the compiled programs whose
        name matches any regex in ``patterns``."""
        return self._select("modules", patterns, label or "|".join(patterns))

    def ops(self, *patterns, label: str = "") -> Tuple[float, float]:
        """``(runs, seconds)`` per device of the operations whose name
        matches any regex in ``patterns``."""
        return self._select("ops", patterns, label or "|".join(patterns))

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (per device), and the
        longest idle gaps, each named by the innermost host span around
        its middle."""
        n = max(len(self.devices), 1)
        per_op: Dict[str, float] = {}
        idle = []
        for v in self.devices.values():
            for name, s, e in v["ops"]:
                if s >= self.t0:
                    per_op[name] = per_op.get(name, 0.0) + (e - s) / n
            idle += gaps(self._clip(v["ops"]), self.t0, self.t1)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_doing((s + e) / 2), e - s]
                              for s, e in longest]}

    def host_doing(self, t: float) -> str:
        inside = [(e - s, n) for n, s, e in self.spans if s <= t <= e]
        return min(inside)[1] if inside else "no host span"


def reduce(path: str, num_devices: int) -> Trace:
    """Read ``path`` and keep, per device, its module runs and operations
    (qualified by module), and the spans of the host thread that ran the
    benchmark's loop."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [
                        (module_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] += [(op_name(ev.name), ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9)
                                   for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events]
                if any(name in BENCH_SPANS for name, _, _ in evs):
                    spans += evs
    for dev in devices.values():
        dev["ops"] = _qualify(dev["modules"], dev["ops"])
    return Trace(devices, spans, num_devices)


def _qualify(modules, ops):
    """Prefix each operation with the program it ran in
    (``jit_emit_iv/fusion.5``), so names of different programs stay
    apart."""
    mods = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while i + 1 < len(mods) and mods[i + 1][1] <= s:
            i += 1
        owner = mods[i][0] if mods and mods[i][1] <= s <= mods[i][2] \
            else "?"
        out.append((f"{owner}/{name}", s, e))
    return out
