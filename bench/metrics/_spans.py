"""Shared reading of the program's own host spans (not a metric itself).

The executor marks its push and emission with profiler spans
(``repro.obs.spans``): ``stream.push`` holds ``stream.dispatch``,
``stream.frontier`` and, on a push that closes intervals, one
``stream.emit`` per close, each holding one ``stream.readback``. They lie
on the trace's host line beside ``bench.push``, on the device's clock.
The names here are the benchmark's own copy of the program's: a program
without spans leaves none in the trace, and every reader then returns
``None``.
"""
from __future__ import annotations

import statistics

PUSH = "stream.push"
DISPATCH = "stream.dispatch"
FRONTIER = "stream.frontier"
EMIT = "stream.emit"
READBACK = "stream.readback"
NAMES = (PUSH, DISPATCH, FRONTIER, EMIT, READBACK)

#: The benchmark's own spans, as ``bench/trace.py`` names them.
BENCH_PUSH, BENCH_READ = "bench.push", "bench.read"


class Span:
    """One host span and the spans that lie inside it."""

    __slots__ = ("name", "start", "end", "inner")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end
        self.inner = []

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3

    def walk(self):
        """This span and every span inside it, outermost first."""
        yield self
        for sp in self.inner:
            yield from sp.walk()

    def named(self, name: str):
        return [sp for sp in self.walk() if sp.name == name]


def nest(spans, names, t0: float):
    """The spans named in ``names`` that start at or after ``t0``, as a
    forest: each holds the spans that lie inside it (one thread's spans
    nest or follow each other, never overlap in part)."""
    out, stack = [], []
    for name, s, e in sorted(((n, s, e) for n, s, e in spans
                              if n in names and s >= t0),
                             key=lambda sp: (sp[1], -sp[2])):
        sp = Span(name, s, e)
        while stack and (s >= stack[-1].end or e > stack[-1].end):
            stack.pop()
        (stack[-1].inner if stack else out).append(sp)
        stack.append(sp)
    return out


def program_spans(trace):
    """The window's program spans as a forest of pushes; ``None`` where
    the trace holds none."""
    if trace is None:
        return None
    return nest(trace.spans, NAMES, trace.t0) or None


def ingest_median_ms(trace, name: str):
    """Median milliseconds of the spans called ``name`` (the push itself
    or a span inside it) over the pushes that closed no interval."""
    roots = program_spans(trace)
    if roots is None:
        return None
    ms = [sp.ms for push in roots
          if push.name == PUSH and not push.named(EMIT)
          for sp in push.named(name)]
    return statistics.median(ms) if ms else None


def per_close_ms(trace, name: str):
    """Mean over the window's closes (``stream.emit`` spans) of the
    milliseconds spent in spans called ``name`` inside each."""
    roots = program_spans(trace)
    if roots is None:
        return None
    emits = [em for root in roots for em in root.named(EMIT)]
    if not emits:
        return None
    return statistics.fmean(sum(sp.ms for sp in em.named(name))
                            for em in emits)


def idle_by_owner(spans, t0: float, idle):
    """Seconds of the device's idle stretches ``idle`` (``(start, end)``
    pairs) by what the host thread was inside: the innermost program
    span, else ``bench.push`` (the benchmark's own part of a push),
    ``bench.read``, or ``"no span"``."""
    owned = []

    def cover(sp):
        at = sp.start
        for child in sp.inner:
            owned.append((at, child.start, sp.name))
            cover(child)
            at = child.end
        owned.append((at, sp.end, sp.name))

    at = t0
    for root in nest(spans, NAMES + (BENCH_PUSH, BENCH_READ), t0):
        owned.append((at, root.start, "no span"))
        cover(root)
        at = root.end
    owned.append((at, float("inf"), "no span"))
    out = {}
    i = 0
    for s, e in sorted(idle):
        while i < len(owned) and owned[i][1] <= s:
            i += 1
        j = i
        while j < len(owned) and owned[j][0] < e:
            a, b, who = owned[j]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[who] = out.get(who, 0.0) + part
            j += 1
    return out
