"""The serve engine's ticks, read from the program's own spans in the
profiler trace.

With ``repro.obs`` tracing on, every span the engine opens is also a
``jax.profiler`` annotation of the same name, so it lies in the host plane
of the ``.xplane.pb`` on the device's clock.  ``reduce`` keeps, inside the
``bench.window`` span:

* the ``serve.tick`` spans that lie wholly in the window, and device 0's
  idle time inside them;
* the device programs (``XLA Modules`` runs) that start inside those
  ticks, by program name without the fingerprint;
* every idle gap of device 0 in the window, attributed to the innermost
  span that covers its midpoint, among the benchmark's own host spans
  (``trace.HOST_SPANS``) and the program's ``serve.*`` spans
  ("host.other" when none does).

A trace of a program without these annotations holds no ``serve.tick``
span; it reduces to ``ticks == 0`` and the readers give nothing.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import pathlib

from benchlib import trace
from benchlib.harness import SCRATCH

PROGRAM_SPANS = "serve."            # the engine's span names start so
TICK = "serve.tick"
DEVICE0 = "/device:TPU:0"


@dataclasses.dataclass
class TickSummary:
    ticks: int                      # serve.tick spans wholly in the window
    idle_ns: float                  # device 0 idle inside those ticks
    launches: dict                  # program name -> runs started in them
    window_idle_ns: float           # device 0 idle in the whole window
    idle_gaps: dict                 # innermost span -> idle ns (window)

    def program_share(self) -> float:
        """Share of the window's idle time attributed to program spans."""
        ns = sum(v for k, v in self.idle_gaps.items()
                 if k.startswith(PROGRAM_SPANS))
        return ns / self.window_idle_ns if self.window_idle_ns else 0.0

    def breakdown(self, top: int = 12) -> dict:
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        progs = sorted(self.launches.items(), key=lambda kv: -kv[1])[:top]
        return {"ticks": self.ticks,
                "tick_idle_ms": (self.idle_ns / 1e6 / self.ticks
                                 if self.ticks else None),
                "launches_per_tick": (sum(self.launches.values())
                                      / self.ticks if self.ticks else None),
                "idle_gaps": [[k, v / 1e9] for k, v in gaps],
                "program_idle_share": self.program_share(),
                "launches": progs}


def _idle(busy, w0, w1):
    """The complement of the merged ``busy`` intervals in [w0, w1]."""
    out, edge = [], w0
    for s, e in trace._merged(busy):
        if s > edge:
            out.append((edge, min(s, w1)))
        edge = max(edge, e)
    if edge < w1:
        out.append((edge, w1))
    return out


def _attribute(gaps, spans):
    """Idle ns per innermost span covering each gap's midpoint."""
    out = collections.Counter()
    spans = sorted(spans, key=lambda h: h[1])
    active, nxt = [], 0
    for a, b in gaps:
        mid = (a + b) / 2
        while nxt < len(spans) and spans[nxt][1] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [h for h in active if h[1] + h[2] >= mid]
        who = min(active, key=lambda h: h[2])[0] if active else "host.other"
        out[who] += b - a
    return out


def reduce(path) -> TickSummary:
    pd = trace.load(path)
    host, dev0 = [], None
    for plane in pd.planes:
        if plane.name == DEVICE0:
            dev0 = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if (e.name == trace.WINDOW or e.name in trace.HOST_SPANS
                            or e.name.startswith(PROGRAM_SPANS)):
                        host.append((e.name, e.start_ns, e.duration_ns))
    windows = [h for h in host if h[0] == trace.WINDOW]
    if not windows or dev0 is None:
        raise ValueError(f"{path}: no {trace.WINDOW} span or no {DEVICE0}")
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]
    lines = {line.name: line for line in dev0.lines}
    busy = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ())]
    gaps = _idle([iv for iv in busy if iv[1] > iv[0]], w0, w1)
    spans = [h for h in host if h[0] != trace.WINDOW
             and h[1] + h[2] > w0 and h[1] < w1]
    ticks = sorted((h[1], h[1] + h[2]) for h in spans
                   if h[0] == TICK and w0 <= h[1] and h[1] + h[2] <= w1)
    starts = [t[0] for t in ticks]

    def in_tick(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t <= ticks[i][1]

    idle_ns = 0.0
    gap_starts = [g[0] for g in gaps]
    for t0, t1 in ticks:
        i = max(bisect.bisect_right(gap_starts, t0) - 1, 0)
        while i < len(gaps) and gaps[i][0] < t1:
            idle_ns += max(0, min(gaps[i][1], t1) - max(gaps[i][0], t0))
            i += 1
    launches = collections.Counter(
        e.name.split("(", 1)[0]
        for e in (lines["XLA Modules"].events if "XLA Modules" in lines
                  else ())
        if in_tick(e.start_ns))
    return TickSummary(
        ticks=len(ticks), idle_ns=idle_ns, launches=dict(launches),
        window_idle_ns=sum(b - a for a, b in gaps),
        idle_gaps=dict(_attribute(gaps, spans)))


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime_ns: int) -> TickSummary:
    return reduce(path)


def trace_file(run) -> pathlib.Path | None:
    """The ``.xplane.pb`` a traced run left where its job records it
    (``<.bench_cache>/trace/<cell>``), or None."""
    if run.trace is None:
        return None
    try:
        return trace.Recorder(SCRATCH / "trace" / run.cell.name).file()
    except FileNotFoundError:
        return None


def of_run(run) -> TickSummary | None:
    """The run's ticks, or None where it holds no ``serve.tick`` span."""
    path = trace_file(run)
    if path is None:
        return None
    summary = _reduced(str(path), path.stat().st_mtime_ns)
    return summary if summary.ticks else None
