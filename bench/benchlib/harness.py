"""What every job shares: the run's context, the device and its memory,
compile counting, and the result a job hands back."""

from __future__ import annotations

import dataclasses
import pathlib
import sys
import time
from typing import Any, Callable, Optional

from benchlib.registry import ROOT, Cell

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# The persistent compilation cache lives inside the checkout at a fixed
# path (the path is part of the cache key), unless the environment names
# one: ``repro.launch.compile_cache`` decides, the benchmark follows.
SCRATCH = ROOT / ".bench_cache"


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


class CompileCounter:
    """Counts the programs JAX compiles or loads from the persistent cache
    (each is one backend-compile event), and how many missed the cache."""

    def __init__(self):
        self.compiles = 0
        self.misses = 0
        self.hits = 0
        self.seconds = 0.0
        self.slowest = []                  # (seconds, function name)
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += secs
            self.slowest = sorted(self.slowest + [(secs, fun_name)])[-8:]

    def _event(self, event, **_kw):
        if event == CACHE_MISS:
            self.misses += 1
        elif event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple[int, int, int, float]:
        return self.compiles, self.misses, self.hits, self.seconds


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                  # perf_counter at process start
    compiles: CompileCounter
    log: Callable[[str], None] = log
    # Tests drive a run with the timed path broken underneath; jobs call
    # this on the objects they are about to time (None in real runs).
    tamper: Optional[Callable[[str, Any], Any]] = None

    def setup_done(self) -> float:
        return time.perf_counter() - self.t_process

    def hook(self, what: str, obj):
        return obj if self.tamper is None else self.tamper(what, obj)


@dataclasses.dataclass
class Check:
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class JobOutput:
    attempted: int
    failed: int
    end_to_end: dict              # name -> value, every one the job can give
    checks: dict                  # name -> Check
    memory_peak_bytes: int
    records: dict = dataclasses.field(default_factory=dict)
    trace: Any = None             # benchlib.trace.TraceSummary when traced

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks.values())


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(planned: int = 0) -> int:
    """Peak bytes in use on the fullest local device, where reported.
    ``planned``, what the compiler's analysis gives for the timed program,
    is logged beside it: on the chip it can read more than the chip holds
    for a step that runs, so it is not the peak."""
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    peak = max(peaks) if peaks else 0
    if planned:
        log(f"memory: peak in use {peak} bytes (memory_stats), planned for "
            f"the timed step {planned} bytes (memory_analysis)")
    return peak


def enable_compile_cache():
    """The program's compile-cache placement, and every program cached
    (the default skips those that compile in under a second, which would
    then compile again in every run's set-up)."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    path = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def scratch_dir(name: str) -> pathlib.Path:
    p = SCRATCH / name
    p.mkdir(parents=True, exist_ok=True)
    return p


