"""Benchmark utilities: timing, CSV emission, shared GSPN inputs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import gspn as G

ROWS = []

# Per-row distribution stats, parallel to ROWS (schema-2 payloads,
# DESIGN.md §13): ``time_fn`` records its iteration spread here via
# LAST_STATS; ``emit`` consumes-and-clears it into ROW_STATS so each CSV
# row carries the p10/p50/p90 of the timing run that produced it (None
# for derived rows emitted without a fresh time_fn call).
ROW_STATS = []
LAST_STATS = None

# Set by ``benchmarks.run --smoke``: every rung runs exactly one timed
# iteration so a full bench sweep can gate a PR in seconds.  Timings are
# then indicative only — the CSV still exercises every code path.
SMOKE = False


def emit(name: str, us_per_call: float, derived: str = ""):
    global LAST_STATS
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    ROW_STATS.append(LAST_STATS)
    LAST_STATS = None
    print(line, flush=True)


def _percentile(sorted_times, q: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    i = min(len(sorted_times) - 1, int(round(q * (len(sorted_times) - 1))))
    return sorted_times[i]


def time_fn(fn, *args, iters: int = 3, warmup: int = 1,
            min_iters: int = 1) -> float:
    """Median wall time (seconds) of fn(*args) with block_until_ready.

    ``min_iters`` floors the iteration count under --smoke: rungs whose
    RELATIVE timing is gated (the dtype-ordering check, DESIGN.md §12)
    ask for a few iterations even in smoke mode so a single scheduler
    hiccup cannot flip the comparison.

    Side effect: records the iteration spread (p10/p50/p90 µs) into
    ``LAST_STATS`` for the next ``emit`` to attach to its row (schema-2
    --json payloads)."""
    global LAST_STATS
    [(median, LAST_STATS)] = time_interleaved(
        [(fn, args)], iters=iters, warmup=warmup, min_iters=min_iters)
    return median


def time_interleaved(calls, *, iters: int = 3, warmup: int = 1,
                     min_iters: int = 1) -> list:
    """``time_fn`` over several ``(fn, args)`` calls at once, one call of
    each per round, so a burst of host load lands on every call alike —
    what a comparison between them needs.  Returns ``(median seconds,
    stats)`` per call, ``stats`` in ``LAST_STATS``'s form."""
    if SMOKE:
        iters, warmup = max(1, min_iters), min(warmup, 1)
    for fn, args in calls:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
    times = [[] for _ in calls]
    for _ in range(iters):
        for (fn, args), ts in zip(calls, times):
            t0 = obs.monotonic()
            jax.block_until_ready(fn(*args))
            ts.append(obs.monotonic() - t0)
    out = []
    for ts in times:
        ts.sort()
        out.append((ts[len(ts) // 2],
                    {"iters": len(ts),
                     "p10_us": round(_percentile(ts, 0.1) * 1e6, 3),
                     "p50_us": round(_percentile(ts, 0.5) * 1e6, 3),
                     "p90_us": round(_percentile(ts, 0.9) * 1e6, 3)}))
    return out


def make_gspn_inputs(batch: int, channels: int, h: int, w: int,
                     channel_shared: bool = True, seed: int = 0,
                     dtype=jnp.float32):
    """Inputs for the canonical scan: x/lam (B*C, H, W); taps (Gw, H, W)."""
    g = batch * channels
    gw = batch if channel_shared else g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (g, h, w), dtype)
    lam = jax.nn.sigmoid(jax.random.normal(ks[1], (g, h, w))).astype(dtype)
    wl, wc, wr = G.normalize_taps(
        jax.random.normal(ks[2], (gw, h, w, 3)))
    return x, wl.astype(dtype), wc.astype(dtype), wr.astype(dtype), lam


def scan_bytes(batch, channels, h, w, channel_shared=True, dtype_bytes=4):
    """Analytic HBM traffic of one fused directional scan: read x, λ, taps,
    write h (the carry stays on-chip — the GSPN-2 design point)."""
    g = batch * channels
    gw = batch if channel_shared else g
    per_plane = h * w * dtype_bytes
    return (2 * g + 3 * gw + g) * per_plane     # x, lam reads + 3 taps + h

def scan_flops(batch, channels, h, w):
    """4 FMAs per element per directional pass."""
    return batch * channels * h * w * 8
