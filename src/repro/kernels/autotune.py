"""Empirical kernel autotuner with a persistent cache (DESIGN.md §11).

``kernels/tuning.py`` picks row tiles from a static VMEM model — correct
admission, but blind to what the device actually prefers (the paper's §4.3
occupancy balance is an *empirical* optimum: one warp per channel slice
only wins when the tile shape matches the hardware).  This module closes
the loop the way Triton-style kernels do: enumerate the admissible
configs, **time them** under jit with proper warmup, and persist the
winner to a JSON cache keyed by everything that changes the optimum —

    (device_kind, H, W, C, direction, impl, stream dtype, carry dtype,
     channel_shared)

Resolution order at every launch site (``plan_for_spec``):

1. an explicit ``row_tile=`` argument always wins (never consults us);
2. a cache hit — env-overridable path (``GSPN_TUNE_CACHE``) layered over
   the checked-in seed cache (``tune_cache_seed.json``, recorded in CPU
   interpret mode so CI exercises the hit path) — validated against the
   shape (must divide H, fit the VMEM budget) before use;
3. graceful fallback: the static heuristic ``tuning.pick_row_tile`` with
   the same stream/carry byte accounting (unknown device, cache miss, or
   a stale/invalid entry all land here, silently).

The candidate enumerator is the single source of truth for what the tuner
may emit; the oracle-conformance grid (``tests/test_conformance.py``)
draws from the same enumerator, so any cache entry is by construction a
config the conformance suite has proven safe.

CLI (also the CI cache-artifact producer)::

    PYTHONPATH=src python -m repro.kernels.autotune warm --out tune.json
    PYTHONPATH=src python -m repro.kernels.autotune show
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import sys

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import spec as spec_mod
from repro.kernels import tuning
from repro.kernels.spec import BOUNDARIES, ScanSpec

ENV_CACHE_PATH = "GSPN_TUNE_CACHE"
SEED_CACHE_PATH = pathlib.Path(__file__).with_name("tune_cache_seed.json")
# Schema 2 (PR 6): entries gained a "pipeline_depth" field (1 = the
# revolving-buffer BlockSpec stream, 2 = the explicitly staged pipeline —
# DESIGN.md §12).  Schema-1 files load unchanged: a missing field reads
# as depth 1, reproducing the pre-PR6 kernels exactly.
# Schema 3 (PR 8): keys are the ScanSpec canonical serialization — the
# legacy key plus a trailing "|bnd-{boundary}" leg (DESIGN.md §14).
# Schema-2 files load unchanged: lookup falls back to the legacy
# encoding, so a boundary-less entry serves every boundary mode.
SCHEMA_VERSION = 3

# Heuristic-fallback tile cap — matches gspn_scan.DEFAULT_ROW_TILE so a
# cache miss reproduces the pre-tuner behaviour bit-for-bit.  Measured
# candidates may explore beyond it (ENUM_CAP).
DEFAULT_CAP = 256
ENUM_CAP = 512

# Per-direction kernel geometry: streamed operand count and VMEM carry
# rows (the adjoint kernels hold three tap·adjoint rows, always f32 —
# see gspn_scan._bwd_step).
DIRECTIONS = ("fwd", "bwd", "pair_fwd", "pair_bwd", "quad")
_N_STREAMS = {"fwd": 6, "bwd": 5, "pair_fwd": 6, "pair_bwd": 5, "quad": 6}
_CARRY_ROWS = {"fwd": 1, "bwd": 3, "pair_fwd": 1, "pair_bwd": 3, "quad": 1}

# Pipeline depths the kernels implement (DESIGN.md §12).  The kernels
# accept either depth at any dtype (the conformance grid proves them
# bit-identical); which depth a key may run is admission policy
# (``depth_admissible``).
PIPELINE_DEPTHS = (1, 2)

# Injectable timer — tests monkeypatch this (or pass ``timer=``) to make
# the measurement harness deterministic.  The default is the repo-wide
# monotonic span clock (DESIGN.md §13) — never wall clock.
_default_timer = obs.monotonic

# Every (key -> plan) resolution this process has made, bounded.  The
# serve engine annotates its decode-step spans with this (DESIGN.md §13)
# so a trace shows exactly which kernel configuration ran.
_RESOLVED_CAP = 256
_RESOLVED: dict[str, tuple[int, int, str]] = {}


def _record_plan(key: "ScanKey", plan: "ScanPlan", source: str):
    if key.encode() not in _RESOLVED and len(_RESOLVED) >= _RESOLVED_CAP:
        return
    prev = _RESOLVED.get(key.encode())
    _RESOLVED[key.encode()] = (plan.row_tile, plan.pipeline_depth, source)
    if prev is None:
        obs.counter(f"autotune_plans_depth{plan.pipeline_depth}_total",
                    "launch keys resolved to this pipeline depth").inc()
        obs.event("kernel.plan", key=key.encode(), row_tile=plan.row_tile,
                  pipeline_depth=plan.pipeline_depth, source=source)


def resolved_plans() -> dict:
    """``key.encode() -> (row_tile, pipeline_depth, source)`` for every
    launch-site resolution so far."""
    return dict(_RESOLVED)


def plans_summary() -> str:
    """Compact one-line view: ``dir@hHxwW/dtype:tT-dD`` per resolved key
    (the decode-step span annotation)."""
    parts = []
    for enc, (t, d, _src) in sorted(_RESOLVED.items()):
        seg = enc.split("|")
        label = "|".join(seg[1:5]) if len(seg) >= 5 else enc
        parts.append(f"{label}:t{t}-d{d}")
    return " ".join(parts)


@functools.lru_cache(maxsize=4)
def device_kind(interpret: bool | None = None) -> str:
    """Normalised device cache key ('TPU v5 lite' → 'tpu-v5-lite').
    Interpret-mode runs (the CPU validation path) get their own namespace
    so interpreter timings can never leak onto real silicon, and vice
    versa.  ``None`` is a launch whose interpret mode follows the platform
    (``gspn_scan.pallas_call``): interpreted everywhere but on a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kind = jax.devices()[0].device_kind.lower().replace(" ", "-")
    return f"{kind}+interpret" if interpret else kind


@dataclasses.dataclass(frozen=True)
class ScanKey:
    """Everything that changes the empirical optimum of one scan launch."""
    device: str
    h: int                       # scan length (rows per carry segment)
    w: int                       # lane width
    c: int                       # G — flattened (batch, channel) planes
    direction: str               # fwd | bwd | pair_fwd | pair_bwd | quad
    impl: str                    # pallas | multidir
    dtype: str                   # streamed dtype (operand tiles)
    carry_dtype: str             # VMEM carry dtype (f32 under the policy)
    channel_shared: bool         # compact channel propagation active
    boundary: str = "one_shot"   # one_shot | chunk_resume | sp_block_local

    def __post_init__(self):
        if self.direction not in DIRECTIONS:
            raise ValueError(f"unknown direction {self.direction!r}; "
                             f"expected one of {DIRECTIONS}")
        if self.boundary not in BOUNDARIES:
            raise ValueError(f"unknown boundary {self.boundary!r}; "
                             f"expected one of {BOUNDARIES}")

    def encode(self) -> str:
        """Schema-3 key: device + shape legs, then the ScanSpec canonical
        serialization verbatim (spec.canonical_key) — appending the
        boundary leg at the END keeps ``plans_summary``'s segment parsing
        and every schema-2 prefix intact."""
        return f"{self.device}|h{self.h}|w{self.w}|c{self.c}|" + \
            spec_mod.canonical_key(self.direction, self.impl, self.dtype,
                                   self.carry_dtype, self.channel_shared,
                                   self.boundary)

    def encode_legacy(self) -> str:
        """The schema-2 key (no boundary leg) — the read-compat fallback
        for caches written before schema 3."""
        return (f"{self.device}|h{self.h}|w{self.w}|c{self.c}"
                f"|{self.direction}|{self.impl}|{self.dtype}"
                f"|carry-{self.carry_dtype}|cs{int(self.channel_shared)}")

    @property
    def stream_bytes(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def carry_bytes(self) -> int:
        """VMEM-resident carry bytes per lane: carry rows × itemsize."""
        return _CARRY_ROWS[self.direction] * jnp.dtype(self.carry_dtype).itemsize

    @property
    def n_streams(self) -> int:
        return _N_STREAMS[self.direction]


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One tunable layout.  ``row_tile`` is the tile knob that reaches the
    kernel (rows per sequential grid step — the grid split is ``h //
    row_tile``); ``double_buffer`` is the accounting layout: True counts
    the prefetch copy Pallas keeps of every block (the footprint the
    compiler allocates, and the only one the enumerator admits); False
    is the resident-only footprint, kept for reporting.
    ``pipeline_depth`` selects the kernel structure itself: 1 = one plane
    per grid step, row by row; 2 = all planes per grid step, staged in
    f32 ``(T, G, W)`` layout (DESIGN.md §12)."""
    row_tile: int
    double_buffer: bool = True
    pipeline_depth: int = 1

    def working_set(self, key: ScanKey) -> int:
        return tuning.scan_working_set(
            self.row_tile, key.w, key.stream_bytes, key.n_streams,
            double_buffer=self.double_buffer,
            carry_dtype_bytes=key.carry_bytes,
            pipeline_depth=self.pipeline_depth, planes=max(key.c, 1))


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """What a launch site needs from the tuner: the tile AND the pipeline
    structure (``row_tile_for`` survives as the tile-only view)."""
    row_tile: int
    pipeline_depth: int = 1


def _staged_fits(key: ScanKey, row_tile: int | None = None) -> bool:
    """Whether the depth-2 working set (all G planes resident) of
    ``row_tile``, else of the smallest admissible tile, fits the VMEM
    limit."""
    t = row_tile or tuning.admissible_tiles(key.h, key.stream_bytes,
                                            DEFAULT_CAP)[0]
    return Candidate(t, pipeline_depth=2).working_set(key) \
        <= tuning.VMEM_BYTES


def depth_admissible(key: ScanKey, pipeline_depth: int) -> bool:
    """Admission policy for the staged pipeline (DESIGN.md §12).  Depth 1
    always.  Depth 2 for narrow (< 4-byte) streams, whose widen-on-load
    it amortises over a tile; and for 4-byte streams on a compiled device
    (not ``+interpret``: the interpreter has no sublanes to fill) when
    the G planes fill a vreg's sublanes (G >= 8) and a depth-2 tile fits
    VMEM — there one row step advances every plane at once instead of
    one plane's row, so the row recurrence's latency is paid H times per
    launch rather than G·H times."""
    if pipeline_depth == 1:
        return True
    if pipeline_depth != 2:
        return False
    if key.stream_bytes < 4:
        return True
    return (not key.device.endswith("+interpret")
            and key.c >= tuning.sublane_rows(key.stream_bytes)
            and _staged_fits(key))


def heuristic_pipeline_depth(key: ScanKey, *,
                             row_tile: int | None = None) -> int:
    """Static-fallback depth: the staged pipeline wherever it is
    admissible (``depth_admissible``) and its tile (``row_tile``, else the
    smallest admissible one) fits the VMEM budget with all G planes
    resident; otherwise the classic one-plane-per-step stream, which fits
    at any G."""
    return 2 if depth_admissible(key, 2) and _staged_fits(key, row_tile) \
        else 1


def enumerate_candidates(key: ScanKey, *,
                         vmem_budget: int = tuning.VMEM_BYTES,
                         cap: int = ENUM_CAP) -> list[Candidate]:
    """All configs the tuner may time (and therefore emit) for ``key``:
    the admissible row tiles of the scan length (``tuning.
    tile_admissible``: power-of-two multiples of the dtype's sublane tile,
    or the whole length) whose double-buffered working set fits the VMEM
    budget — Pallas always double-buffers its blocks, so a tile that fits
    only single-buffered is refused by the compiler — at every admissible
    pipeline depth (``depth_admissible``)."""
    out: list[Candidate] = []
    for t in tuning.admissible_tiles(key.h, key.stream_bytes, cap):
        for depth in PIPELINE_DEPTHS:
            if not depth_admissible(key, depth):
                continue
            cand = Candidate(row_tile=t, pipeline_depth=depth)
            if cand.working_set(key) <= vmem_budget:
                out.append(cand)
    return out


def heuristic_row_tile(key: ScanKey, *, cap: int = DEFAULT_CAP,
                       vmem_budget: int = tuning.VMEM_BYTES,
                       pipeline_depth: int | None = None) -> int:
    """The static-VMEM-model fallback — identical accounting to the
    pre-tuner call sites (cache miss ⇒ unchanged behaviour).  The depth
    defaults to the heuristic depth for the key's stream dtype so the
    fallback tile is admissible for the kernel structure it will run."""
    depth = (heuristic_pipeline_depth(key) if pipeline_depth is None
             else pipeline_depth)
    return tuning.pick_row_tile(
        key.h, key.w, key.stream_bytes, vmem_budget=vmem_budget, cap=cap,
        n_streams=key.n_streams, carry_dtype_bytes=key.carry_bytes,
        pipeline_depth=depth, planes=max(key.c, 1)).row_tile


# ---------------------------------------------------------------------------
# Persistent cache.
# ---------------------------------------------------------------------------

class TuningCache:
    """JSON-backed ``key.encode() -> entry`` map.

    Entries are plain dicts: ``{"row_tile", "double_buffer", "us",
    "n_grid_steps", "working_set_bytes", "source"}``.  Corrupt or
    missing files load as empty caches (the tuner must never take the
    serving path down)."""

    def __init__(self, entries: dict | None = None,
                 path: str | os.PathLike | None = None):
        self.entries: dict[str, dict] = dict(entries or {})
        self.path = pathlib.Path(path) if path else None

    @classmethod
    def load(cls, path) -> "TuningCache":
        path = pathlib.Path(path)
        try:
            payload = json.loads(path.read_text())
            entries = payload.get("entries", {})
            if not isinstance(entries, dict):
                raise ValueError("entries is not a mapping")
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            print(f"[autotune] ignoring unreadable cache {path}: {exc!r}",
                  file=sys.stderr)
            entries = {}
        return cls(entries, path=path)

    def save(self, path=None) -> pathlib.Path:
        path = pathlib.Path(path) if path else self.path
        if path is None:
            raise ValueError("no cache path to save to")
        payload = {"schema": SCHEMA_VERSION, "entries": self.entries}
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
        self.path = path
        return path

    def lookup(self, key: ScanKey) -> dict | None:
        """Schema-3 key first, then the schema-2 legacy encoding: a
        boundary-less pre-schema-3 entry serves every boundary mode (the
        tile optimum does not depend on how the segment resumes)."""
        hit = self.entries.get(key.encode())
        if hit is not None:
            return hit
        return self.entries.get(key.encode_legacy())

    def store(self, key: ScanKey, entry: dict):
        self.entries[key.encode()] = dict(entry)

    def merge(self, other: "TuningCache"):
        self.entries.update(other.entries)

    def __len__(self):
        return len(self.entries)


_CACHE: TuningCache | None = None


def get_cache(reload: bool = False) -> TuningCache:
    """Process-global cache: checked-in seed, overlaid (entries win) by
    the ``GSPN_TUNE_CACHE`` path when set."""
    global _CACHE
    if _CACHE is None or reload:
        cache = (TuningCache.load(SEED_CACHE_PATH)
                 if SEED_CACHE_PATH.exists() else TuningCache())
        env = os.environ.get(ENV_CACHE_PATH)
        if env:
            cache.merge(TuningCache.load(env))
            cache.path = pathlib.Path(env)
        _CACHE = cache
    return _CACHE


def load_cache(path) -> int:
    """Layer an explicit cache file over the global cache (the launchers'
    ``--tune-cache`` flag).  Returns the number of entries loaded."""
    extra = TuningCache.load(path)
    cache = get_cache()
    cache.merge(extra)
    cache.path = extra.path
    return len(extra)


def _entry_depth(entry: dict) -> int:
    """Pipeline depth recorded in a cache entry; schema-1 entries (no
    field) read as depth 1 — the pre-PR6 kernel structure."""
    try:
        return int(entry.get("pipeline_depth", 1))
    except (TypeError, ValueError):
        return -1


def _entry_invalid_reason(key: ScanKey, entry: dict, *,
                          vmem_budget: int = tuning.VMEM_BYTES) -> str | None:
    """Why a cache entry cannot be honoured for this key, or ``None`` when
    it is valid: the row tile must be admissible for H (a power-of-two
    multiple of the dtype's sublane tile dividing H, or H itself —
    ``tuning.tile_admissible``), the pipeline depth known, and the
    double-buffered working set at that depth (what the compiler
    allocates) must fit the budget.  ``plan_for`` turns a non-None reason
    into an obs counter + event so a corrupted or stale cache is visible
    instead of silently degrading to the heuristic."""
    try:
        t = int(entry["row_tile"])
    except (KeyError, TypeError, ValueError):
        return f"row_tile missing or non-integer: {entry.get('row_tile')!r}"
    if not tuning.tile_admissible(t, key.h, key.stream_bytes):
        return (f"row_tile {t} is not admissible for h={key.h}: it must "
                f"divide h and be h or a power of two multiple of "
                f"{tuning.sublane_rows(key.stream_bytes)} rows")
    depth = _entry_depth(entry)
    if depth not in PIPELINE_DEPTHS:
        return (f"pipeline_depth {entry.get('pipeline_depth')!r} not in "
                f"{PIPELINE_DEPTHS}")
    ws = Candidate(t, pipeline_depth=depth).working_set(key)
    if ws > vmem_budget:
        return f"working set {ws}B exceeds VMEM budget {vmem_budget}B"
    return None


def _entry_valid(key: ScanKey, entry: dict, *,
                 vmem_budget: int = tuning.VMEM_BYTES) -> bool:
    """Boolean view of :func:`_entry_invalid_reason`."""
    return _entry_invalid_reason(key, entry, vmem_budget=vmem_budget) is None


def plan_for_spec(spec: ScanSpec, h: int, w: int, *, c: int = 0,
                  cache: TuningCache | None = None,
                  cap: int = DEFAULT_CAP) -> ScanPlan:
    """THE launch-site planning entry point: tuned ``(row_tile,
    pipeline_depth)`` if the cache knows this (device, shape, spec-policy)
    key, heuristic otherwise.  The cache key is the spec's canonical
    serialization (``ScanKey.encode`` ends with ``spec.canonical()``)
    plus the device and shape legs.  The spec's explicit ``row_tile`` /
    ``pipeline_depth`` fields always win; an explicit tile bypasses the
    cache entirely (a measured entry's depth belongs to the tile it was
    measured with) and takes the heuristic depth unless one is given.

    Every fused-scan launch (fwd, bwd, pair, quad — and through them the
    chunked-prefill and sp block-local paths) funnels here, so one cache
    governs the whole stack.  The kwargs-style :func:`plan_for` survives
    only as a deprecation shim over this function."""
    key = ScanKey(device_kind(spec.interpret), h, w, c, spec.direction,
                  spec.impl, str(jnp.dtype(spec.stream_dtype)),
                  str(jnp.dtype(spec.carry_dtype)),
                  spec.channel_shared, spec.boundary)
    if spec.row_tile is not None:
        depth = (heuristic_pipeline_depth(key, row_tile=spec.row_tile)
                 if spec.pipeline_depth is None else spec.pipeline_depth)
        plan = ScanPlan(spec.row_tile, depth)
        _record_plan(key, plan, "explicit")
        return plan
    cache = cache if cache is not None else get_cache()
    entry = cache.lookup(key)
    if entry is not None:
        reason = _entry_invalid_reason(key, entry)
        if reason is None:
            t, depth = int(entry["row_tile"]), _entry_depth(entry)
            source = "cache"
        else:
            # A present-but-unusable entry is a signal (corrupt file,
            # stale shape, hand-edited cache) — count it and log why
            # before degrading to the heuristic.
            obs.counter("autotune_cache_rejects_total").inc()
            obs.event("autotune.cache_reject", key=key.encode(),
                      reason=reason)
            entry = None
    if entry is None:
        depth = heuristic_pipeline_depth(key)
        t = heuristic_row_tile(key, cap=cap, pipeline_depth=depth)
        source = "heuristic"
    if spec.pipeline_depth is not None:
        depth = spec.pipeline_depth
    plan = ScanPlan(t, depth)
    _record_plan(key, plan, source)
    return plan


# Warn-once latch for the deprecated kwargs-style entry points.  Module
# state (not functools caching) so a test can reset it explicitly.
_plan_for_warned = False


def _spec_from_kwargs(direction, impl, dtype, carry_dtype, channel_shared,
                      interpret, row_tile, pipeline_depth,
                      boundary) -> ScanSpec:
    """Fold the legacy loose-kwargs planning surface into a ScanSpec.
    ``channel_shared`` is a bool in the old surface; the spec carries the
    actual channel count, but only the >1 bit reaches the cache key, so
    any shared count reproduces the legacy key exactly."""
    return ScanSpec(direction=direction, impl=impl,
                    channels_per_weight=2 if channel_shared else 1,
                    stream_dtype=str(jnp.dtype(dtype)),
                    carry_dtype=str(jnp.dtype(carry_dtype)),
                    row_tile=row_tile, pipeline_depth=pipeline_depth,
                    boundary=boundary, interpret=interpret)


def plan_for(h: int, w: int, *, c: int = 0, direction: str = "fwd",
             impl: str = "pallas", dtype="float32",
             carry_dtype="float32", channel_shared: bool = False,
             interpret: bool = False, cache: TuningCache | None = None,
             cap: int = DEFAULT_CAP, row_tile: int | None = None,
             pipeline_depth: int | None = None,
             boundary: str = "one_shot") -> ScanPlan:
    """DEPRECATED kwargs-style shim over :func:`plan_for_spec` — builds
    the equivalent ScanSpec and forwards.  Kept so pre-spec callers keep
    resolving identical plans (pinned by tests/test_autotune.py); new
    code should construct a :class:`ScanSpec` and call
    :func:`plan_for_spec`.  Warns once per process."""
    global _plan_for_warned
    if not _plan_for_warned:
        _plan_for_warned = True
        import warnings
        warnings.warn(
            "autotune.plan_for is deprecated; construct a ScanSpec and "
            "call plan_for_spec", DeprecationWarning, stacklevel=2)
    spec = _spec_from_kwargs(direction, impl, dtype, carry_dtype,
                             channel_shared, interpret, row_tile,
                             pipeline_depth, boundary)
    return plan_for_spec(spec, h, w, c=c, cache=cache, cap=cap)


def row_tile_for(h: int, w: int, *, c: int = 0, direction: str = "fwd",
                 impl: str = "pallas", dtype="float32",
                 carry_dtype="float32", channel_shared: bool = False,
                 interpret: bool = False, cache: TuningCache | None = None,
                 cap: int = DEFAULT_CAP) -> int:
    """Tile-only view of :func:`plan_for_spec` (kept for callers that
    manage the pipeline structure themselves)."""
    spec = _spec_from_kwargs(direction, impl, dtype, carry_dtype,
                             channel_shared, interpret, None, None,
                             "one_shot")
    return plan_for_spec(spec, h, w, c=c, cache=cache, cap=cap).row_tile


# ---------------------------------------------------------------------------
# Measurement harness.
# ---------------------------------------------------------------------------

def measure(fn, *, iters: int = 3, warmup: int = 1, timer=None) -> float:
    """Median wall seconds of ``fn()`` with ``block_until_ready``.
    ``timer`` is injectable (defaults to the module's ``_default_timer``)
    so tests can drive the harness deterministically."""
    timer = timer or _default_timer
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = timer()
        jax.block_until_ready(fn())
        times.append(timer() - t0)
    times.sort()
    return times[len(times) // 2]


def _make_operands(key: ScanKey, seed: int = 0):
    """Synthetic operands matching the key's layout.  Taps are softmaxed
    per position (row-stochastic-ish) so timings run on realistic
    magnitudes; the tuner never checks numerics — the conformance grid
    owns that."""
    dtype = jnp.dtype(key.dtype)
    g = max(key.c, 1)
    gw = 1 if key.channel_shared else g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (g, key.h, key.w), jnp.float32)
    lam = jax.nn.sigmoid(
        jax.random.normal(ks[1], (g, key.h, key.w), jnp.float32))
    taps = jax.nn.softmax(
        jax.random.normal(ks[2], (gw, key.h, key.w, 3), jnp.float32), axis=-1)
    wl, wc, wr = taps[..., 0], taps[..., 1], taps[..., 2]
    cast = lambda a: a.astype(dtype)
    return tuple(map(cast, (x, wl, wc, wr, lam))), g // gw


def default_runner_factory(key: ScanKey, *, interpret: bool | None = None,
                           seed: int = 0):
    """Builds, per candidate, a zero-arg jitted launch of the ACTUAL
    kernel the key describes (lazy kernel imports — this module is
    imported by the kernels themselves)."""
    from repro.kernels import gspn_multidir as _mk
    from repro.kernels import gspn_scan as _pk

    (x, wl, wc, wr, lam), cpw = _make_operands(key, seed)

    def factory(cand: Candidate):
        # The candidate's knobs travel as ONE ScanSpec — the same object
        # a production launch site would hand down (DESIGN.md §14).
        sp = ScanSpec(direction=key.direction, impl=key.impl,
                      channels_per_weight=max(cpw, 1),
                      stream_dtype=key.dtype, carry_dtype=key.carry_dtype,
                      row_tile=cand.row_tile,
                      pipeline_depth=cand.pipeline_depth,
                      boundary=key.boundary, interpret=interpret)
        if key.direction == "fwd":
            run = jax.jit(lambda *a: _pk.gspn_scan_fwd_pallas(*a, spec=sp))
            args = (x, wl, wc, wr, lam)
        elif key.direction == "bwd":
            run = jax.jit(lambda *a: _pk.gspn_scan_bwd_pallas(*a, spec=sp))
            args = (x, wl, wc, wr)          # x stands in for dy
        elif key.direction == "pair_fwd":
            pair = lambda a: jnp.stack([a, a])
            run = jax.jit(lambda xx, l2, w2, c2, r2: _mk.gspn_scan_bidir_pallas(
                xx, {"wl": w2, "wc": c2, "wr": r2}, l2, spec=sp))
            args = (x, pair(lam), pair(wl), pair(wc), pair(wr))
        elif key.direction == "pair_bwd":
            pair = lambda a: jnp.stack([a, a])
            run = jax.jit(lambda d2, w2, c2, r2: _mk.gspn_scan_bidir_bwd_pallas(
                d2, w2, c2, r2, spec=sp))
            args = (pair(x), pair(wl), pair(wc), pair(wr))
        elif key.direction == "quad":
            quad = lambda a: jnp.stack([a] * 4)
            run = jax.jit(lambda xx, l4, w4, c4, r4: _mk.gspn_scan_quad_pallas(
                xx, {"wl": w4, "wc": c4, "wr": r4}, l4, spec=sp))
            args = (x, quad(lam), quad(wl), quad(wc), quad(wr))
        else:  # pragma: no cover — ScanKey.__post_init__ guards this
            raise ValueError(key.direction)
        return lambda: run(*args)

    return factory


def autotune_key(key: ScanKey, *, candidates=None, iters: int = 3,
                 warmup: int = 1, cache: TuningCache | None = None,
                 timer=None, runner_factory=None,
                 interpret: bool | None = None) -> dict:
    """Time every candidate for ``key`` and cache the winner.

    The candidate list always contains the heuristic's choice (the
    enumerator admits every tile the heuristic may pick), so the measured
    winner is never slower than the heuristic beyond timing noise.
    Returns the stored entry; ties break toward the first (smallest,
    double-buffered) candidate, making the harness deterministic under a
    fixed candidate list and timer.
    """
    cands = list(candidates if candidates is not None
                 else enumerate_candidates(key))
    cache = cache if cache is not None else get_cache()
    if not cands:
        entry = {"row_tile": heuristic_row_tile(key), "double_buffer": True,
                 "pipeline_depth": heuristic_pipeline_depth(key),
                 "us": None, "n_grid_steps": None, "working_set_bytes": None,
                 "source": "heuristic"}
        cache.store(key, entry)
        return entry
    if runner_factory is None:
        runner_factory = default_runner_factory(key, interpret=interpret)

    timed: list[tuple[float, Candidate]] = []
    with obs.trace("autotune.key", key=key.encode(),
                   n_candidates=len(cands)):
        for cand in cands:
            fn = runner_factory(cand)
            with obs.trace("autotune.measure", row_tile=cand.row_tile,
                           pipeline_depth=cand.pipeline_depth):
                us = measure(fn, iters=iters, warmup=warmup,
                             timer=timer) * 1e6
            obs.event("autotune.candidate", key=key.encode(),
                      row_tile=cand.row_tile,
                      pipeline_depth=cand.pipeline_depth, us=round(us, 3))
            timed.append((us, cand))
    obs.counter("autotune_keys_measured_total").inc()
    obs.counter("autotune_candidates_timed_total").inc(len(timed))
    best_us, best = min(timed, key=lambda r: r[0])
    entry = {
        "row_tile": best.row_tile,
        "double_buffer": best.double_buffer,
        "pipeline_depth": best.pipeline_depth,
        "us": round(best_us, 3),
        "n_grid_steps": key.h // best.row_tile,
        "working_set_bytes": best.working_set(key),
        "source": "measured",
    }
    cache.store(key, entry)
    return entry


# ---------------------------------------------------------------------------
# Warm list + CLI (the CI tuning-cache artifact producer).
# ---------------------------------------------------------------------------

# (h, w, c, direction, impl, dtype, channel_shared) — the smoke-ladder and
# test shapes; carry follows the §10 policy (f32; adjoints are always f32).
WARM_SPECS = [
    (64, 64, 8, "fwd", "pallas", "float32", True),
    (64, 64, 8, "fwd", "pallas", "bfloat16", True),
    (64, 64, 8, "bwd", "pallas", "float32", True),
    (64, 64, 8, "pair_fwd", "multidir", "float32", True),
    (128, 128, 8, "fwd", "pallas", "float32", True),
    (128, 128, 8, "fwd", "pallas", "bfloat16", True),
    (128, 128, 8, "bwd", "pallas", "float32", True),
    (128, 128, 8, "bwd", "pallas", "bfloat16", True),
    (128, 128, 8, "pair_fwd", "multidir", "float32", True),
    (128, 128, 8, "pair_fwd", "multidir", "bfloat16", True),
    (128, 128, 8, "pair_bwd", "multidir", "float32", True),
    (192, 192, 8, "fwd", "pallas", "float32", True),
]


def warm(specs=None, *, cache: TuningCache | None = None, iters: int = 2,
         warmup: int = 1, interpret: bool | None = None,
         verbose: bool = True):
    """Tune every spec on the current device and return the cache."""
    cache = cache if cache is not None else get_cache()
    for h, w, c, direction, impl, dtype, cs in (specs or WARM_SPECS):
        key = ScanKey(device_kind(interpret), h, w, c, direction, impl,
                      str(jnp.dtype(dtype)), "float32", cs)
        entry = autotune_key(key, iters=iters, warmup=warmup, cache=cache,
                             interpret=interpret)
        if verbose:
            print(f"[autotune] {key.encode()} -> row_tile="
                  f"{entry['row_tile']} depth={entry['pipeline_depth']} "
                  f"({entry['us']}us)", file=sys.stderr)
    return cache


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="repro.kernels.autotune")
    sub = ap.add_subparsers(dest="cmd", required=True)
    ap_warm = sub.add_parser("warm", help="measure the built-in warm list")
    ap_warm.add_argument("--out", default="",
                         help="write the cache here (default: seed path)")
    ap_warm.add_argument("--iters", type=int, default=2)
    ap_warm.add_argument("--warmup", type=int, default=1,
                         help="discarded runs per candidate before timing "
                              "(2+ recommended when re-measuring the seed)")
    sub.add_parser("show", help="print the resolved cache")
    args = ap.parse_args(argv)

    if args.cmd == "warm":
        # Measure into a FRESH cache: the artifact must contain only this
        # device's fresh measurements, never the layered seed/env entries.
        cache = warm(cache=TuningCache(), iters=args.iters,
                     warmup=args.warmup)
        path = cache.save(args.out or SEED_CACHE_PATH)
        print(f"[autotune] wrote {len(cache)} entries to {path}")
        return 0
    if args.cmd == "show":
        cache = get_cache(reload=True)
        print(json.dumps({"schema": SCHEMA_VERSION,
                          "entries": cache.entries}, indent=1,
                         sort_keys=True))
        return 0
    return 2  # pragma: no cover


if __name__ == "__main__":
    sys.exit(main())
