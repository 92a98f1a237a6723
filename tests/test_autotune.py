"""Autotuner unit tests (DESIGN.md §11): cache round-trip and layering,
graceful fallback to the static heuristic, deterministic measurement under
an injected timer, VMEM-budget candidate admission (including the PR-4
bf16-carry byte accounting), the precision-policy routing of the static
picker, and the schema-3 spec-canonical keying with schema-2 read-compat
(DESIGN.md §14)."""

import json

import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.base import PRECISIONS, resolve_precision
from repro.kernels import autotune as A
from repro.kernels import tuning
from repro.kernels.spec import ScanSpec

pytestmark = pytest.mark.kernels


def _key(**kw):
    base = dict(device="testdev", h=64, w=32, c=4, direction="fwd",
                impl="pallas", dtype="float32", carry_dtype="float32",
                channel_shared=True)
    base.update(kw)
    return A.ScanKey(**base)


# ---------------------------------------------------------------------------
# Cache persistence.
# ---------------------------------------------------------------------------

def test_cache_roundtrips_to_disk(tmp_path):
    cache = A.TuningCache()
    k1, k2 = _key(), _key(direction="bwd", dtype="bfloat16")
    e1 = {"row_tile": 16, "double_buffer": True, "us": 12.5,
          "n_grid_steps": 4, "working_set_bytes": 1024,
          "source": "measured"}
    e2 = dict(e1, row_tile=8, us=99.0)
    cache.store(k1, e1)
    cache.store(k2, e2)
    path = cache.save(tmp_path / "cache.json")

    fresh = A.TuningCache.load(path)
    assert len(fresh) == 2
    assert fresh.lookup(k1) == e1
    assert fresh.lookup(k2) == e2
    # distinct keys stay distinct under encode()
    assert k1.encode() != k2.encode()


def test_corrupt_or_missing_cache_loads_empty(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert len(A.TuningCache.load(bad)) == 0
    assert len(A.TuningCache.load(tmp_path / "nope.json")) == 0
    # wrong payload shape is also tolerated
    bad.write_text(json.dumps({"entries": [1, 2]}))
    assert len(A.TuningCache.load(bad)) == 0


def test_env_cache_layers_over_seed(tmp_path, monkeypatch):
    k = _key(device=A.device_kind(True), h=32)
    extra = A.TuningCache()
    extra.store(k, {"row_tile": 8, "double_buffer": True, "us": 1.0,
                    "n_grid_steps": 4, "working_set_bytes": 64,
                    "source": "measured"})
    path = extra.save(tmp_path / "overlay.json")
    monkeypatch.setenv(A.ENV_CACHE_PATH, str(path))
    try:
        cache = A.get_cache(reload=True)
        assert cache.lookup(k)["row_tile"] == 8
        assert A.row_tile_for(32, k.w, c=k.c, direction="fwd",
                              dtype="float32", channel_shared=True,
                              interpret=True) == 8     # heuristic: 32
    finally:
        monkeypatch.delenv(A.ENV_CACHE_PATH)
        A.get_cache(reload=True)        # restore the unlayered global


# ---------------------------------------------------------------------------
# Lookup / fallback ladder.
# ---------------------------------------------------------------------------

def test_miss_falls_back_to_heuristic_without_error():
    empty = A.TuningCache()
    got = A.row_tile_for(64, 32, c=4, direction="fwd", dtype="float32",
                         channel_shared=True, cache=empty)
    want = tuning.pick_row_tile(64, 32, 4, cap=A.DEFAULT_CAP,
                                n_streams=6, carry_dtype_bytes=4).row_tile
    assert got == want
    # and matches the legacy gspn_scan wrapper's accounting exactly
    from repro.kernels.gspn_scan import pick_row_tile as wrapper
    assert got == wrapper(64, w=32, dtype_bytes=4)


def test_unknown_device_entry_is_a_miss():
    cache = A.TuningCache()
    cache.store(_key(device="tpu-v99"), {"row_tile": 2})
    got = A.row_tile_for(64, 32, c=4, direction="fwd", dtype="float32",
                         channel_shared=True, cache=cache)
    # the current device key differs, so the entry never matches
    assert got == tuning.pick_row_tile(64, 32, 4, cap=A.DEFAULT_CAP,
                                       n_streams=6).row_tile


def test_hit_overrides_heuristic():
    key = _key(device=A.device_kind(False))
    cache = A.TuningCache()
    cache.store(key, {"row_tile": 8, "double_buffer": True, "us": 1.0,
                      "n_grid_steps": 8, "working_set_bytes": 64,
                      "source": "measured"})
    got = A.row_tile_for(key.h, key.w, c=key.c, direction="fwd",
                         dtype="float32", channel_shared=True, cache=cache)
    assert got == 8  # not the heuristic's 64


@pytest.mark.parametrize("bad_entry", [
    {"row_tile": 3},            # not a sublane-tile multiple
    {"row_tile": 48},           # does not divide h=64
    {"row_tile": 0},
    {"row_tile": "wat"},
    {},
])
def test_invalid_cache_entry_falls_back(bad_entry):
    key = _key(device=A.device_kind(False))
    cache = A.TuningCache()
    cache.store(key, bad_entry)
    got = A.row_tile_for(key.h, key.w, c=key.c, direction="fwd",
                         dtype="float32", channel_shared=True, cache=cache)
    assert got == A.heuristic_row_tile(key)


def test_oversized_cache_entry_falls_back():
    """A tile whose minimal working set exceeds VMEM is rejected even if
    it divides the scan length (stale entry from a bigger device)."""
    key = _key(device=A.device_kind(False), h=1 << 20, w=8192)
    cache = A.TuningCache()
    cache.store(key, {"row_tile": 1 << 19})
    assert not A._entry_valid(key, {"row_tile": 1 << 19})
    got = A.row_tile_for(key.h, key.w, c=key.c, direction="fwd",
                         dtype="float32", channel_shared=True, cache=cache)
    assert got == A.heuristic_row_tile(key)


# ---------------------------------------------------------------------------
# Deterministic measurement harness.
# ---------------------------------------------------------------------------

def _scripted(costs):
    """(runner_factory, timer): the runner records which candidate is
    'executing'; the timer advances a fake clock by that candidate's cost
    per reading."""
    state = {"rt": None, "t": 0.0}

    def factory(cand):
        def fn():
            state["rt"] = cand.row_tile
        return fn

    def timer():
        state["t"] += costs[state["rt"]]
        return state["t"]

    return factory, timer


def test_autotune_deterministic_under_scripted_timer():
    key = _key()
    cands = [A.Candidate(4), A.Candidate(8), A.Candidate(16)]
    factory, timer = _scripted({4: 5.0, 8: 1.0, 16: 3.0})
    cache = A.TuningCache()
    e1 = A.autotune_key(key, candidates=cands, cache=cache,
                        runner_factory=factory, timer=timer)
    assert e1["row_tile"] == 8
    assert e1["source"] == "measured"
    assert e1["n_grid_steps"] == key.h // 8

    # identical inputs => identical winner (fresh scripted state)
    factory, timer = _scripted({4: 5.0, 8: 1.0, 16: 3.0})
    e2 = A.autotune_key(key, candidates=cands, cache=A.TuningCache(),
                        runner_factory=factory, timer=timer)
    assert e2 == e1


def test_autotune_tie_breaks_to_first_candidate():
    key = _key()
    cands = [A.Candidate(4), A.Candidate(8)]
    factory, timer = _scripted({4: 2.0, 8: 2.0})
    e = A.autotune_key(key, candidates=cands, cache=A.TuningCache(),
                       runner_factory=factory, timer=timer)
    assert e["row_tile"] == 4


def test_monkeypatched_default_timer_is_honoured(monkeypatch):
    """measure() consults the module-level default timer, so a test can
    freeze time globally."""
    ticks = iter(range(100))
    monkeypatch.setattr(A, "_default_timer", lambda: float(next(ticks)))
    dt = A.measure(lambda: None, iters=3, warmup=0)
    assert dt == 1.0      # consecutive integer ticks => 1s per call


def test_winner_never_slower_than_heuristic_candidate():
    """The heuristic's tile is always in the timed candidate set, so the
    measured winner's cost is <= the heuristic tile's cost."""
    key = _key()
    cands = A.enumerate_candidates(key)
    heur = A.heuristic_row_tile(key)
    assert heur in [c.row_tile for c in cands]
    costs = {c.row_tile: float(i + 1) for i, c in enumerate(cands)}
    factory, timer = _scripted(costs)
    e = A.autotune_key(key, candidates=cands, cache=A.TuningCache(),
                       runner_factory=factory, timer=timer)
    assert costs[e["row_tile"]] <= costs[heur]


def test_warm_measures_real_kernel(tmp_path):
    """End-to-end: one tiny spec through the real jitted interpret-mode
    kernel lands a valid measured entry in the cache."""
    cache = A.TuningCache()
    A.warm([(8, 8, 2, "fwd", "pallas", "float32", True)], cache=cache,
           iters=1, verbose=False)
    assert len(cache) == 1
    (entry,) = cache.entries.values()
    assert entry["source"] == "measured"
    assert 8 % entry["row_tile"] == 0
    path = cache.save(tmp_path / "warm.json")
    assert A.TuningCache.load(path).entries == cache.entries


# ---------------------------------------------------------------------------
# Candidate admission: the VMEM budget is a hard wall.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("budget", [1 << 14, 1 << 16, 1 << 18])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_candidates_never_exceed_vmem_budget(budget, dtype):
    # Row tiles start at one sublane tile (8 f32 / 16 bf16 rows) rather
    # than one row, so each budget is scaled by 16 to stay admissible.
    budget *= 16
    key = _key(h=4096, w=128, dtype=dtype)
    cands = A.enumerate_candidates(key, vmem_budget=budget)
    assert cands, (budget, dtype)
    for c in cands:
        # the double-buffered footprint Pallas allocates must fit, and so
        # (a fortiori) must the resident working set
        assert c.working_set(key) <= budget
        assert A.Candidate(c.row_tile, double_buffer=False) \
            .working_set(key) <= budget


def test_candidate_admission_grows_with_budget():
    key = _key(h=4096, w=128)
    small = max(c.row_tile
                for c in A.enumerate_candidates(key, vmem_budget=1 << 16))
    big = max(c.row_tile
              for c in A.enumerate_candidates(key, vmem_budget=1 << 20))
    assert big > small


def test_candidate_bf16_carry_byte_accounting():
    """Regression pin of the PR-4 accounting at the candidate level: the
    streamed term scales with the stream dtype, the carry term with the
    carry dtype — and the adjoint directions carry three f32 rows."""
    w, t, n = 128, 64, 6
    k_f32 = _key(w=w)
    k_bf16 = _key(w=w, dtype="bfloat16")
    k_bf16_carry = _key(w=w, dtype="bfloat16", carry_dtype="bfloat16")
    assert A.Candidate(t).working_set(k_f32) == n * t * w * 4 * 2 + w * 4
    # narrow streams add a one-group (16-row) f32 widening stage
    stage = n * 16 * w * 4
    assert A.Candidate(t).working_set(k_bf16) \
        == n * t * w * 2 * 2 + stage + w * 4
    assert A.Candidate(t).working_set(k_bf16_carry) \
        == n * t * w * 2 * 2 + stage + w * 2
    # adjoint kernels: 5 streams, 3 carry rows, carry always f32
    k_bwd = _key(w=w, direction="bwd", dtype="bfloat16")
    assert k_bwd.carry_bytes == 3 * 4
    assert A.Candidate(t).working_set(k_bwd) \
        == 5 * t * w * 2 * 2 + 5 * 16 * w * 4 + w * 12
    # at a tight budget (and a scan long enough not to cap on divisors),
    # bf16 streams admit strictly larger tiles
    budget = 1 << 18
    max16 = max(c.row_tile for c in A.enumerate_candidates(
        _key(h=4096, w=w, dtype="bfloat16"), vmem_budget=budget))
    max32 = max(c.row_tile for c in A.enumerate_candidates(
        _key(h=4096, w=w), vmem_budget=budget))
    assert max16 > max32


def test_scan_key_rejects_unknown_direction():
    with pytest.raises(ValueError):
        _key(direction="sideways")


# ---------------------------------------------------------------------------
# Precision-policy routing (the fix for dtype_bytes=4-regardless-of-policy
# call sites) — parametrized over every named preset.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PRECISIONS))
def test_pick_row_tile_routes_through_policy(name):
    p = resolve_precision(name)
    sb, cb = tuning.policy_itemsizes(name)
    assert sb == jnp.dtype(p.compute_dtype).itemsize
    assert cb == jnp.dtype(p.carry_dtype).itemsize
    tc = tuning.pick_row_tile_for_policy(4096, 128, name,
                                         vmem_budget=1 << 21)
    want = tuning.pick_row_tile(4096, 128, sb, vmem_budget=1 << 21,
                                carry_dtype_bytes=cb)
    assert tc == want


def test_policy_presets_pin_expected_itemsizes():
    assert tuning.policy_itemsizes("f32") == (4, 4)
    assert tuning.policy_itemsizes("bf16") == (2, 4)      # f32 carries
    assert tuning.policy_itemsizes("bf16_f32params") == (2, 4)
    # bf16 streams unlock a >= tile vs f32 at any fixed budget
    t16 = tuning.pick_row_tile_for_policy(4096, 128, "bf16",
                                          vmem_budget=1 << 21).row_tile
    t32 = tuning.pick_row_tile_for_policy(4096, 128, "f32",
                                          vmem_budget=1 << 21).row_tile
    assert t16 >= 2 * t32


# ---------------------------------------------------------------------------
# Pipeline depth: schema-2 entries, back-compat reads, depth selection
# (DESIGN.md §12).
# ---------------------------------------------------------------------------

def test_pipeline_depth_cache_roundtrip(tmp_path):
    cache = A.TuningCache()
    key = _key(dtype="bfloat16")
    entry = {"row_tile": 16, "double_buffer": True, "pipeline_depth": 2,
             "us": 3.0, "n_grid_steps": 4, "working_set_bytes": 4096,
             "source": "measured"}
    cache.store(key, entry)
    path = cache.save(tmp_path / "depth.json")
    payload = json.loads(path.read_text())
    assert payload["schema"] == A.SCHEMA_VERSION == 3
    fresh = A.TuningCache.load(path)
    assert fresh.lookup(key)["pipeline_depth"] == 2
    plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                      dtype="bfloat16", channel_shared=True, cache=fresh)
    # device differs from "testdev" => miss; re-store under the real key
    key_dev = _key(device=A.device_kind(False), dtype="bfloat16")
    fresh.store(key_dev, entry)
    plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                      dtype="bfloat16", channel_shared=True, cache=fresh)
    assert plan == A.ScanPlan(row_tile=16, pipeline_depth=2)


def test_pre_pr6_cache_file_reads_as_depth_1(tmp_path):
    """A schema-1 file (no pipeline_depth field anywhere) must load
    without error and resolve to depth 1 — the pre-PR6 kernels."""
    key = _key(device=A.device_kind(False))
    old_payload = {"schema": 1, "entries": {key.encode(): {
        "row_tile": 8, "double_buffer": True, "us": 5.0,
        "n_grid_steps": 8, "working_set_bytes": 2048,
        "source": "measured"}}}
    path = tmp_path / "pre_pr6.json"
    path.write_text(json.dumps(old_payload))
    cache = A.TuningCache.load(path)
    assert len(cache) == 1
    plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                      dtype="float32", channel_shared=True, cache=cache)
    assert plan == A.ScanPlan(row_tile=8, pipeline_depth=1)


def test_garbage_pipeline_depth_entry_falls_back():
    key = _key(device=A.device_kind(False))
    cache = A.TuningCache()
    for bad in ("wat", 3, -1, None):
        cache.store(key, {"row_tile": 8, "pipeline_depth": bad})
        plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                          dtype="float32", channel_shared=True, cache=cache)
        assert plan.row_tile == A.heuristic_row_tile(key)
        assert plan.pipeline_depth == 1


@pytest.mark.parametrize("kw,depths,heuristic", [
    ({}, {1}, 1),
    (dict(device="cpu+interpret", h=56, w=56, c=64), {1}, 1),
    (dict(dtype="bfloat16"), {1, 2}, 2),
    (dict(device="tpu-v5-lite", h=56, w=56, c=64), {1, 2}, 2),
    (dict(device="tpu-v5-lite", h=256, w=256, c=16), {1, 2}, 2),
    (dict(device="tpu-v5-lite", c=4), {1}, 1),
    (dict(device="tpu-v5-lite", h=4, w=1024, c=2048), {1}, 1),
], ids=["f32-g4", "f32-interpret", "bf16", "f32-v5e-g64", "f32-v5e-g16",
        "f32-v5e-g4", "f32-v5e-too-big"])
def test_depth_enumeration_follows_stream_width(kw, depths, heuristic):
    """Depth 1 is always enumerated.  Depth 2 is enumerated for narrow
    (< 4-byte) streams, and for 4-byte streams only on a compiled device
    (not ``+interpret``) with G >= 8 planes whose depth-2 working set
    fits VMEM: the train_224 (G 64, W = H = 56) and infer_1024 (G 16,
    W = H = 256) stages, not G 4, nor G 2048 of 1024-wide rows."""
    key = _key(**kw)
    assert {c.pipeline_depth for c in A.enumerate_candidates(key)} == depths
    assert A.heuristic_pipeline_depth(key) == heuristic


@pytest.mark.parametrize("g,h,depth", [
    (8, 2, 2),          # one chunk + boundary row, batch 1
    (256, 32, 1),       # prefill_32k: batch 32 × 8 planes, 32 rows
    (2048, 4, 1),       # train_4k: batch 256 × 8 planes, 4 rows
], ids=["chunk_b1", "prefill_32k", "train_4k"])
def test_heuristic_plan_fits_vmem_at_model_planes(g, h, depth):
    """Depth 2 holds all G planes in every grid step, so at the LM
    shapes' plane counts (G = batch × 8, W = 1024, bf16) the heuristic
    must fall back to depth 1 rather than emit a plan the compiler
    refuses; whatever it emits fits the VMEM limit, with or without an
    explicit row tile."""
    key = _key(h=h, w=1024, c=g, dtype="bfloat16")
    assert A.heuristic_pipeline_depth(key) == depth
    t = A.heuristic_row_tile(key)
    assert A.Candidate(t, pipeline_depth=depth).working_set(key) \
        <= tuning.VMEM_BYTES
    spec = ScanSpec(impl="pallas", channels_per_weight=8,
                    stream_dtype="bfloat16", row_tile=h)
    plan = A.plan_for_spec(spec, h, 1024, c=g, cache=A.TuningCache())
    assert A.Candidate(h, pipeline_depth=plan.pipeline_depth) \
        .working_set(key) <= tuning.VMEM_BYTES


def test_explicit_args_override_plan():
    """An explicit row_tile bypasses the cache; an explicit depth wins
    over both cache and heuristic."""
    key = _key(device=A.device_kind(False), dtype="bfloat16")
    cache = A.TuningCache()
    cache.store(key, {"row_tile": 16, "pipeline_depth": 1})
    kw = dict(c=key.c, direction="fwd", dtype="bfloat16",
              channel_shared=True, cache=cache)
    assert A.plan_for(key.h, key.w, row_tile=32, **kw) \
        == A.ScanPlan(32, 2)                 # heuristic depth for bf16
    assert A.plan_for(key.h, key.w, row_tile=32, pipeline_depth=1, **kw) \
        == A.ScanPlan(32, 1)
    assert A.plan_for(key.h, key.w, pipeline_depth=2, **kw) \
        == A.ScanPlan(16, 2)                 # cache tile, forced depth


def _scripted_depth(costs):
    """Like _scripted but keyed by (row_tile, pipeline_depth)."""
    state = {"k": None, "t": 0.0}

    def factory(cand):
        def fn():
            state["k"] = (cand.row_tile, cand.pipeline_depth)
        return fn

    def timer():
        state["t"] += costs[state["k"]]
        return state["t"]

    return factory, timer


def test_scripted_timer_selects_depth_2_when_faster():
    key = _key(dtype="bfloat16")
    cands = [A.Candidate(16, pipeline_depth=1),
             A.Candidate(16, pipeline_depth=2),
             A.Candidate(32, pipeline_depth=1)]
    factory, timer = _scripted_depth({(16, 1): 9.0, (16, 2): 1.0,
                                      (32, 1): 5.0})
    cache = A.TuningCache()
    e = A.autotune_key(key, candidates=cands, cache=cache,
                       runner_factory=factory, timer=timer)
    assert e["row_tile"] == 16
    assert e["pipeline_depth"] == 2
    # ...and the stored entry drives the plan
    plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                      dtype="bfloat16", channel_shared=True, cache=cache)
    # key device is "testdev" — rebuild under the live device for lookup
    key_dev = _key(device=A.device_kind(False), dtype="bfloat16")
    cache.store(key_dev, e)
    plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                      dtype="bfloat16", channel_shared=True, cache=cache)
    assert plan == A.ScanPlan(16, 2)


def test_scripted_timer_keeps_depth_1_when_faster():
    key = _key(dtype="bfloat16")
    cands = [A.Candidate(16, pipeline_depth=1),
             A.Candidate(16, pipeline_depth=2)]
    factory, timer = _scripted_depth({(16, 1): 1.0, (16, 2): 9.0})
    e = A.autotune_key(key, candidates=cands, cache=A.TuningCache(),
                       runner_factory=factory, timer=timer)
    assert e["pipeline_depth"] == 1


def test_depth2_candidates_respect_vmem_budget():
    """The staging term is part of admission: at a tight budget the
    largest depth-2 tile is half the largest depth-1 bf16 tile."""
    # One plane: a depth-2 tile holds all c planes, so c=1 keeps the
    # comparison per plane.
    key = _key(h=4096, w=128, dtype="bfloat16", c=1)
    budget = 1 << 18
    cands = A.enumerate_candidates(key, vmem_budget=budget)
    for c in cands:
        assert A.Candidate(c.row_tile, double_buffer=False,
                           pipeline_depth=c.pipeline_depth) \
            .working_set(key) <= budget
    max_d1 = max(c.row_tile for c in cands if c.pipeline_depth == 1)
    max_d2 = max(c.row_tile for c in cands if c.pipeline_depth == 2)
    # staging shrinks the biggest admissible tile (the exact ×1/2 at
    # equal buffering is pinned in test_kernels); single-buffered
    # admission can stretch depth 1 even further ahead.
    assert max_d2 <= max_d1 // 2


# ---------------------------------------------------------------------------
# Schema 3: spec-canonical keys, boundary axis, schema-2 read-compat,
# and the cache-reject observability signal (DESIGN.md §14).
# ---------------------------------------------------------------------------

def test_schema3_key_is_shape_legs_plus_spec_canonical():
    key = _key(boundary="sp_block_local")
    sp = ScanSpec(direction=key.direction, impl=key.impl,
                  channels_per_weight=2, stream_dtype=key.dtype,
                  carry_dtype=key.carry_dtype, boundary=key.boundary)
    assert key.encode() == f"testdev|h64|w32|c4|{sp.canonical()}"
    assert key.encode().endswith(sp.canonical())
    # the legacy (schema-2) spelling carries no boundary leg
    assert "bnd-" not in key.encode_legacy()
    assert key.encode_legacy() == _key().encode_legacy()


def test_scan_key_rejects_unknown_boundary():
    with pytest.raises(ValueError):
        _key(boundary="wraparound")


def test_boundary_distinguishes_schema3_entries():
    """Same shape+policy, different boundary behaviour => distinct cache
    slots; each lookup finds its own entry."""
    cache = A.TuningCache()
    entry = {"row_tile": 16, "double_buffer": True, "pipeline_depth": 1,
             "us": 1.0, "n_grid_steps": 4, "working_set_bytes": 64,
             "source": "measured"}
    k_one = _key(device=A.device_kind(False))
    k_sp = _key(device=A.device_kind(False), boundary="sp_block_local")
    cache.store(k_one, dict(entry, row_tile=16))
    cache.store(k_sp, dict(entry, row_tile=8))
    assert k_one.encode() != k_sp.encode()
    assert cache.lookup(k_one)["row_tile"] == 16
    assert cache.lookup(k_sp)["row_tile"] == 8


def test_schema2_cache_file_read_compat(tmp_path):
    """A schema-2 file (legacy 9-segment keys, no boundary leg) keeps
    serving plans: the lookup falls back to the legacy encoding, and a
    boundary-less entry serves every boundary mode."""
    key = _key(device=A.device_kind(False))
    entry = {"row_tile": 16, "double_buffer": True, "pipeline_depth": 1,
             "us": 2.0, "n_grid_steps": 4, "working_set_bytes": 1024,
             "source": "measured"}
    payload = {"schema": 2, "entries": {key.encode_legacy(): entry}}
    path = tmp_path / "schema2.json"
    path.write_text(json.dumps(payload))
    cache = A.TuningCache.load(path)
    assert len(cache) == 1
    for boundary in ("one_shot", "chunk_resume", "sp_block_local"):
        plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                          dtype="float32", channel_shared=True,
                          cache=cache, boundary=boundary)
        assert plan == A.ScanPlan(row_tile=16, pipeline_depth=1)


def test_schema3_entry_shadows_legacy_fallback():
    """When both spellings are present the schema-3 key wins — re-tuned
    entries override the migrated legacy ones."""
    key = _key(device=A.device_kind(False))
    cache = A.TuningCache()
    cache.entries[key.encode_legacy()] = {"row_tile": 8}
    assert cache.lookup(key)["row_tile"] == 8       # legacy fallback
    cache.store(key, {"row_tile": 16})
    assert cache.lookup(key)["row_tile"] == 16      # v3 shadows it


def test_seed_cache_stays_legacy_keyed_for_compat_coverage():
    """The checked-in seed cache keeps schema-2 keys on purpose: every CI
    run then exercises the legacy-fallback path against real entries."""
    seed = A.TuningCache.load(A.SEED_CACHE_PATH)
    assert len(seed) > 0
    assert all("bnd-" not in k for k in seed.entries)


def test_plan_for_spec_routes_spec_fields():
    """plan_for_spec is plan_for with every leg drawn from the spec —
    including the explicit tile/depth overrides."""
    sp = ScanSpec(direction="fwd", impl="pallas", channels_per_weight=2,
                  stream_dtype="bfloat16", row_tile=32, pipeline_depth=1)
    assert A.plan_for_spec(sp, 64, 32, c=4) == A.ScanPlan(32, 1)
    sp_auto = sp.with_(row_tile=None, pipeline_depth=None)
    key = _key(device=A.device_kind(True), dtype="bfloat16")
    assert A.plan_for_spec(sp_auto, 64, 32, c=4, cache=A.TuningCache()) \
        == A.ScanPlan(A.heuristic_row_tile(key),
                      A.heuristic_pipeline_depth(key))


def test_invalid_cache_entry_emits_reject_counter_and_event():
    """Satellite: a present-but-invalid entry must not fall through to
    the heuristic silently — the reject increments a counter and logs an
    event naming the key and the reason."""
    obs.REGISTRY.reset()
    key = _key(device=A.device_kind(False))
    cache = A.TuningCache()
    cache.store(key, {"row_tile": 128})             # does not divide h=64
    before = obs.counter("autotune_cache_rejects_total").value
    obs.enable()
    try:
        plan = A.plan_for(key.h, key.w, c=key.c, direction="fwd",
                          dtype="float32", channel_shared=True,
                          cache=cache)
        rejects = [r for r in obs.records()
                   if r.ph == "i" and r.name == "autotune.cache_reject"]
    finally:
        obs.disable()
        obs.clear()
    assert plan.row_tile == A.heuristic_row_tile(key)
    assert obs.counter("autotune_cache_rejects_total").value == before + 1
    assert rejects
    assert rejects[0].args["key"] == key.encode()
    assert "divide" in rejects[0].args["reason"]
    # a clean miss (no entry at all) stays silent — no reject signal
    obs.REGISTRY.reset()
    A.plan_for(key.h, key.w, c=key.c, direction="fwd", dtype="float32",
               channel_shared=True, cache=A.TuningCache())
    assert obs.counter("autotune_cache_rejects_total").value == 0


def test_entry_invalid_reason_strings():
    key = _key()
    reason = A._entry_invalid_reason
    assert reason(key, {"row_tile": 16}) is None
    assert "missing" in reason(key, {})
    assert "power of two" in reason(key, {"row_tile": 3})
    assert "divide" in reason(key, {"row_tile": 128})
    assert "pipeline_depth" in reason(key, {"row_tile": 16,
                                            "pipeline_depth": 7})
    big = _key(h=1 << 20, w=8192)
    assert "VMEM" in reason(big, {"row_tile": 1 << 19})


# ---------------------------------------------------------------------------
# Deprecated kwargs-style shims over plan_for_spec (PR consolidation).
# ---------------------------------------------------------------------------

def test_plan_for_shim_equivalent_to_plan_for_spec(monkeypatch):
    """The deprecated kwargs surface must resolve the IDENTICAL plan as
    the spec surface for every leg combination — cache hit, reject path,
    heuristic miss — and warn exactly once per process."""
    import warnings

    monkeypatch.setattr(A, "_plan_for_warned", False)
    cache = A.TuningCache()
    hit = _key(device=A.device_kind(False))
    cache.store(hit, {"row_tile": 16, "pipeline_depth": 2})

    cases = [
        dict(direction="fwd", channel_shared=True, dtype="float32"),
        dict(direction="bwd", channel_shared=False, dtype="bfloat16"),
        dict(direction="fwd", channel_shared=True, dtype="float32",
             boundary="chunk_resume"),
        dict(direction="fwd", channel_shared=False, dtype="float32",
             row_tile=16, pipeline_depth=1),
    ]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for kw in cases:
            legacy = A.plan_for(hit.h, hit.w, c=hit.c, impl="pallas",
                                carry_dtype="float32", cache=cache, **kw)
            spec = ScanSpec(
                direction=kw["direction"], impl="pallas",
                channels_per_weight=2 if kw["channel_shared"] else 1,
                stream_dtype=kw["dtype"], carry_dtype="float32",
                row_tile=kw.get("row_tile"),
                pipeline_depth=kw.get("pipeline_depth"),
                boundary=kw.get("boundary", "one_shot"),
                interpret=False)
            assert legacy == A.plan_for_spec(spec, hit.h, hit.w, c=hit.c,
                                             cache=cache), kw
        deprecations = [w for w in rec
                        if issubclass(w.category, DeprecationWarning)
                        and "plan_for" in str(w.message)]
    assert len(deprecations) == 1       # warn-once latch across 4 calls
    # the cache-hit case actually hit: kwargs and spec agree on the key
    assert A.plan_for(hit.h, hit.w, c=hit.c, direction="fwd",
                      channel_shared=True, cache=cache) == A.ScanPlan(16, 2)


def test_row_tile_for_is_the_tile_view_of_plan_for_spec():
    cache = A.TuningCache()
    key = _key(device=A.device_kind(False), channel_shared=False)
    cache.store(key, {"row_tile": 16, "pipeline_depth": 2})
    sp = ScanSpec(direction="fwd", impl="pallas", channels_per_weight=1,
                  interpret=False)
    assert A.row_tile_for(key.h, key.w, c=key.c, channel_shared=False,
                          cache=cache) \
        == A.plan_for_spec(sp, key.h, key.w, c=key.c, cache=cache).row_tile \
        == 16
