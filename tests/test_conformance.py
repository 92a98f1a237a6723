"""Spec-space oracle conformance (DESIGN.md §11, §14).

The grid is no longer hand-sampled: :func:`repro.kernels.spec
.enumerate_specs` is the single source of truth for the admissible
launch-policy space, and EVERY spec it emits runs forward AND grad
against the pure-jnp oracle (``kernels/ref.py``) within per-dtype
tolerances.  A new propagation variant therefore becomes a spec plus an
automatic conformance entry — adding a kernel fork without teaching the
enumerator about it cannot pass review silently.

Two grid sizes (``GSPN_SPEC_GRID`` env):

* ``pr`` (default) — the full 44-spec grid, one cycled spatial
  orientation per fwd spec, one base shape per direction family; runs in
  the blocking PR matrix.
* ``full`` — every orientation × an extended shape set per spec; the
  nightly-style ``spec-grid`` CI lane.

On top of the enumerated grid, seeded property-based sampling covers the
expensive cross-cutting invariants: pair/quad fusion ≡ per-direction
composition, chunked prefill ≡ one-shot, and depth-1 ≡ depth-2 bitwise.
A tuner sweep still runs every row tile the candidate enumerator can
emit, proving any cache entry numerically safe before it reaches a
launch site.
"""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gspn as G
from repro.kernels import autotune
from repro.kernels import gspn_multidir as MK
from repro.kernels import ref as R
from repro.kernels.ops import gspn_scan_pair
from repro.kernels.spec import ScanSpec, enumerate_specs

pytestmark = pytest.mark.kernels

GRID_MODE = os.environ.get("GSPN_SPEC_GRID", "pr")   # pr | full
SINGLE_DIRS = ["tb", "bt", "lr", "rl"]

# Per-dtype (rtol, atol): the kernels accumulate in f32 whatever the
# stream dtype, so bf16 error is bounded by operand quantisation plus one
# output rounding per row (taps are row-stochastic => non-expansive).
TOL = {
    "float32": {"fwd": (1e-5, 1e-5), "grad": (1e-4, 1e-5)},
    "bfloat16": {"fwd": (7.5e-2, 7.5e-2), "grad": (1.5e-1, 1.5e-1)},
}

# Shapes per direction family.  The quad launch requires square grids.
BASE_SHAPES = {"fwd": (12, 8), "pair_fwd": (12, 8), "quad": (12, 12)}
FULL_EXTRA_SHAPES = {
    "fwd": [(16, 24), (24, 16), (8, 32)],
    "pair_fwd": [(16, 24), (24, 16), (8, 32)],
    "quad": [(16, 16), (8, 8)],
}

SPECS = enumerate_specs()


def _cases():
    """(spec, orientation, h, w) — the enumerated sweep.

    ``pr`` runs every spec once (orientation cycled across fwd specs so
    the four spatial directions all stay covered); ``full`` crosses each
    spec with every orientation and the extended shape set.
    """
    cases = []
    for i, sp in enumerate(SPECS):
        fam = sp.direction
        shapes = [BASE_SHAPES[fam]]
        if GRID_MODE == "full":
            shapes += FULL_EXTRA_SHAPES[fam]
        if fam == "fwd":
            oris = SINGLE_DIRS if GRID_MODE == "full" \
                else [SINGLE_DIRS[i % 4]]
        else:
            oris = [None]
        for ori in oris:
            for h, w in shapes:
                cases.append((sp, ori, h, w))
    return cases


CASES = _cases()


def _case_id(case):
    sp, ori, h, w = case
    return f"{sp.spec_id()}-{ori or sp.direction}-h{h}w{w}".replace("|", "_")


def _operands(h, w, c, gw, dtype, seed, n_dirs: int = 1):
    """x/lam (C, H, W), softmaxed taps (n_dirs*, Gw, H, W), dy cotangent."""
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (c, h, w)).astype(dt)
    lam = jax.nn.sigmoid(jax.random.normal(ks[1], (c, h, w))).astype(dt)
    shape = (n_dirs, gw, h, w, 3) if n_dirs > 1 else (gw, h, w, 3)
    taps = jax.nn.softmax(jax.random.normal(ks[2], shape), axis=-1)
    wl, wc, wr = (taps[..., i].astype(dt) for i in range(3))
    dy = jax.random.normal(ks[3], (c, h, w))
    return x, wl, wc, wr, lam, dy


def _oracle_single(x, wl, wc, wr, lam, direction):
    """ref.py scan in f32 on the oriented operands, un-oriented back."""
    can = lambda a: G._to_canonical(a.astype(jnp.float32), direction)
    h = R.gspn_scan_ref(can(x), can(wl), can(wc), can(wr), can(lam))
    return G._from_canonical(h, direction)


def _oracle_pair(x, wl2, wc2, wr2, lam2):
    f32 = lambda a: a.astype(jnp.float32)
    fwd = R.gspn_scan_ref(f32(x), f32(wl2[0]), f32(wc2[0]), f32(wr2[0]),
                          f32(lam2[0]))
    rev = R.gspn_scan_ref(f32(x), f32(wl2[1]), f32(wc2[1]), f32(wr2[1]),
                          f32(lam2[1]), reverse=True)
    return jnp.stack([fwd, rev])


def _oracle_quad(x, wl4, wc4, wr4, lam4):
    """Quad-launch semantics: entries 0/1 stream x, entries 2/3 its
    transpose (taps arrive pre-transposed); odd entries scan reversed."""
    f32 = lambda a: a.astype(jnp.float32)
    xt = jnp.swapaxes(f32(x), -1, -2)
    outs = []
    for d in range(4):
        outs.append(R.gspn_scan_ref(
            f32(x) if d < 2 else xt, f32(wl4[d]), f32(wc4[d]),
            f32(wr4[d]), f32(lam4[d]), reverse=(d % 2 == 1)))
    return jnp.stack(outs)


def _check(a, b, which, dtype):
    rtol, atol = TOL[dtype][which]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=rtol, atol=atol, err_msg=which)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_spec_grid_conformance(case):
    """Every spec the enumerator emits matches the oracle, fwd + grad.

    The spec travels intact: each call path receives the enumerated
    ScanSpec itself (refined only in the shape-derived legs), so the grid
    exercises the exact objects the autotune cache is keyed on.
    """
    sp, ori, h, w = case
    seed = CASES.index(case)
    c = sp.channels_per_weight * 2
    gw = c // sp.channels_per_weight

    if sp.direction == "fwd":
        x, wl, wc, wr, lam, dy = _operands(h, w, c, gw, sp.stream_dtype,
                                           seed)

        def impl_fn(*a):
            return G.directional_scan(*a, ori, spec=sp)

        want = _oracle_single(x, wl, wc, wr, lam, ori)
        args = (x, wl, wc, wr, lam)
        cot = dy
    elif sp.direction == "pair_fwd":
        x, wl2, wc2, wr2, lam_s, dy = _operands(h, w, c, gw,
                                                sp.stream_dtype, seed,
                                                n_dirs=2)
        lam2 = jnp.stack([lam_s, -lam_s])

        def impl_fn(*a):
            return gspn_scan_pair(*a, spec=sp)

        want = _oracle_pair(x, wl2, wc2, wr2, lam2)
        args = (x, wl2, wc2, wr2, lam2)
        cot = jnp.stack([dy, -dy])
    else:   # quad — forward-only single-launch path
        x, wl4, wc4, wr4, lam_s, _ = _operands(h, w, c, gw,
                                               sp.stream_dtype, seed,
                                               n_dirs=4)
        lam4 = jnp.stack([lam_s, -lam_s, 2 * lam_s, lam_s])
        got = MK.gspn_scan_quad_pallas(
            x, {"wl": wl4, "wc": wc4, "wr": wr4}, lam4, spec=sp)
        _check(got, _oracle_quad(x, wl4, wc4, wr4, lam4), "fwd",
               sp.stream_dtype)
        return

    _check(impl_fn(*args), want, "fwd", sp.stream_dtype)

    def loss_impl(*a):
        return jnp.sum(impl_fn(*a).astype(jnp.float32) * cot)

    if sp.direction == "fwd":
        def loss_ref(*a):
            return jnp.sum(_oracle_single(*a, ori) * cot)
    else:
        def loss_ref(*a):
            return jnp.sum(_oracle_pair(*a) * cot)

    g_impl = jax.grad(loss_impl, argnums=tuple(range(5)))(*args)
    g_ref = jax.grad(loss_ref, argnums=tuple(range(5)))(*args)
    for gi, gr in zip(g_impl, g_ref):
        _check(gi, gr, "grad", sp.stream_dtype)


def test_enumerated_grid_is_the_whole_admissible_space():
    """Structural pins on the enumerator itself: the grid stays exactly
    the dispatch matrix × dtype policy × channel modes — a silently
    shrunken grid would hollow out the sweep above."""
    assert len(SPECS) == len(set(SPECS))             # hashable + distinct
    dirs = {s.direction for s in SPECS}
    assert dirs == {"fwd", "pair_fwd", "quad"}
    assert {s.channels_per_weight for s in SPECS} == {1, 3}
    assert {s.stream_dtype for s in SPECS} == {"float32", "bfloat16"}
    for s in SPECS:
        if s.impl == "xla":
            assert s.pipeline_depth is None and s.carry_dtype == "float32"
        else:
            assert s.pipeline_depth in (1, 2)
            if s.stream_dtype == "float32":
                assert s.carry_dtype == "float32"
    # bf16 fused entries carry both policies; every fused entry appears
    # at both depths.
    fused = [s for s in SPECS if s.impl in ("pallas", "multidir")]
    assert {s.carry_dtype for s in fused
            if s.stream_dtype == "bfloat16"} == {"float32", "bfloat16"}
    assert all(s.with_(pipeline_depth=3 - s.pipeline_depth) in set(SPECS)
               for s in fused)


# ---------------------------------------------------------------------------
# Seeded property-based sampling: the expensive cross-cutting invariants
# (fusion ≡ composition, chunked prefill ≡ one-shot).  Each sample draws
# a random geometry/policy from a fixed seed, so the sampled subspace
# grows over reruns of the full lane without bloating the PR matrix.
# ---------------------------------------------------------------------------

N_PROPERTY_SAMPLES = 3 if GRID_MODE == "pr" else 8


def _sample_rng(seed):
    return random.Random(0xC0FFEE + seed)


@pytest.mark.parametrize("sample", range(N_PROPERTY_SAMPLES))
def test_property_pair_fusion_equals_composition(sample):
    """The fused opposite pair ≡ two independent directional scans, fwd
    and grad — the invariant that lets dispatch fuse without asking."""
    rng = _sample_rng(sample)
    h = rng.choice([8, 12, 16, 24])
    w = rng.choice([8, 16, 24])
    cpw = rng.choice([1, 2, 4])
    dtype = rng.choice(["float32", "bfloat16"])
    c = cpw * 2
    x, wl2, wc2, wr2, lam_s, dy = _operands(h, w, c, c // cpw, dtype,
                                            200 + sample, n_dirs=2)
    lam2 = jnp.stack([lam_s, -lam_s])
    dy2 = jnp.stack([dy, -dy])
    sp = ScanSpec(impl="multidir", channels_per_weight=cpw)

    def fused(*a):
        return gspn_scan_pair(*a, spec=sp)

    def composed(x, wl2, wc2, wr2, lam2):
        one = ScanSpec(impl="pallas", channels_per_weight=cpw)
        tb = G.directional_scan(x, wl2[0], wc2[0], wr2[0], lam2[0], "tb",
                                spec=one)
        bt = G.directional_scan(x, wl2[1], wc2[1], wr2[1], lam2[1], "bt",
                                spec=one)
        return jnp.stack([tb, bt])

    args = (x, wl2, wc2, wr2, lam2)
    _check(fused(*args), composed(*args), "fwd", dtype)
    gf = jax.grad(lambda *a: jnp.sum(fused(*a).astype(jnp.float32) * dy2),
                  argnums=tuple(range(5)))(*args)
    gc = jax.grad(lambda *a: jnp.sum(composed(*a).astype(jnp.float32)
                                     * dy2),
                  argnums=tuple(range(5)))(*args)
    for a, b in zip(gf, gc):
        _check(a, b, "grad", dtype)


@pytest.mark.parametrize("sample", range(N_PROPERTY_SAMPLES))
def test_property_quad_fusion_equals_composition(sample):
    """The single-launch quad ≡ four per-direction reference scans."""
    rng = _sample_rng(100 + sample)
    n = rng.choice([8, 12, 16])
    cpw = rng.choice([1, 2])
    dtype = rng.choice(["float32", "bfloat16"])
    c = cpw * 2
    x, wl4, wc4, wr4, lam_s, _ = _operands(n, n, c, c // cpw, dtype,
                                           300 + sample, n_dirs=4)
    lam4 = jnp.stack([lam_s, -lam_s, 2 * lam_s, lam_s])
    sp = ScanSpec(direction="quad", impl="multidir",
                  channels_per_weight=cpw)
    got = MK.gspn_scan_quad_pallas(x, {"wl": wl4, "wc": wc4, "wr": wr4},
                                   lam4, spec=sp)
    _check(got, _oracle_quad(x, wl4, wc4, wr4, lam4), "fwd", dtype)


@pytest.mark.parametrize("sample", range(N_PROPERTY_SAMPLES))
def test_property_chunked_prefill_equals_oneshot(sample):
    """Chaining row-aligned prefill chunks (ragged tail allowed) over a
    sampled split ≡ the one-shot mixer at 1e-5 — the §9 serve contract."""
    rng = _sample_rng(200 + sample)
    w = rng.choice([4, 8])
    n_rows = rng.randint(4, 8)
    tail = rng.randint(1, w)            # ragged final chunk
    total = (n_rows - 1) * w + tail
    scfg = G.GSPNSeqConfig(dim=12, proxy_dim=4, row_width=w, impl="xla")
    p = G.init_gspn_seq_mixer(jax.random.PRNGKey(400 + sample), scfg)
    x = jax.random.normal(jax.random.PRNGKey(500 + sample), (2, total, 12))
    ref = G.apply_gspn_seq_mixer(p, x, scfg)

    # Random row-aligned split points, ragged tail.
    rows = sorted(rng.sample(range(1, n_rows), rng.randint(1, 3)))
    bounds = [0] + [r * w for r in rows] + [total]
    cache = {"prev_row": jnp.zeros((2, 4, w)),
             "cur_row": jnp.zeros((2, 4, w)),
             "row_state": jnp.zeros((2, 4)),
             "pos": jnp.zeros((2,), jnp.int32)}
    ys = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        y, cache = G.gspn_seq_prefill_chunk(p, x[:, lo:hi], scfg, cache)
        ys.append(y)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, axis=1)), np.asarray(ref),
        rtol=1e-5, atol=1e-5, err_msg=str(bounds))


# ---------------------------------------------------------------------------
# Every config the tuner can emit: the cache only ever stores row tiles
# from enumerate_candidates, so sweeping the enumerator's output over the
# fused specs proves any cache entry is safe (DESIGN.md §11).
# ---------------------------------------------------------------------------

# One probe per (direction, stream, cpw) at the policy carry — depth and
# tile are plan OUTPUTS here, so the depth/carry spec axes would only
# duplicate sweeps.
TUNER_SPECS = [s for s in SPECS
               if s.impl in ("pallas", "multidir")
               and s.pipeline_depth == 1 and s.carry_dtype == "float32"]


def _tuner_id(sp):
    return sp.spec_id().replace("|", "_")


@pytest.mark.parametrize("sp", TUNER_SPECS, ids=_tuner_id)
def test_every_tuner_candidate_matches_oracle(sp):
    seed = 1000 + TUNER_SPECS.index(sp)
    h, w = (16, 16) if sp.direction == "quad" else (16, 8)
    c = sp.channels_per_weight * 2
    gw = c // sp.channels_per_weight
    probe = sp.with_(row_tile=None, pipeline_depth=None)
    key = autotune.ScanKey(
        autotune.device_kind(True), h, w, c, probe.direction, probe.impl,
        probe.stream_dtype, probe.carry_dtype, probe.channel_shared,
        probe.boundary)
    cands = autotune.enumerate_candidates(key)
    assert cands, key
    plans = sorted({(cand.row_tile, cand.pipeline_depth)
                    for cand in cands})
    tiles = sorted({t for t, _ in plans})
    # The heuristic's choice is always in the candidate set — a measured
    # winner can therefore never be slower than the heuristic beyond
    # timing noise (the tuner times the heuristic tile too).
    assert autotune.heuristic_row_tile(key) in tiles
    # In interpret mode depth 2 is enumerated exactly for narrow streams
    # (admission policy: f32 stages only on a compiled device).
    assert (2 in {d for _, d in plans}) == (key.stream_bytes < 4)

    if sp.direction == "pair_fwd":
        x, wl2, wc2, wr2, lam_s, _ = _operands(h, w, c, gw,
                                               sp.stream_dtype, seed,
                                               n_dirs=2)
        lam2 = jnp.stack([lam_s, lam_s])
        want = _oracle_pair(x, wl2, wc2, wr2, lam2)
        for t, d in plans:
            got = gspn_scan_pair(x, wl2, wc2, wr2, lam2,
                                 spec=probe.with_(row_tile=t,
                                                  pipeline_depth=d))
            _check(got, want, "fwd", sp.stream_dtype)
    elif sp.direction == "quad":
        x, wl4, wc4, wr4, lam_s, _ = _operands(h, w, c, gw,
                                               sp.stream_dtype, seed,
                                               n_dirs=4)
        lam4 = jnp.stack([lam_s] * 4)
        want = _oracle_quad(x, wl4, wc4, wr4, lam4)
        for t, d in plans:
            got = MK.gspn_scan_quad_pallas(
                x, {"wl": wl4, "wc": wc4, "wr": wr4}, lam4,
                spec=probe.with_(row_tile=t, pipeline_depth=d))
            _check(got, want, "fwd", sp.stream_dtype)
    else:
        x, wl, wc, wr, lam, _ = _operands(h, w, c, gw, sp.stream_dtype,
                                          seed)
        want = _oracle_single(x, wl, wc, wr, lam, "tb")
        for t, d in plans:
            got = G.directional_scan(
                x, wl, wc, wr, lam, "tb",
                spec=probe.with_(row_tile=t, pipeline_depth=d))
            _check(got, want, "fwd", sp.stream_dtype)


# ---------------------------------------------------------------------------
# Pipeline-depth bit agreement (DESIGN.md §12).
#
# Depth 1 (the revolving-buffer per-plane kernels) and depth 2 (the staged
# plane-blocked pipeline) execute the SAME f32 operation sequence per
# element — staging only changes where casts and copies happen, never the
# arithmetic.  In interpret mode that makes the two depths bit-identical,
# and this grid pins it: forward AND grad, all four directions, the fused
# pair, the quad launch, bf16/f32 streams, bf16/f32 carries — two row
# tiles of 4 planes sharing one weight plane (taps broadcast in the
# stage) — plus the stage gspn2-t's train step runs at 224²: f32 pair,
# two planes per weight plane, 8 weight planes (taps staged at their own
# planes, data planes channel-major), one whole 14-row tile.
# ---------------------------------------------------------------------------

DEPTH_DIRS = SINGLE_DIRS + ["pair", "quad"]
DTYPES = ["float32", "bfloat16"]
# (H = W, planes, weight planes, row tile)
TWO_TILES = (16, 4, 1, 8)
UNALIGNED_CPW2 = (14, 16, 8, 14)
DEPTH_CASES = [
    pytest.param(d, dt, cd, TWO_TILES, id=f"{d}-{dt}-{cd}")
    for d in DEPTH_DIRS for dt in DTYPES for cd in ["float32", "bfloat16"]
] + [pytest.param("pair", "float32", "float32", UNALIGNED_CPW2,
                  id="pair-float32-float32-cpw2-h14")]


@pytest.mark.parametrize("direction,dtype,carry_dtype,geometry",
                         DEPTH_CASES)
def test_pipeline_depth_bit_agreement(direction, dtype, carry_dtype,
                                      geometry):
    seed = 77 + DEPTH_DIRS.index(direction)
    h, c, gw, row_tile = geometry
    w = h

    def bitwise(a, b):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    def spec_at(depth, **kw):
        return ScanSpec(channels_per_weight=c // gw, row_tile=row_tile,
                        carry_dtype=carry_dtype, pipeline_depth=depth,
                        **kw)

    if direction == "quad":
        # Forward-only single-launch path; exercised directly.
        x, wl4, wc4, wr4, lam_s, _ = _operands(h, w, c, gw, dtype, seed,
                                               n_dirs=4)
        lam4 = jnp.stack([lam_s] * 4)
        outs = [MK.gspn_scan_quad_pallas(
                    x, {"wl": wl4, "wc": wc4, "wr": wr4}, lam4,
                    spec=spec_at(d, impl="multidir"))
                for d in (1, 2)]
        bitwise(*outs)
        return

    if direction == "pair":
        x, wl2, wc2, wr2, lam_s, dy = _operands(h, w, c, gw, dtype, seed,
                                                n_dirs=2)
        lam2 = jnp.stack([lam_s, lam_s])

        def run(depth, *a):
            return gspn_scan_pair(*a, spec=spec_at(depth, impl="multidir"))

        args = (x, wl2, wc2, wr2, lam2)
        cot = jnp.stack([dy, -dy])
    else:
        x, wl, wc, wr, lam, dy = _operands(h, w, c, gw, dtype, seed)

        def run(depth, *a):
            return G.directional_scan(*a, direction,
                                      spec=spec_at(depth, impl="pallas"))

        args = (x, wl, wc, wr, lam)
        cot = dy

    bitwise(run(1, *args), run(2, *args))
    grads = [jax.grad(
                 lambda *a, _d=d: jnp.sum(run(_d, *a).astype(jnp.float32)
                                          * cot),
                 argnums=tuple(range(5)))(*args)
             for d in (1, 2)]
    for g1, g2 in zip(*grads):
        bitwise(g1, g2)
