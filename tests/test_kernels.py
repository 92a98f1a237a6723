"""Pallas kernel validation: interpret-mode vs the pure-jnp oracle across
shapes, dtypes, chunk settings and channel-sharing modes, plus gradients
against the dense Eq.-4 oracle, and the VMEM tile tuner's working-set
math under mixed dtypes (DESIGN.md §10)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gspn as G
from repro.kernels import ref as R
from repro.kernels import tuning
from repro.kernels.ops import gspn_scan

pytestmark = pytest.mark.kernels

SHAPES = [
    (1, 4, 8),
    (2, 16, 24),
    (3, 32, 16),
    (6, 8, 128),       # lane-aligned width
    (4, 64, 32),
]


def _make(gd, h, w, gw, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (gd, h, w), dtype)
    lam = jax.random.normal(ks[1], (gd, h, w), dtype)
    logits = jax.random.normal(ks[2], (gw, h, w, 3))
    wl, wc, wr = G.normalize_taps(logits)
    return x, wl.astype(dtype), wc.astype(dtype), wr.astype(dtype), lam


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("cpw", [1, 2])
def test_pallas_fwd_matches_ref(shape, cpw):
    gd, h, w = shape
    gd = gd * cpw
    x, wl, wc, wr, lam = _make(gd, h, w, gd // cpw)
    h_ref = R.gspn_scan_ref(x, wl, wc, wr, lam)
    h_pl = gspn_scan(x, wl, wc, wr, lam, impl="pallas")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_dtypes(dtype):
    x, wl, wc, wr, lam = _make(4, 16, 32, 4, dtype)
    h_ref = R.gspn_scan_ref(x.astype(jnp.float32), wl.astype(jnp.float32),
                            wc.astype(jnp.float32), wr.astype(jnp.float32),
                            lam.astype(jnp.float32))
    h_pl = gspn_scan(x, wl, wc, wr, lam, impl="pallas")
    assert h_pl.dtype == dtype
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(h_pl, np.float32),
                               np.asarray(h_ref), rtol=tol, atol=tol)


def test_scan_matches_dense_eq4_oracle():
    x, wl, wc, wr, lam = _make(2, 8, 12, 2)
    h_ref = R.gspn_scan_ref(x, wl, wc, wr, lam)
    h_dense = R.gspn_dense_oracle(x, wl, wc, wr, lam)
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_dense),
                               rtol=1e-5, atol=1e-5)


def test_per_step_emulation_matches():
    x, wl, wc, wr, lam = _make(2, 12, 16, 2)
    h_ref = R.gspn_scan_ref(x, wl, wc, wr, lam)
    h_ps = R.gspn_scan_per_step(x, wl, wc, wr, lam, block=False)
    np.testing.assert_allclose(np.asarray(h_ps), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cpw", [1, 3])
def test_custom_vjp_matches_autodiff(impl, cpw):
    gd, h, w = 2 * cpw, 16, 24
    x, wl, wc, wr, lam = _make(gd, h, w, gd // cpw, seed=3)
    logits = jax.random.normal(jax.random.PRNGKey(9), (gd // cpw, h, w, 3))

    def loss_ops(x, logits, lam):
        wl, wc, wr = G.normalize_taps(logits)
        return jnp.sum(jnp.sin(gspn_scan(x, wl, wc, wr, lam, impl=impl)))

    def loss_ref(x, logits, lam):
        wl, wc, wr = G.normalize_taps(logits)
        return jnp.sum(jnp.sin(R.gspn_scan_ref(x, wl, wc, wr, lam)))

    g_ops = jax.grad(loss_ops, argnums=(0, 1, 2))(x, logits, lam)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, logits, lam)
    for a, b in zip(g_ops, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_matches_blockdiag(chunk):
    x, wl, wc, wr, lam = _make(4, 16, 20, 2, seed=5)
    out = gspn_scan(x, wl, wc, wr, lam, chunk=chunk, impl="xla")
    ref = R.gspn_scan_chunked_ref(x, wl, wc, wr, lam, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunk_full_equals_unchunked():
    x, wl, wc, wr, lam = _make(2, 16, 20, 2, seed=6)
    a = gspn_scan(x, wl, wc, wr, lam, chunk=16, impl="pallas")
    b = gspn_scan(x, wl, wc, wr, lam, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Scan lengths no sublane tile divides: the whole-H tile (Mosaic's block
# rule admits only multiples of 8 / 16 rows or the full length).
# ---------------------------------------------------------------------------

def _check_scan_and_pair(x, wl, wc, wr, lam, want_tile):
    """gspn_scan and gspn_scan_pair (fwd + grad) at both pipeline depths
    against the f32 reference on the same (stream-rounded) operands, with
    the tuner's heuristic tile asserted to be ``want_tile``."""
    from repro.kernels import autotune
    from repro.kernels.ops import gspn_scan_pair
    from repro.kernels.spec import ScanSpec

    f32 = [a.astype(jnp.float32) for a in (x, wl, wc, wr, lam)]
    tol = 2e-2 if x.dtype == jnp.bfloat16 else 1e-5
    cpw = x.shape[0] // wl.shape[0]
    for direction in ("fwd", "pair_fwd"):
        key = autotune.ScanKey("any", x.shape[1], x.shape[2], x.shape[0],
                               direction, "pallas", str(x.dtype), "float32",
                               cpw > 1)
        for depth in (1, 2):
            assert autotune.heuristic_row_tile(
                key, pipeline_depth=depth) == want_tile
    pair = [jnp.stack([a, a[:, ::-1]]) for a in (wl, wc, wr, lam)]
    want = R.gspn_scan_ref(*f32)
    want_rev = R.gspn_scan_ref(*f32[:1], *(a[:, ::-1] for a in f32[1:]),
                               reverse=True)

    def loss_ref(x, lam):
        return jnp.sum(jnp.sin(R.gspn_scan_ref(x, *f32[1:4], lam)))

    g_want = jax.grad(loss_ref, argnums=(0, 1))(f32[0], f32[4])
    for depth in (1, 2):
        sp = ScanSpec(impl="pallas", pipeline_depth=depth)
        got = gspn_scan(x, wl, wc, wr, lam, spec=sp)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=tol, atol=tol)
        got2 = gspn_scan_pair(x, *pair, spec=sp.with_(impl="multidir"))
        np.testing.assert_allclose(np.asarray(got2[0], np.float32),
                                   np.asarray(want), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(got2[1], np.float32),
                                   np.asarray(want_rev), rtol=tol, atol=tol)

        def loss(x, lam, sp=sp):
            h = gspn_scan(x, wl, wc, wr, lam, spec=sp)
            return jnp.sum(jnp.sin(h.astype(jnp.float32)))

        if x.dtype == jnp.float32:
            for a, b in zip(jax.grad(loss, argnums=(0, 1))(x, lam), g_want):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h", [3, 12])
def test_fold_with_odd_row_count_matches_ref(h, dtype):
    """An LM prompt folding into 3 or 12 grid rows of width W (planes
    B·C_proxy with channel-shared taps): no power-of-two multiple of the
    sublane tile divides H, so the tile is the whole fold."""
    x, wl, wc, wr, lam = _make(8, h, 64, 2, dtype, seed=h)
    _check_scan_and_pair(x, wl, wc, wr, lam, want_tile=h)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_narrow_grid_w7_matches_ref(dtype):
    """The last gspn2-t stage at 224²: a 7×7 grid — whole-H tile and a
    7-lane row, through the single and the fused pair kernel."""
    x, wl, wc, wr, lam = _make(4, 7, 7, 2, dtype, seed=7)
    _check_scan_and_pair(x, wl, wc, wr, lam, want_tile=7)


# ---------------------------------------------------------------------------
# VMEM tile tuner under mixed dtypes (DESIGN.md §10).
# ---------------------------------------------------------------------------

def test_working_set_math_mixed_dtypes():
    """Exact accounting: n_streams double-buffered streamed tiles in the
    STREAM dtype + one carry row in the CARRY dtype (+ for narrow streams
    a one-sublane-group f32 widening stage per stream)."""
    t, w, n = 64, 128, 6
    assert tuning.scan_working_set(t, w, 4, n) == n * t * w * 4 * 2 + w * 4
    # bf16 streams halve the streamed term and add a 16-row f32 stage;
    # the f32 carry is fixed
    stage = n * 16 * w * 4
    assert tuning.scan_working_set(t, w, 2, n) \
        == n * t * w * 2 * 2 + stage + w * 4
    # carry_dtype_bytes moves only the carry term
    assert (tuning.scan_working_set(t, w, 2, n, carry_dtype_bytes=2)
            == n * t * w * 2 * 2 + stage + w * 2)
    # headroom: disabling double-buffering halves the streamed term only
    assert (tuning.scan_working_set(t, w, 4, n, double_buffer=False)
            == n * t * w * 4 + w * 4)


def test_pick_row_tile_bf16_unlocks_double_tile():
    """At a fixed VMEM budget, halving the streamed dtype doubles the row
    tile — the §10 payoff the backward pass was missing while it
    hard-coded dtype_bytes=4."""
    budget = 2 ** 21
    t32 = tuning.pick_row_tile(4096, 128, 4, vmem_budget=budget)
    t16 = tuning.pick_row_tile(4096, 128, 2, vmem_budget=budget)
    assert t16.row_tile == 2 * t32.row_tile
    assert t32.working_set_bytes <= budget
    assert t16.working_set_bytes <= budget
    # and the bf16 choice would NOT fit if streamed at 4 bytes
    assert tuning.scan_working_set(t16.row_tile, 128, 4) > budget


def test_pick_row_tile_carry_bytes_respected():
    """An (artificially) enormous carry must shrink the tile: the carry
    term is part of the budget, not a constant 4-byte afterthought."""
    budget = 2 ** 18
    small = tuning.pick_row_tile(1024, 128, 2, vmem_budget=budget)
    big_carry = tuning.pick_row_tile(1024, 128, 2, vmem_budget=budget,
                                     carry_dtype_bytes=400)
    assert big_carry.row_tile <= small.row_tile
    assert big_carry.working_set_bytes <= budget


@pytest.mark.parametrize("h", [48, 96, 136, 4096])
@pytest.mark.parametrize("dtype_bytes", [2, 4])
def test_pick_row_tile_divides_scan_length(h, dtype_bytes):
    c = tuning.pick_row_tile(h, 64, dtype_bytes, cap=256)
    assert h % c.row_tile == 0
    # Mosaic's block rule: a power-of-two multiple of the sublane tile,
    # or the whole scan length
    assert c.row_tile == h or (
        c.row_tile & (c.row_tile - 1) == 0
        and c.row_tile % tuning.sublane_rows(dtype_bytes) == 0)
    assert c.n_grid_steps == h // c.row_tile
    assert c.row_tile <= 256


def test_bwd_row_tile_sees_streamed_dtype():
    """gspn_scan_bwd_pallas routes the REAL dy dtype into the tuner (the
    fix for the hard-coded dtype_bytes=4): at equal shapes the bf16
    adjoint may never pick a smaller tile than the f32 one."""
    from repro.kernels.gspn_scan import pick_row_tile as wrapper
    t32 = wrapper(4096, w=128, dtype_bytes=4, n_streams=5,
                  carry_dtype_bytes=12)
    t16 = wrapper(4096, w=128, dtype_bytes=2, n_streams=5,
                  carry_dtype_bytes=12)
    assert t16 >= t32


def test_depth2_staging_term_in_working_set():
    """Depth-2 keeps exactly one whole-tile f32 staging copy per streamed
    tile (DESIGN.md §12), independent of the stream dtype; depth 1 stages
    narrow streams one 16-row group at a time."""
    t, w, n = 64, 128, 6
    for b, d1_stage in ((2, n * 16 * w * 4), (4, 0)):
        assert (tuning.scan_working_set(t, w, b, n, pipeline_depth=2)
                == tuning.scan_working_set(t, w, b, n) - d1_stage
                + n * t * w * 4)
    # bf16 depth-2 footprint lands exactly on the f32 depth-1 footprint
    # (2·2 + 4 = 4·2 bytes per streamed element).
    assert (tuning.scan_working_set(t, w, 2, n, pipeline_depth=2)
            == tuning.scan_working_set(t, w, 4, n, pipeline_depth=1))


def test_admitted_tile_bf16_never_below_f32():
    """The narrow-dtype admission pin (ISSUE 6 satellite): at equal
    shapes and budget, the tile the tuner admits for a bf16 stream is
    never smaller than the f32 one — at depth 1 (halved streamed term)
    AND at the depth the heuristic would actually run bf16 at (depth 2,
    whose staging term brings it exactly back to the f32 footprint)."""
    budget = 2 ** 21
    for h, w in ((4096, 128), (1024, 64), (128, 128)):
        t32 = tuning.pick_row_tile(h, w, 4, vmem_budget=budget).row_tile
        for depth in (1, 2):
            t16 = tuning.pick_row_tile(h, w, 2, vmem_budget=budget,
                                       pipeline_depth=depth).row_tile
            assert t16 >= t32, (h, w, depth, t16, t32)


def test_depth2_halves_admissible_tile_same_dtype():
    """At a tight budget the staging copies halve the admissible tile
    RELATIVE TO THE SAME dtype at depth 1 — the §12 trade: smaller tile,
    but bulk converts instead of per-row narrow-dtype stores."""
    budget = 2 ** 21
    t16_d1 = tuning.pick_row_tile(4096, 128, 2, vmem_budget=budget,
                                  pipeline_depth=1).row_tile
    t16_d2 = tuning.pick_row_tile(4096, 128, 2, vmem_budget=budget,
                                  pipeline_depth=2).row_tile
    assert t16_d2 == t16_d1 // 2


def test_ref_vjp_helper_matches_autodiff():
    x, wl, wc, wr, lam = _make(4, 8, 12, 2, seed=7)
    dy = jax.random.normal(jax.random.PRNGKey(11), x.shape)

    def f(x, wl, wc, wr, lam):
        return jnp.sum(R.gspn_scan_ref(x, wl, wc, wr, lam) * dy)

    g = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, wl, wc, wr, lam)
    dx, dwl, dwc, dwr, dlam = R.gspn_scan_ref_vjp(x, wl, wc, wr, lam, dy)
    for a, b in zip((dx, dwl, dwc, dwr, dlam), g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
