"""Fused multi-direction dispatch: equivalence of the pair-fused path
against the per-direction reference (all four directions, compact channel
mode, non-square grids), gradients through the pair custom_vjp, the
dispatch-count guarantee (≤2 pallas_calls for a 4-direction pass), and the
single-launch quad kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gspn as G
from repro.core.gspn import _from_canonical, _to_canonical
from repro.kernels import gspn_multidir as MK
from repro.kernels import gspn_scan as GS
from repro.kernels import ref as R
from repro.kernels.ops import gspn_scan_pair

pytestmark = pytest.mark.kernels

DIRECTIONS = G.DIRECTIONS


def _make_dir_inputs(gd, h, w, gw, seed=0):
    """x/lam plus per-direction taps in ORIGINAL orientation (the
    directional_scan multi convention)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (gd, h, w))
    lam = jax.random.normal(ks[1], (len(DIRECTIONS), gd, h, w))
    logits = jax.random.normal(ks[2], (len(DIRECTIONS), gw, h, w, 3))
    wls, wcs, wrs = [], [], []
    for d_idx, d in enumerate(DIRECTIONS):
        wl, wc, wr = G._normalize_taps_oriented(logits[d_idx], d, "softmax")
        wls.append(wl)
        wcs.append(wc)
        wrs.append(wr)
    return x, jnp.stack(wls), jnp.stack(wcs), jnp.stack(wrs), lam, logits


def _ref_direction(x, wl, wc, wr, lam, d):
    """Per-direction oracle: orient, lax.scan reference, orient back."""
    h = R.gspn_scan_ref(
        _to_canonical(x, d), _to_canonical(wl, d), _to_canonical(wc, d),
        _to_canonical(wr, d), _to_canonical(lam, d))
    return _from_canonical(h, d)


@pytest.mark.parametrize("shape,cpw", [((2, 16, 16), 1),    # square
                                       ((4, 8, 24), 2),     # non-square, compact
                                       ((6, 32, 16), 3)])   # H > W, compact
@pytest.mark.parametrize("impl", ["xla", "multidir"])
def test_multi_directional_scan_matches_per_direction(shape, cpw, impl):
    gd, h, w = shape
    x, wl, wc, wr, lam, _ = _make_dir_inputs(gd, h, w, gd // cpw)
    out = G.directional_scan(x, wl, wc, wr, lam, DIRECTIONS, impl=impl)
    assert out.shape == (len(DIRECTIONS), gd, h, w)
    for d_idx, d in enumerate(DIRECTIONS):
        ref = _ref_direction(x, wl[d_idx], wc[d_idx], wr[d_idx],
                             lam[d_idx], d)
        np.testing.assert_allclose(np.asarray(out[d_idx]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"direction {d}")


@pytest.mark.parametrize("impl", ["xla", "multidir"])
def test_multi_directional_scan_gradients(impl):
    gd, h, w, cpw = 4, 8, 12, 2
    x, _, _, _, lam, logits = _make_dir_inputs(gd, h, w, gd // cpw, seed=3)

    def loss(x, logits, lam, impl):
        wls, wcs, wrs = [], [], []
        for d_idx, d in enumerate(DIRECTIONS):
            a, b_, c = G._normalize_taps_oriented(logits[d_idx], d, "softmax")
            wls.append(a)
            wcs.append(b_)
            wrs.append(c)
        out = G.directional_scan(x, jnp.stack(wls), jnp.stack(wcs),
                                 jnp.stack(wrs), lam, DIRECTIONS, impl=impl)
        return jnp.sum(jnp.sin(out))

    def loss_ref(x, logits, lam):
        acc = 0.0
        for d_idx, d in enumerate(DIRECTIONS):
            a, b_, c = G._normalize_taps_oriented(logits[d_idx], d, "softmax")
            acc = acc + jnp.sum(jnp.sin(
                _ref_direction(x, a, b_, c, lam[d_idx], d)))
        return acc

    g_got = jax.grad(loss, argnums=(0, 1, 2))(x, logits, lam, impl)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, logits, lam)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_four_direction_pass_issues_at_most_two_pallas_calls(monkeypatch):
    # Every launch goes through gspn_scan.pallas_call, which builds the
    # Mosaic and the interpreter variant of ONE launch (lowering keeps
    # one), so launches are counted there rather than at pl.pallas_call.
    calls = []
    real = GS.pallas_call

    def counting(*args, **kwargs):
        calls.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(GS, "pallas_call", counting)
    gd, h, w = 2, 8, 16
    x, wl, wc, wr, lam, _ = _make_dir_inputs(gd, h, w, gd)
    out = G.directional_scan(x, wl, wc, wr, lam, DIRECTIONS, impl="multidir")
    jax.block_until_ready(out)
    assert len(calls) == 2, f"expected 2 fused dispatches, saw {calls}"


def test_pair_op_chunked_matches_blockdiag():
    gd, h, w, chunk = 4, 16, 20, 4
    x, wl, wc, wr, lam, _ = _make_dir_inputs(gd, h, w, 2, seed=5)
    out = gspn_scan_pair(x, wl[:2], wc[:2], wr[:2], lam[:2],
                         chunk=chunk, impl="multidir")
    ref_tb = R.gspn_scan_chunked_ref(x, wl[0], wc[0], wr[0], lam[0], chunk)
    ref_bt = jnp.flip(R.gspn_scan_chunked_ref(
        jnp.flip(x, 1), jnp.flip(wl[1], 1), jnp.flip(wc[1], 1),
        jnp.flip(wr[1], 1), jnp.flip(lam[1], 1), chunk), 1)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref_tb),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref_bt),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cpw", [1, 2])
def test_quad_single_launch_matches_per_direction(cpw):
    gd, n = 2 * cpw, 16
    x, wl, wc, wr, lam, _ = _make_dir_inputs(gd, n, n, gd // cpw, seed=7)
    T = lambda a: jnp.swapaxes(a, -1, -2)
    # quad convention: entries 2/3 (lr/rl) in transposed geometry.
    taps4 = {
        "wl": jnp.stack([wl[0], wl[1], T(wl[2]), T(wl[3])]),
        "wc": jnp.stack([wc[0], wc[1], T(wc[2]), T(wc[3])]),
        "wr": jnp.stack([wr[0], wr[1], T(wr[2]), T(wr[3])]),
    }
    lam4 = jnp.stack([lam[0], lam[1], T(lam[2]), T(lam[3])])
    out = MK.gspn_scan_quad_pallas(x, taps4, lam4, channels_per_weight=cpw,
                                   row_tile=4)
    for d_idx, d in enumerate(DIRECTIONS):
        got = out[d_idx] if d_idx < 2 else T(out[d_idx])
        ref = _ref_direction(x, wl[d_idx], wc[d_idx], wr[d_idx],
                             lam[d_idx], d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"direction {d}")


def test_attention_multidir_equals_xla_including_grads():
    """impl='multidir' end-to-end through the attention module."""
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 24, 32))
    cfgs = {impl: G.GSPNAttentionConfig(dim=32, proxy_dim=4, impl=impl)
            for impl in ("multidir", "xla")}
    params = G.init_gspn_attention(jax.random.PRNGKey(1), cfgs["xla"])
    ys, gs = {}, {}
    for impl, cfg in cfgs.items():
        ys[impl] = G.apply_gspn_attention(params, img, cfg)
        gs[impl] = jax.grad(lambda p: jnp.sum(jnp.sin(
            G.apply_gspn_attention(p, img, cfg))))(params)
    np.testing.assert_allclose(np.asarray(ys["multidir"]),
                               np.asarray(ys["xla"]), rtol=2e-5, atol=2e-5)
    for k in gs["xla"]:
        np.testing.assert_allclose(np.asarray(gs["multidir"][k]),
                                   np.asarray(gs["xla"][k]),
                                   rtol=1e-4, atol=1e-5)
