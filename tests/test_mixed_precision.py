"""Mixed-precision numerics gates (DESIGN.md §10).

Pins the documented error budget: bf16 streamed operands with f32 carries
track the f32 oracle within 1e-2 relative L2 error — forward and
gradients, across all four scan directions, compact-channel mode, the
fused pair op, chunked GSPN prefill, and the sp boundary exchange —
plus the serve-side state-pool narrowing and the train-side f32-master /
dynamic-loss-scale policy.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gspn as G
from repro.kernels import ref as R
from repro.kernels.ops import gspn_scan

TOL = 1e-2     # the §10 documented bf16-vs-f32 bound (relative L2)


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _dir_inputs(b, cp, h, w, seed=0):
    """Direction-stacked inputs in ORIGINAL orientation (f32)."""
    g = b * cp
    nd = len(G.DIRECTIONS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (g, h, w))
    lam = jax.nn.sigmoid(jax.random.normal(ks[1], (nd, g, h, w)))
    logits = jax.random.normal(ks[2], (nd, b, h, w, 3))
    taps = [G._normalize_taps_oriented(logits[i], d, "softmax")
            for i, d in enumerate(G.DIRECTIONS)]
    wl, wc, wr = (jnp.stack([t[k] for t in taps]) for k in range(3))
    return x, wl, wc, wr, lam


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


# ---------------------------------------------------------------------------
# Kernel-level: forward + grads, all four directions, compact mode.
# ---------------------------------------------------------------------------

@pytest.mark.kernels
@pytest.mark.parametrize("impl", ["xla", "multidir"])
@pytest.mark.parametrize("cpw", [1, 3])
def test_bf16_forward_all_directions(impl, cpw):
    """bf16 streams ≤ 1e-2 off the f32 oracle, per direction, through the
    fused multi-direction dispatch (pair fusion included)."""
    args32 = _dir_inputs(2, cpw, 16, 12)
    ref = G.directional_scan(*args32, G.DIRECTIONS, impl="xla")
    out = G.directional_scan(*_cast(args32, jnp.bfloat16), G.DIRECTIONS,
                             impl=impl)
    assert out.dtype == jnp.bfloat16
    for i, d in enumerate(G.DIRECTIONS):
        assert rel_err(out[i], ref[i]) < TOL, d


@pytest.mark.kernels
@pytest.mark.parametrize("impl", ["xla", "multidir"])
def test_bf16_grads_all_directions(impl):
    """Gradients through the custom-vjp adjoint: bf16 within 1e-2 of f32
    for every tensor argument."""
    args32 = _dir_inputs(2, 2, 16, 12, seed=3)

    def loss(fn_impl, dtype):
        def f(*a):
            a = _cast(a, dtype)
            h = G.directional_scan(*a, G.DIRECTIONS, impl=fn_impl)
            return jnp.sum(jnp.sin(h.astype(jnp.float32)))
        return f

    g_ref = jax.grad(loss("xla", jnp.float32), argnums=(0, 4))(*args32)
    g_bf = jax.grad(loss(impl, jnp.bfloat16), argnums=(0, 4))(*args32)
    for a, b in zip(g_bf, g_ref):
        assert rel_err(a, b) < TOL


@pytest.mark.kernels
def test_bf16_carry_dtype_knob():
    """The carry_dtype leg is threadable end-to-end; a bf16 carry stays
    within a looser bound (it exists for experiments, not the policy)."""
    x, wl, wc, wr, lam = _dir_inputs(1, 2, 16, 12)[0:5]
    ref = R.gspn_scan_ref(x, wl[0], wc[0], wr[0], lam[0])
    b = jnp.bfloat16
    out = gspn_scan(x.astype(b), wl[0].astype(b), wc[0].astype(b),
                    wr[0].astype(b), lam[0].astype(b), impl="pallas",
                    carry_dtype="bfloat16")
    assert rel_err(out, ref) < 5e-2


# ---------------------------------------------------------------------------
# Module-level: attention module, seq mixer, chunked prefill.
# ---------------------------------------------------------------------------

@pytest.mark.kernels
@pytest.mark.parametrize("channel_shared", [True, False])
def test_bf16_attention_module(channel_shared):
    cfg = G.GSPNAttentionConfig(dim=16, proxy_dim=4,
                                channel_shared=channel_shared)
    p = G.init_gspn_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 16))
    ref = G.apply_gspn_attention(p, x, cfg)
    cfg_b = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16)
    out = G.apply_gspn_attention(p, x, cfg_b)
    assert out.dtype == x.dtype
    assert rel_err(out, ref) < TOL


@pytest.mark.serve
def test_bf16_chunked_prefill_matches_f32_oneshot():
    """Chaining bf16 prefill chunks stays within the §10 bound of the f32
    one-shot mixer — the cross-chunk boundary rounding included — and the
    f32 chunked path stays EXACT (1e-5), so narrowing is opt-in."""
    scfg = G.GSPNSeqConfig(dim=16, proxy_dim=4, row_width=8, impl="xla")
    p = G.init_gspn_seq_mixer(jax.random.PRNGKey(0), scfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 16))
    ref = G.apply_gspn_seq_mixer(p, x, scfg)

    def chunked(cfg):
        cache = {"prev_row": jnp.zeros((2, 4, 8)),
                 "cur_row": jnp.zeros((2, 4, 8)),
                 "row_state": jnp.zeros((2, 4)),
                 "pos": jnp.zeros((2,), jnp.int32)}
        ys = []
        for lo, hi in ((0, 16), (16, 32), (32, 40)):   # ragged tail
            y, cache = G.gspn_seq_prefill_chunk(p, x[:, lo:hi], cfg, cache)
            ys.append(y)
        return jnp.concatenate(ys, axis=1)

    np.testing.assert_allclose(np.asarray(chunked(scfg)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    scfg_b = dataclasses.replace(scfg, compute_dtype=jnp.bfloat16)
    assert rel_err(chunked(scfg_b), ref) < TOL


# ---------------------------------------------------------------------------
# sp path: bf16 boundary exchange (8 fake CPU devices).
# ---------------------------------------------------------------------------

@pytest.mark.distributed
def test_sp_bf16_boundary_exchange(run_sub):
    """Both exchange strategies with bf16 wire payloads stay within the
    §10 bound of the f32 single-device oracle, forward and gradient."""
    run_sub("""
        from repro.parallel.gspn_sp import gspn_scan_sp
        from repro.kernels import ref as R
        from repro.core import gspn as G

        mesh = make_mesh((8,), ("seq",))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (4, 33, 16))
        lam = jax.random.normal(ks[1], (4, 33, 16))
        wl, wc, wr = G.normalize_taps(
            jax.random.normal(ks[2], (2, 33, 16, 3)))
        ref = R.gspn_scan_ref(x, wl, wc, wr, lam)

        def rel(a, b):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            return np.linalg.norm(a - b) / np.linalg.norm(b)

        for strat in ("ppermute", "allgather"):
            out = jax.jit(lambda *a: gspn_scan_sp(
                *a, mesh=mesh, strategy=strat,
                boundary_dtype=jnp.bfloat16))(x, wl, wc, wr, lam)
            assert rel(out, ref) < 1e-2, (strat, rel(out, ref))

        g_ref = jax.grad(lambda x: jnp.sum(jnp.sin(
            R.gspn_scan_ref(x, wl, wc, wr, lam))))(x)
        g_sp = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(
            gspn_scan_sp(x, wl, wc, wr, lam, mesh=mesh,
                         boundary_dtype=jnp.bfloat16)))))(x)
        assert rel(g_sp, g_ref) < 1e-2, rel(g_sp, g_ref)
    """, timeout=560)


# ---------------------------------------------------------------------------
# Serve: state pool narrowing.
# ---------------------------------------------------------------------------

def _serve_cfg():
    from repro.models.lm import LMConfig
    return LMConfig(
        name="mp-serve", family="dense", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
        prelude=(("gspn", 1),), unit=(("attn", 1),), n_units=1,
        gspn_proxy_dim=2, gspn_row_width=8, remat="none",
        compute_dtype=jnp.float32)


@pytest.mark.serve
def test_state_pool_bf16_halves_bytes_and_survives_ticks():
    """bf16 pool ≥1.9× smaller than f32; KV leaves stay bf16 and the GSPN
    recurrent rows stay at the f32 carry dtype across commit + decode
    updates (the pool must not widen after tick one)."""
    from repro.models.lm import init_lm
    from repro.serve.cache import StateCachePool
    from repro.serve.engine import Request, ServeEngine

    cfg = _serve_cfg()
    pool32 = StateCachePool(cfg, 2, 64, state_dtype=jnp.float32)
    pool16 = StateCachePool(cfg, 2, 64, state_dtype=jnp.bfloat16)
    assert pool32.nbytes / pool16.nbytes >= 1.9

    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, batch_size=2, max_len=64,
                      prefill_chunk=8, state_dtype=jnp.bfloat16)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=np.arange(4 + 8 * i) % 64,
                           max_new_tokens=4))
    res = eng.run()
    assert len(res) == 3
    assert all(len(r.tokens) == 4 for r in res.values())
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.pool.caches):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            kv = path[-1].key in ("k", "v")
            assert leaf.dtype == (jnp.bfloat16 if kv else jnp.float32), \
                (path, leaf.dtype)


@pytest.mark.serve
def test_state_pool_first_token_invariant_to_state_dtype():
    """The first sampled token comes from the (f32-computed) prefill
    logits before any narrowed state is read back, so it must be
    identical under bf16 state."""
    from repro.models.lm import init_lm
    from repro.serve.engine import Request, ServeEngine

    cfg = _serve_cfg()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    firsts = {}
    for sd in (None, jnp.bfloat16):
        eng = ServeEngine(params, cfg, batch_size=2, max_len=64,
                          state_dtype=sd)
        eng.submit(Request(uid=0, prompt=np.arange(12) % 64,
                           max_new_tokens=2))
        firsts[sd] = eng.run()[0].tokens[0]
    assert firsts[None] == firsts[jnp.bfloat16]


# ---------------------------------------------------------------------------
# Train: f32 master copy + dynamic loss scaling.
# ---------------------------------------------------------------------------

def _train_fixture(ls):
    from repro.configs.base import with_precision
    from repro.models.lm import LMConfig, init_lm
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import build_train_step, loss_scale_init
    from repro.optim.adamw import adamw_init

    cfg = LMConfig(name="mp-train", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                   unit=(("gspn", 1),), n_units=1, gspn_proxy_dim=2,
                   gspn_row_width=4, remat="none")
    cfg = with_precision(cfg, "bf16")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    step = build_train_step(cfg, AdamWConfig(), master_weights=True,
                            loss_scaling=ls)
    state = {"params": params,
             "opt": adamw_init(AdamWConfig(), params),
             "master": jax.tree.map(lambda p: p.astype(jnp.float32),
                                    params),
             "loss_scale": loss_scale_init(ls)}
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32) + 3,
             "labels": jnp.ones((2, 16), jnp.int32)}
    return step, state, batch


def test_master_copy_update_and_scale_growth():
    from repro.train.step import LossScaleConfig
    ls = LossScaleConfig(init_scale=2.0 ** 10, growth_interval=2)
    step, state, batch = _train_fixture(ls)
    s1, m1 = jax.jit(step)(state, batch)
    assert np.isfinite(float(m1["loss"]))
    assert float(m1["grads_finite"]) == 1.0
    assert jax.tree.leaves(s1["master"])[0].dtype == jnp.float32
    assert jax.tree.leaves(s1["params"])[0].dtype == jnp.bfloat16
    # working copy is the master rounded to bf16
    for p, mast in zip(jax.tree.leaves(s1["params"]),
                       jax.tree.leaves(s1["master"])):
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(mast.astype(jnp.bfloat16)))
    assert int(s1["loss_scale"]["good_steps"]) == 1
    s2, _ = jax.jit(step)(s1, batch)
    # growth_interval=2 consecutive finite steps → scale doubles
    assert float(s2["loss_scale"]["scale"]) == 2.0 ** 11


def test_loss_scale_overflow_skips_update_and_backs_off():
    from repro.train.step import LossScaleConfig
    # 2^127 is finite in f32 but scale·loss overflows → inf grads →
    # the step must be skipped and the scale halved.
    ls = LossScaleConfig(init_scale=2.0 ** 127)
    step, state, batch = _train_fixture(ls)
    s1, m1 = jax.jit(step)(state, batch)
    assert float(m1["grads_finite"]) == 0.0
    for new, old in zip(jax.tree.leaves(s1["master"]),
                        jax.tree.leaves(state["master"])):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    assert float(s1["loss_scale"]["scale"]) == 2.0 ** 126
    assert int(s1["loss_scale"]["good_steps"]) == 0


def test_loss_scale_transition_unit():
    from repro.train.step import (LossScaleConfig, loss_scale_init,
                                  loss_scale_update, tree_all_finite)
    ls = LossScaleConfig(init_scale=4.0, growth_interval=3, min_scale=1.0)
    s = loss_scale_init(ls)
    s = loss_scale_update(ls, s, jnp.asarray(False))
    assert float(s["scale"]) == 2.0 and int(s["good_steps"]) == 0
    s = loss_scale_update(ls, s, jnp.asarray(False))
    s = loss_scale_update(ls, s, jnp.asarray(False))
    assert float(s["scale"]) == 1.0          # clamped at min_scale
    for _ in range(3):
        s = loss_scale_update(ls, s, jnp.asarray(True))
    assert float(s["scale"]) == 2.0          # grew after the interval
    assert not bool(tree_all_finite({"a": jnp.array([1.0, np.inf])}))
    assert bool(tree_all_finite({"a": jnp.array([1.0, 2.0])}))
