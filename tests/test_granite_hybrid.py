"""Granite-4.0-H (Mamba-2 + NoPE attention hybrid) on the serving path:
the program against the plain reference of ``bench/refs/granite_hybrid.py``
on seeded weights at ``reduced()`` size, Mamba-2's gated norm in closed
form, the configuration at its published sizes, and the pool and engine
holding SSM state, conv tail and KV side by side."""

from __future__ import annotations

import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from refs import granite_hybrid as ref  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.base import get_arch, with_precision  # noqa: E402
from repro.kernels import chunk_attention as CA  # noqa: E402
from repro.models import attention, lm  # noqa: E402
from repro.models import ssm  # noqa: E402
from repro.serve.cache import StateCachePool, narrow_state  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

# The program in float32 against the float32 reference: both run every
# matmul at ``highest``, so the gap is float32 round-off of two different
# evaluation orders (chunked SSD against a per-token recurrence, blocked
# against dense attention).  Logits read about 0.1 here; 2e-5 absolute is
# about 2e-4 of them, two orders above the gaps read (~1e-7), and a
# dropped SSM state or conv tail moves them by 1e-3 and more.
F32_ATOL = 2e-5
PROMPT, DECODE = 30, 7


def _cfg():
    return with_precision(get_arch("granite-4.0-h-micro").reduced(), "f32")


def _consts(cfg):
    types = []
    for _where, kind, n in cfg.stages():
        types += ["attention" if kind == "attn" else "mamba"] * n
    return ref.consts({
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "mamba_n_heads": 2 * cfg.d_model // cfg.ssm_head_dim,
        "mamba_d_head": cfg.ssm_head_dim, "mamba_d_state": cfg.ssm_state,
        "mamba_n_groups": 1, "mamba_d_conv": 4,
        "rms_norm_eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embed_scale,
        "residual_multiplier": cfg.residual_scale,
        "attention_multiplier": cfg.attn_scale,
        "logits_scaling": cfg.logit_divisor, "layer_types": types})


def _params(cfg, seed=0):
    """The program's init, with Mamba-2's per-head scalars drawn as Mamba-2
    draws them (A in [1, 16], dt log-uniform in [0.001, 0.1])."""
    params = lm.init_lm(jax.random.PRNGKey(seed), cfg)
    stages = dict(params["stages"])
    for i, (name, st) in enumerate(sorted(stages.items())):
        if "mix" not in st:
            continue
        k1, k2 = jax.random.split(jax.random.PRNGKey(100 + i))
        a = jax.random.uniform(k1, st["mix"]["a_log"].shape, minval=1.0,
                               maxval=16.0)
        dt = jnp.exp(jax.random.uniform(k2, st["mix"]["dt_bias"].shape,
                                        minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        mix = dict(st["mix"], a_log=jnp.log(a),
                   dt_bias=dt + jnp.log(-jnp.expm1(-dt)))
        stages[name] = dict(st, mix=mix)
    return dict(params, stages=stages)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = _params(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT + DECODE),
                              0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, _consts(cfg), toks)
    return cfg, params, toks, want


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def test_full_forward_matches_reference(setup):
    cfg, params, toks, want = setup
    with jax.default_matmul_precision("highest"):
        got, _ = lm.apply_lm(params, cfg, toks)
    assert _gap(got, want) <= F32_ATOL


def _decode_rest(params, cfg, toks, caches, out):
    for t in range(PROMPT, PROMPT + DECODE - 1):
        logits, caches = lm.lm_decode_step(params, cfg, toks[:, t:t + 1],
                                           caches)
        out.append(logits)
    return jnp.concatenate(out, axis=1)


def test_one_shot_prefill_then_decode_matches_reference(setup):
    cfg, params, toks, want = setup
    with jax.default_matmul_precision("highest"):
        logits, caches, _ = lm.lm_prefill(params, cfg, toks[:, :PROMPT], 64)
        got = _decode_rest(params, cfg, toks, caches, [logits])
    assert _gap(got, want[:, :-1]) <= F32_ATOL


def _cases(chunks, ids):
    """Each chunk length on the XLA sweep (the ids as they were) and on the
    fused kernel (``-pallas``)."""
    return ([pytest.param(c, "xla", id=i) for c, i in zip(chunks, ids)]
            + [pytest.param(c, "pallas", id=f"{i}-pallas")
               for c, i in zip(chunks, ids)])


def _chunk_path(path, monkeypatch):
    """Run the chunks' attention on ``path``.  Off the TPU the dispatch
    takes the XLA sweep; for ``pallas`` the test puts the fused kernel, in
    interpret mode, in the sweep's place, as a TPU would run it.  Returns a
    check that the kernel was the dispatch's choice for these shapes."""
    if path == "pallas":
        monkeypatch.setattr(
            attention, "_chunk_sweep",
            lambda q, k, v, off, block_k: CA.chunk_attention(
                q, k, v, off, interpret=True))
    before = _count("chunk_attention_pallas_total")
    return lambda: _count("chunk_attention_pallas_total") > before


def _count(name):
    return getattr(obs.REGISTRY.get(name), "value", 0)


@pytest.mark.parametrize("chunk,path", _cases(
    [2, 5, 8, 13], ["under-conv-tail", "ragged", "ssd-chunk",
                    "ragged-long"]))
def test_chunked_prefill_then_decode_matches_reference(setup, chunk, path,
                                                       monkeypatch):
    """Chunks shorter than the conv tail (conv_k - 1 = 3), chunks that do
    not divide the prompt (a ragged last chunk), and chunks that straddle
    the SSD chunk (8): every chunk resumes SSM state and conv tail, with
    its attention on the XLA sweep or the fused kernel."""
    cfg, params, toks, want = setup
    took_kernel = _chunk_path(path, monkeypatch)
    with jax.default_matmul_precision("highest"):
        caches = lm.init_lm_cache(cfg, 2, 64)
        out, off = [], 0
        while off < PROMPT:
            end = min(off + chunk, PROMPT)
            logits, caches = lm.lm_prefill_chunk(params, cfg,
                                                 toks[:, off:end], caches,
                                                 off)
            out.append(logits)
            off = end
        got = _decode_rest(params, cfg, toks, caches, out)
    assert took_kernel()
    assert _gap(got, want[:, :-1]) <= F32_ATOL


@pytest.mark.parametrize("chunk,path", _cases(
    [2, 8, 13], ["under-conv-tail", "ssd-chunk", "ragged-long"]))
def test_padded_chunks_then_decode_match_reference(setup, chunk, path,
                                                   monkeypatch):
    """Every chunk padded to ``chunk`` rows with only the prompt's tokens
    counted (``n_valid``), as the engine runs a ragged last chunk: the
    padding leaves SSM state, conv tail and KV length as the tokens left
    them, and the logits are those of the last token, with the chunks'
    attention on the XLA sweep or the fused kernel."""
    cfg, params, toks, want = setup
    assert lm.supports_padded_chunks(cfg)
    took_kernel = _chunk_path(path, monkeypatch)
    with jax.default_matmul_precision("highest"):
        caches = lm.init_lm_cache(cfg, 2, 64)
        off = 0
        while off < PROMPT:
            n = min(chunk, PROMPT - off)
            part = jnp.pad(toks[:, off:off + n], ((0, 0), (0, chunk - n)),
                           constant_values=1)
            logits, caches = lm.lm_prefill_chunk(
                params, cfg, part, caches, off, n_valid=jnp.int32(n))
            off += n
        assert logits.shape == (2, 1, cfg.vocab)
        got = _decode_rest(params, cfg, toks, caches, [logits])
    assert took_kernel()
    assert _gap(got, want[:, PROMPT - 1:-1]) <= F32_ATOL


@pytest.mark.parametrize("arch,kernels", [("granite-4.0-h-micro", 4),
                                          ("qwen2-1.5b-gspn", 0)])
def test_chunk_program_counts_its_kernel_attentions(arch, kernels):
    """Tracing a 2048-token chunk over a 17,408-row cache (serve_doc's
    program) engages the fused kernel once per attention layer, and the
    GSPN model (serve_chat's, no attention layer) engages no chunk
    attention at all."""
    cfg = with_precision(get_arch(arch).full(), "bf16")
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: lm.init_lm_cache(cfg, 1, 17408))
    before = (_count("chunk_attention_pallas_total"),
              _count("chunk_attention_xla_total"))
    jax.eval_shape(lambda p, t, c, o: lm.lm_prefill_chunk(p, cfg, t, c, o),
                   params, jax.ShapeDtypeStruct((1, 2048), jnp.int32),
                   caches, jax.ShapeDtypeStruct((), jnp.int32))
    assert _count("chunk_attention_pallas_total") == before[0] + kernels
    assert _count("chunk_attention_xla_total") == before[1]


def test_a_chunk_that_drops_the_ssm_state_is_caught(setup):
    """The tolerance is tight enough to see a chunk boundary that forgets
    the SSM state."""
    cfg, params, toks, want = setup
    with jax.default_matmul_precision("highest"):
        caches = lm.init_lm_cache(cfg, 2, 64)
        out = []
        for off in range(0, PROMPT, 10):
            if off:
                caches = {k: dict(v, ssm=jnp.zeros_like(v["ssm"]))
                          if "ssm" in v else v for k, v in caches.items()}
            logits, caches = lm.lm_prefill_chunk(
                params, cfg, toks[:, off:off + 10], caches, off)
            out.append(logits)
        got = jnp.concatenate(out, axis=1)
    assert _gap(got, want[:, :PROMPT]) > 50 * F32_ATOL


def test_mamba2_gated_norm_is_the_published_order():
    """RMSNorm(y * silu(z)) * g (Mamba-2's RMSNormGated, norm_before_gate
    False), checked against that closed form on a one-token decode step
    from zero state, where y = (C.B) dt x + D x."""
    cfg = ssm.Mamba2Config(dim=8, d_state=4, head_dim=4, norm_eps=1e-5)
    p = ssm.init_mamba2(jax.random.PRNGKey(0), cfg)
    p["norm"]["scale"] = jnp.linspace(0.5, 1.5, cfg.d_inner)
    p["d_skip"] = jnp.linspace(0.5, 2.0, cfg.n_heads)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.dim))
    pol = lm.DTypePolicy(jnp.float32, jnp.float32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _ = ssm.apply_mamba2_decode(
            p, x, cfg, ssm.init_mamba2_cache(2, cfg), pol)
        proj = x @ p["in_proj"]
        di, ns = cfg.d_inner, cfg.d_state
        z, xbc = proj[..., :di], proj[..., di:2 * di + 2 * ns]
        dt = jax.nn.softplus(proj[..., 2 * di + 2 * ns:] + p["dt_bias"])
        w, b = p["conv"]["w"][:, 0], p["conv"]["b"]
        xbc = jax.nn.silu(xbc * w[-1] + b)                # zero conv tail
        xs = xbc[..., :di].reshape(2, 1, cfg.n_heads, cfg.head_dim)
        cb = jnp.sum(xbc[..., di:di + ns] * xbc[..., di + ns:], -1)
        y = ((cb[..., None] * dt + p["d_skip"])[..., None] * xs
             ).reshape(2, 1, di)
        g = y * jax.nn.silu(z)
        g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
        want = (g * p["norm"]["scale"]) @ p["out_proj"]
    assert _gap(out, want) <= 1e-5


def test_full_config_follows_layer_types():
    cfg = get_arch("granite-4.0-h-micro").full()
    kinds = [k for _w, k, n in cfg.stages() for _ in range(n)]
    assert len(kinds) == 40 == cfg.n_layers
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert set(kinds) == {"attn", "mamba_ffn"}
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab, cfg.ssm_state, cfg.ssm_head_dim, cfg.gla_chunk) == \
        (2048, 32, 8, 64, 8192, 100352, 128, 64, 256)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_divisor,
            cfg.attn_scale, cfg.rope, cfg.norm_eps) == \
        (12.0, 0.22, 8.0, 0.015625, False, 1e-5)
    shapes = jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(a.size) for a in jax.tree.leaves(shapes))
    assert abs(n - 3.19e9) <= 0.01 * 3.19e9, n
    assert lm.supports_chunked_prefill(cfg)
    assert lm.prefill_chunk_alignment(cfg) == 256


# ---------------------------------------------------------------------------
# Two kinds of state in one pool.
# ---------------------------------------------------------------------------

def _kinds(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out.setdefault(path[-1].key, []).append(leaf)
    return out


def test_pool_slot_reuse_is_clean_for_ssm_conv_and_kv():
    """A freed slot's SSM state, conv tail and KV are all overwritten by
    the next commit: nothing of the previous occupant survives."""
    cfg = _cfg()
    pool = StateCachePool(cfg, 3, 32)
    pool.caches = jax.tree.map(lambda a: jnp.full_like(a, 7), pool.caches)
    slot = pool.alloc()
    pool.free(slot)
    assert pool.alloc() == slot
    new = lm.init_lm_cache(cfg, 1, 32)
    new = jax.tree.map(lambda a: jnp.full_like(a, 3), new)
    pool.commit(slot, new)
    kinds = _kinds(pool.caches)
    assert {"ssm", "conv", "k", "v"} <= set(kinds)
    for name in ("ssm", "conv", "k", "v", "length"):
        for leaf in kinds[name]:
            leaf = jnp.moveaxis(leaf, 1, 0)          # prelude: (n, B, ...)
            assert bool(jnp.all(leaf[slot] == 3)), name
            others = [s for s in range(3) if s != slot]
            assert bool(jnp.all(leaf[jnp.array(others)] == 7)), name
    by = pool.bytes_by_kind()
    assert by["ssm"] > 0 and by["conv"] > 0 and by["kv"] > 0


def test_narrow_state_keeps_ssm_f32_under_bf16():
    cfg = _cfg()
    pool = StateCachePool(cfg, 2, 32, state_dtype=jnp.bfloat16)
    kinds = _kinds(pool.caches)
    for leaf in kinds["ssm"] + kinds["conv"]:
        assert leaf.dtype == jnp.float32
    for leaf in kinds["k"] + kinds["v"]:
        assert leaf.dtype == jnp.bfloat16
    assert all(a.dtype == jnp.int32 for a in kinds["length"])
    again = _kinds(narrow_state(pool.caches, jnp.bfloat16))
    assert all(a.dtype == jnp.float32 for a in again["ssm"])


@pytest.mark.serve
def test_engine_serves_hybrid_through_chunked_prefill():
    """The engine takes the chunked path for the hybrid (nothing keyed on
    the model's name) and streams exactly what one-shot prefill plus
    decode gives."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (40, 9, 27, 16)]

    def serve(chunk):
        eng = ServeEngine(params, cfg, batch_size=2, max_len=64,
                          prefill_chunk=chunk)
        streamed = {}
        eng.stream = lambda uid, tok: streamed.setdefault(uid, []).append(tok)
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr, max_new_tokens=6))
        res = eng.run()
        assert {u: r.tokens for u, r in res.items()} == streamed
        return streamed, eng

    one_shot, eng0 = serve(0)
    chunked, eng = serve(8)
    assert eng0.prefill_chunk == 0
    assert eng.prefill_chunk == 8 and eng.pad_chunks
    # every prompt in chunks of 8, the last padded: 5 + 2 + 4 + 2
    assert eng.metrics["prefill_chunks"] == 13
    assert chunked == one_shot


@pytest.mark.serve
def test_engine_score_replays_served_tokens():
    """``score`` feeds served tokens back through the engine's prefill and
    decode programs: its logits are the one-shot forward's at the served
    positions, and each served token is their argmax (greedy)."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (19, 8)]
    eng = ServeEngine(params, cfg, batch_size=3, max_len=64, prefill_chunk=8)
    for i, pr in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=pr, max_new_tokens=5 + i))
    res = eng.run()
    cases = [(pr, res[i].tokens) for i, pr in enumerate(prompts)]
    rows = eng.score(cases)
    assert eng.idle
    assert sorted(eng.pool.alloc() for _ in range(3)) == [0, 1, 2]
    for (pr, toks), got in zip(cases, rows):
        assert got.shape == (len(toks), cfg.vocab)
        assert list(np.argmax(got, -1)) == list(toks)
        seq = jnp.asarray(np.concatenate([pr, toks[:-1]]))[None]
        want, _ = lm.apply_lm(params, cfg, seq)
        assert _gap(got, want[0, len(pr) - 1:]) <= F32_ATOL
