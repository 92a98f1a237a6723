"""Continuous-batching engine: chunked-prefill equivalence, scheduler edge
cases, and the paged state-cache pool (DESIGN.md §9 invariants).

All configs are tiny (d_model 32, vocab 64) so the whole module stays
cheap inside the tier-1 ``pytest -q`` gate; ``pytest -m serve`` selects
just this surface.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lm import (LMConfig, init_lm, init_lm_cache,
                             lm_decode_step, lm_prefill, lm_prefill_chunk,
                             prefill_chunk_alignment,
                             supports_chunked_prefill)
from repro.serve.cache import StateCachePool
from repro.serve.engine import Request, ServeEngine

pytestmark = pytest.mark.serve


def _gspn_cfg(**kw):
    """gspn prelude + attn unit: exercises both chunked-prefill paths and
    both cache batch-axis layouts."""
    base = dict(name="g", family="gspn", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, d_ff=64, vocab=64,
                prelude=(("gspn", 1),), unit=(("attn", 1),), n_units=1,
                gspn_proxy_dim=4, gspn_row_width=8, remat="none",
                compute_dtype=jnp.float32)
    base.update(kw)
    return LMConfig(**base)


def _tree_close(a, b, atol):
    for ka, kb in zip(sorted(a), sorted(b)):
        assert ka == kb
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x, np.float32), np.asarray(y, np.float32),
                atol=atol, rtol=0), a[ka], b[kb])


# ---------------------------------------------------------------------------
# Chunked prefill == one-shot prefill (the §9 headline invariant).
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_one_shot():
    """Logits AND every cache leaf must agree to 1e-5 when the prompt is
    consumed in chunks (incl. a ragged tail) vs in one shot."""
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    max_len, chunk = 64, 16
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, 43), jnp.int32)[None]

    logits1, caches1, _ = lm_prefill(p, cfg, prompt, max_len)

    caches = init_lm_cache(cfg, 1, max_len)
    outs = []
    for off in range(0, prompt.shape[1], chunk):
        lg, caches = lm_prefill_chunk(p, cfg, prompt[:, off:off + chunk],
                                      caches, off)
        outs.append(lg)
    logits2 = jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2),
                               atol=1e-5, rtol=0)
    _tree_close(caches1, caches, 1e-5)

    # and decode continues identically from either cache
    tok = jnp.argmax(logits1[:, -1:], -1).astype(jnp.int32)
    l1, _ = lm_decode_step(p, cfg, tok, caches1)
    l2, _ = lm_decode_step(p, cfg, tok, caches)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-5, rtol=0)


def test_engine_chunked_equals_one_shot_tokens():
    """Greedy engine output is invariant to the prefill chunking."""
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, n) for n in (40, 7, 24)]

    def run(chunk):
        eng = ServeEngine(p, cfg, batch_size=2, max_len=96,
                          prefill_chunk=chunk)
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr, max_new_tokens=5))
        res = eng.run()
        return {u: res[u].tokens for u in res}, eng

    one_shot, _ = run(0)
    chunked, eng = run(16)
    assert one_shot == chunked
    # the 40- and 24-token prompts actually went through the chunked path
    assert eng.metrics["prefill_chunks"] >= 3 + 2


def test_chunk_support_matrix():
    assert supports_chunked_prefill(_gspn_cfg())
    assert prefill_chunk_alignment(_gspn_cfg()) == 8
    # Mamba-2 resumes from its state and conv tail, and aligns chunks to
    # its scan chunk; xLSTM has no incremental prefill; row_width=0
    # defeats a fixed fold
    assert supports_chunked_prefill(_gspn_cfg(unit=(("mamba", 1),)))
    assert prefill_chunk_alignment(
        _gspn_cfg(unit=(("mamba", 1),), gla_chunk=16)) == 16
    assert not supports_chunked_prefill(_gspn_cfg(unit=(("mlstm", 1),)))
    assert not supports_chunked_prefill(_gspn_cfg(gspn_row_width=0))
    eng = ServeEngine(init_lm(jax.random.PRNGKey(0), _gspn_cfg()),
                      _gspn_cfg(), batch_size=1, max_len=64,
                      prefill_chunk=13)
    assert eng.prefill_chunk == 8          # snapped down to the fold width
    with pytest.raises(ValueError):        # oversized prompts rejected at
        eng.submit(Request(uid=0,          # submit, not silently clamped
                           prompt=np.arange(65) % 64, max_new_tokens=1))
    with pytest.raises(ValueError):        # prompt + generated must fit too
        eng.submit(Request(uid=0,
                           prompt=np.arange(60) % 64, max_new_tokens=10))


# ---------------------------------------------------------------------------
# Scheduler edge cases.
# ---------------------------------------------------------------------------

def test_admission_under_full_batch():
    """More requests than slots: the pool backpressures, everything still
    completes, and concurrency never exceeds the slot count."""
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(p, cfg, batch_size=2, max_len=96, prefill_chunk=16)
    for i in range(5):
        # one prompt length => one jit variant; the edge case under test
        # is admission order, not shapes
        eng.submit(Request(uid=i, prompt=(np.arange(12) + i) % 64,
                           max_new_tokens=4))
    res = eng.run()
    assert sorted(res) == list(range(5))
    assert eng.metrics["queue_depth_max"] >= 3   # requests actually waited
    assert eng.pool.n_free == 2                  # all slots returned
    assert eng.pool.n_used == 0


def test_sjf_admits_shortest_prompt_first():
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)

    def order(sched):
        eng = ServeEngine(p, cfg, batch_size=1, max_len=96,
                          prefill_chunk=16, scheduler=sched)
        for i, n in enumerate([40, 6, 24]):
            eng.submit(Request(uid=i, prompt=np.arange(n) % 64,
                               max_new_tokens=3))
        eng.run()
        return list(eng.metrics["admission_order"])

    assert order("fcfs") == [0, 1, 2]
    assert order("sjf") == [1, 2, 0]


def test_retirement_eos_vs_max_tokens():
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(12) % 64

    ref = ServeEngine(p, cfg, batch_size=1, max_len=96, prefill_chunk=16)
    ref.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    eos = ref.run()[0].tokens[2]      # 3rd generated token as synthetic EOS

    eng = ServeEngine(p, cfg, batch_size=1, max_len=96, prefill_chunk=16,
                      eos_id=eos)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    eng.submit(Request(uid=1, prompt=prompt[:5], max_new_tokens=2))
    res = eng.run()
    assert res[0].finish_reason == "eos"
    assert res[0].tokens[-1] == eos and len(res[0].tokens) <= 3
    assert res[1].finish_reason == "length"
    assert len(res[1].tokens) == 2


def test_request_metrics_and_streaming():
    """One engine run pins both the per-request metrics fields and the
    streaming callback (every token, in generation order)."""
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    seen = {}
    eng = ServeEngine(p, cfg, batch_size=2, max_len=96, prefill_chunk=16,
                      stream=lambda uid, tok: seen.setdefault(uid, [])
                      .append(tok))
    for i in range(3):
        eng.submit(Request(uid=i, prompt=np.arange(20) % 64,
                           max_new_tokens=4))
    res = eng.run()
    assert {u: r.tokens for u, r in res.items()} == seen
    for r in res.values():
        assert r.ttft > 0.0
        assert r.queue_delay >= 0.0
        assert r.prefill_chunks == 2          # 20 tokens / 16-chunk
        assert len(r.itl) == len(r.tokens) - 1
        assert r.finish_reason == "length"

    # with on_finish set, results are delivered, not retained (engine
    # state stays bounded for long-running servers); reuses the jits
    eng.reset()
    delivered = []
    eng.on_finish = delivered.append
    eng.submit(Request(uid=7, prompt=np.arange(20) % 64, max_new_tokens=4))
    assert eng.run() == {}
    assert [r.uid for r in delivered] == [7]
    assert delivered[0].tokens == res[0].tokens    # same prompt, same model


# ---------------------------------------------------------------------------
# State-cache pool.
# ---------------------------------------------------------------------------

def test_cache_pool_alloc_free_reuse():
    cfg = _gspn_cfg()
    pool = StateCachePool(cfg, 2, 32)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1}
    assert pool.alloc() is None               # exhaustion, not an exception
    pool.free(a)
    assert pool.n_free == 1
    assert pool.alloc() == a                  # LIFO reuse of the freed page
    pool.free(b)
    with pytest.raises(ValueError):
        pool.free(b)                          # double-free is a bug


def test_cache_pool_commit_writes_only_its_slot():
    cfg = _gspn_cfg()
    pool = StateCachePool(cfg, 4, 32)
    pool.caches = jax.tree.map(lambda a: jnp.full_like(a, 7), pool.caches)
    new = jax.tree.map(lambda a: jnp.full_like(a, -3),
                       init_lm_cache(cfg, 1, 32))
    slot = pool.alloc()
    pool.commit(slot, new)
    prelude_keys = {f"s{si}_{kind}" for si, (w, kind, n)
                    in enumerate(cfg.stages()) if w == "prelude"}
    for key, sub in pool.caches.items():
        axis = 1 if key in prelude_keys else 2
        for leaf in jax.tree.leaves(sub):
            got = np.moveaxis(np.asarray(leaf, np.float32), axis, 0)
            np.testing.assert_array_equal(got[slot], -3.0)
            others = [s for s in range(4) if s != slot]
            np.testing.assert_array_equal(got[others], 7.0)


def test_cache_pool_reuse_after_free_is_clean():
    """A request decoded in a reused slot must match a fresh engine —
    chunked prefill must fully overwrite the previous occupant's page."""
    cfg = _gspn_cfg()
    p = init_lm(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(23) % 64

    fresh = ServeEngine(p, cfg, batch_size=1, max_len=96, prefill_chunk=16)
    fresh.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
    expect = fresh.run()[0].tokens

    eng = ServeEngine(p, cfg, batch_size=1, max_len=96, prefill_chunk=16)
    eng.submit(Request(uid=0, prompt=np.arange(40) % 64, max_new_tokens=9))
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=5))
    assert eng.run()[1].tokens == expect
