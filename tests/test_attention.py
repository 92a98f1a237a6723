"""Attention: flash custom_vjp vs full reference, decode vs full,
RoPE/M-RoPE consistency, and the fused chunked-prefill kernel (in
interpret mode) against the XLA sweep and the full reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import chunk_attention as CA
from repro.models import attention
from repro.models.attention import (AttentionConfig, apply_attention,
                                    apply_attention_decode,
                                    chunked_attention, decode_attention,
                                    full_attention, init_attention,
                                    init_kv_cache)
from repro.models.layers import apply_mrope, apply_rope


def _qkv(b=2, s=64, hq=6, hkv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hq, d)),
            jax.random.normal(ks[1], (b, s, hkv, d)),
            jax.random.normal(ks[2], (b, s, hkv, d)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [8, 16, 64])
def test_flash_matches_full(causal, block_k):
    q, k, v = _qkv()
    o1 = chunked_attention(q, k, v, causal=causal, block_k=block_k)
    o2 = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_full(causal):
    q, k, v = _qkv(s=32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v)))

    g1 = jax.grad(loss(lambda q, k, v: chunked_attention(
        q, k, v, causal=causal, block_k=8)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: full_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_decode_matches_full_layerwise():
    cfg = AttentionConfig(dim=32, n_heads=4, n_kv_heads=2, qkv_bias=True)
    params = init_attention(jax.random.PRNGKey(0), cfg)
    b, s = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, 32))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    y_full = apply_attention(params, x, cfg, positions=pos)
    cache = init_kv_cache(b, 16, cfg, jnp.float32)
    outs = []
    for t in range(s):
        y, cache = apply_attention_decode(params, x[:, t:t + 1], cfg, cache)
        outs.append(y[:, 0])
    y_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec, np.float32),
                               np.asarray(y_full, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_rope_relative_shift_invariance():
    """RoPE inner products depend only on relative offsets."""
    d = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 1, d))
    p0 = jnp.array([[0, 3]])
    p1 = jnp.array([[5, 8]])
    r0 = apply_rope(x, p0)
    r1 = apply_rope(x, p1)
    dot0 = jnp.sum(r0[0, 0, 0] * r0[0, 1, 0])
    dot1 = jnp.sum(r1[0, 0, 0] * r1[0, 1, 0])
    np.testing.assert_allclose(float(dot0), float(dot1), rtol=1e-5)


def test_mrope_reduces_to_rope_for_text():
    d = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3, d))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 6))
    a = apply_rope(x, pos)
    b = apply_mrope(x, pos3, sections=(4, 2, 2))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_decode_attention_masks_beyond_length():
    q, k, v = _qkv(b=2, s=8, hq=4, hkv=2, d=8, seed=3)
    q1 = q[:, :1]
    out_full = decode_attention(q1, k, v, jnp.array([8, 8]))
    # poisoning cache beyond the valid length must not change the output
    k2 = k.at[:, 5:].set(1e3)
    v2 = v.at[:, 5:].set(1e3)
    out_masked = decode_attention(q1, k2, v2, jnp.array([5, 5]))
    out_ref = decode_attention(q1, k, v, jnp.array([5, 5]))
    np.testing.assert_allclose(np.asarray(out_masked), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(out_full), np.asarray(out_ref))


@pytest.mark.parametrize("offset", [0, 5, 40])
def test_chunk_prefill_blocked_matches_dense(offset):
    """Over a cache longer than the key block, chunked-prefill attention
    sweeps key blocks with an online softmax; it equals the dense form."""
    from repro.models.attention import chunk_prefill_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 12, 4, 8))
    kc = jax.random.normal(ks[1], (2, 64, 2, 8))
    vc = jax.random.normal(ks[2], (2, 64, 2, 8))
    off = jnp.asarray(offset, jnp.int32)
    dense = chunk_prefill_attention(q, kc, vc, off, block_k=64)
    blocked = chunk_prefill_attention(q, kc, vc, off, block_k=16)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


# The fused chunk kernel at a small shape with the serve_doc tiling's
# structure: several query tiles (CK_BQ chunk rows each) and key blocks, so
# offsets land before, on and after block edges.
CK_T, CK_S, CK_D, CK_BQ, CK_BK = 32, 256, 64, 16, 64


def _count(name):
    return getattr(obs.REGISTRY.get(name), "value", 0)


@pytest.mark.parametrize("n_valid", [CK_T, CK_T - 7], ids=["full", "padded"])
@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("offset", [0, 5, CK_BK - 1, CK_BK, 100,
                                    CK_S - CK_T])
def test_chunk_kernel_matches_xla_and_full(offset, groups, n_valid):
    """The kernel (interpret mode) equals the XLA sweep and full attention
    over the visible prefix, on the chunk's valid rows: at float32 operands
    (``highest``) to round-off, and at the default within the bf16 rounding
    of its MXU operands."""
    hkv = 2
    ks = jax.random.split(jax.random.PRNGKey(offset + groups), 3)
    q = jax.random.normal(ks[0], (2, CK_T, hkv * groups, CK_D))
    kc = jax.random.normal(ks[1], (2, CK_S, hkv, CK_D))
    vc = jax.random.normal(ks[2], (2, CK_S, hkv, CK_D))
    off = jnp.asarray(offset, jnp.int32)

    def kernel():
        return CA.chunk_attention(q, kc, vc, off, interpret=True,
                                  block_rows=CK_BQ * groups,
                                  block_k=CK_BK)[:, :n_valid]

    with jax.default_matmul_precision("highest"):
        got = kernel()
        sweep = attention._chunk_sweep(q, kc, vc, off,
                                       block_k=CK_BK)[:, :n_valid]
        full = full_attention(q[:, :n_valid], kc[:, :offset + n_valid],
                              vc[:, :offset + n_valid], q_offset=offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sweep),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    assert CA.operand_dtype() == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(kernel()), np.asarray(full),
                               atol=3e-2, rtol=0)


@pytest.mark.parametrize("s,d", [(1100, 64), (256, 12)],
                         ids=["cache-not-a-block-multiple", "head-dim-12"])
def test_chunk_attention_refused_shape_takes_the_sweep(s, d):
    """A shape the kernel does not take (a cache longer than the key block
    and not a multiple of it; a head dim off the tile) is traced with the
    XLA sweep alone, and the dispatch counts it as such."""
    assert CA.tiles(16, s, d, 2) is None
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 16, 4, d))
    kc = jax.random.normal(ks[1], (1, s, 2, d))
    vc = jax.random.normal(ks[2], (1, s, 2, d))
    off = jnp.int32(s - 16)
    xla, pallas = (_count("chunk_attention_xla_total"),
                   _count("chunk_attention_pallas_total"))
    jaxpr = jax.make_jaxpr(attention.chunk_prefill_attention)(q, kc, vc, off)
    assert "platform_index" not in str(jaxpr)
    assert _count("chunk_attention_xla_total") == xla + 1
    assert _count("chunk_attention_pallas_total") == pallas
    np.testing.assert_array_equal(
        np.asarray(attention.chunk_prefill_attention(q, kc, vc, off)),
        np.asarray(attention._chunk_sweep(q, kc, vc, off)))


def test_chunk_attention_taken_shape_dispatches_by_platform():
    """A shape the kernel takes is traced with it as the TPU branch of a
    ``platform_dependent`` (the CPU runs the sweep), and counted."""
    assert CA.tiles(2048, 17408, 64, 4) == (512, 1024)
    assert CA.tiles(2048, 17408, 128, 16) == (128, 1024)
    assert CA.tiles(2048, 17408, 128, 5) == (256, 1024)
    q = jnp.zeros((1, 32, 8, 64))
    kc = jnp.zeros((1, 512, 2, 64))
    pallas = _count("chunk_attention_pallas_total")
    jaxpr = jax.make_jaxpr(attention.chunk_prefill_attention)(
        q, kc, kc, jnp.int32(0))
    assert "platform_index" in str(jaxpr) and "pallas_call" in str(jaxpr)
    assert _count("chunk_attention_pallas_total") == pallas + 1


@pytest.mark.parametrize("tq,bk", [(256, 512), (16, 64), (64, 16)])
def test_kv_block_is_the_last_visible_block_clamped(tq, bk):
    """The key block fetched at step j of query tile i is j up to the block
    of the tile's last visible key, then stays there: every block with a
    key the tile sees is fetched, none past it."""
    s = 8 * max(tq, bk)
    for off in range(0, s - 2 * tq + 1, max(1, min(tq, bk) // 3)):
        for i in range(2):
            last_key = off + (i + 1) * tq - 1
            seen = {key // bk for key in range(last_key + 1)}
            got = [int(CA.kv_block(j, off, i, tq, bk))
                   for j in range(s // bk)]
            assert set(got) == seen
            assert got == sorted(got)
            assert max(got) == int(CA.last_visible_block(off, i, tq, bk))
