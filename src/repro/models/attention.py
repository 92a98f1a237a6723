"""Grouped-query attention with chunked (flash-style) softmax and KV-cache
decode.

Three entry points:

* :func:`chunked_attention` — online-softmax attention computed over KV
  blocks via ``lax.scan`` (memory O(S·block) instead of O(S²)); used for
  training and prefill.  This is the TPU-idiomatic analogue of fusing the
  attention loop — and one of the beyond-paper memory-term optimisations
  recorded in EXPERIMENTS.md §Perf.
* :func:`full_attention` — materialised reference (small shapes / tests).
* :func:`decode_attention` — one-token query against a (possibly padded)
  KV cache with explicit length masking.

GQA layout: q (B, S, Hq, D), k/v (B, S, Hkv, D), Hq = G·Hkv.  Instead of
repeating KV heads we reshape q to (B, S, Hkv, G, D) and contract per KV
head — avoiding the materialised repeat (less HBM traffic, and XLA keeps
the sharding on the kv-head axis).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import chunk_attention
from repro.models.layers import DTypePolicy, DEFAULT_POLICY, dense_init

NEG_INF = -1e30


def _group_q(q, hkv):
    b, s, hq, d = q.shape
    g = hq // hkv
    return q.reshape(b, s, hkv, g, d)


def full_attention(q, k, v, *, causal: bool = True,
                   q_offset: int = 0, bias=None):
    """Reference attention.  q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    qg = _group_q(q, hkv).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) / math.sqrt(d)
    if causal:
        iq = jnp.arange(sq)[:, None] + q_offset
        ik = jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(ik <= iq, logits, NEG_INF)
    if bias is not None:
        logits = logits + bias
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def _fwd_blocks(qg, kb, vb, iq, causal, block_k):
    """Online-softmax forward over kv blocks.  Returns (out_unnormalised,
    m_final, l_final) with shapes (b,hkv,g,sq,d) / (b,hkv,g,sq)."""
    b, sq = qg.shape[0], qg.shape[1]
    hkv, g, d = qg.shape[2], qg.shape[3], qg.shape[4]
    nblk = kb.shape[1]

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, blk_idx = blk
        kf = k_blk.astype(jnp.float32)
        vf = v_blk.astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
        if causal:
            ik = blk_idx * block_k + jnp.arange(block_k)
            mask = ik[None, :] <= iq[:, None]
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        scale = jnp.exp(m_prev - m_new)
        l_new = l_prev * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vf)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)))
    return acc, m_f, l_f


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention(q, k, v, causal, block_k, q_offset):
    out, _ = _flash_fwd(q, k, v, causal, block_k, q_offset)
    return out


def _flash_fwd(q, k, v, causal, block_k, q_offset):
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    nblk = sk // block_k
    qg = _group_q(q, hkv).astype(jnp.float32) / math.sqrt(d)
    kb = k.reshape(b, nblk, block_k, hkv, d)
    vb = v.reshape(b, nblk, block_k, hkv, d)
    iq = jnp.arange(sq) + q_offset
    acc, m_f, l_f = _fwd_blocks(qg, kb, vb, iq, causal, block_k)
    out = acc / jnp.maximum(l_f[..., None], 1e-30)
    lse = m_f + jnp.log(jnp.maximum(l_f, 1e-30))       # (b,hkv,g,sq)
    out_b = jnp.moveaxis(out, 3, 1).reshape(b, sq, hq, d).astype(q.dtype)
    # Residuals are force-saved across scanned layers (remat does not see
    # through custom_vjp), so every saved tensor costs an (L, B, S, D)
    # stack.  ``out`` is NOT saved — the backward recomputes it from
    # (q, k, v, lse) in a first block sweep (§Perf: one x-sized bf16 stack
    # per layer ≈ 5 GB/device on the 72B 4k train cell, for ~+25% of the
    # backward-attention FLOPs — the right trade on a memory-bound cell).
    return out_b, (q, k, v, lse)


def _flash_bwd(causal, block_k, q_offset, res, dout):
    """FlashAttention-2-style backward: recompute per-block probabilities,
    accumulate dq/dk/dv — O(S·block) memory (no stored S² tensors).
    Two sweeps: (1) recompute out from (q,k,v,lse) for delta, (2) grads."""
    q, k, v, lse = res
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    nblk = sk // block_k
    qg = _group_q(q, hkv).astype(jnp.float32) / math.sqrt(d)
    kb = k.reshape(b, nblk, block_k, hkv, d)
    vb = v.reshape(b, nblk, block_k, hkv, d)
    do = jnp.moveaxis(_group_q(dout, hkv).astype(jnp.float32),
                      (1, 2, 3), (3, 1, 2))            # (b,hkv,g,sq,d)
    iq = jnp.arange(sq) + q_offset

    # Sweep 1: delta = rowsum(dout * out) with out recomputed blockwise
    # (p = exp(logits - lse) is already normalised — no m/l tracking).
    def delta_body(acc, blk):
        k_blk, v_blk, blk_idx = blk
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            k_blk.astype(jnp.float32))
        if causal:
            ik = blk_idx * block_k + jnp.arange(block_k)
            mask = ik[None, :] <= iq[:, None]
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])
        acc = acc + jnp.einsum("bhgqk,bkhd->bhgqd", p,
                               v_blk.astype(jnp.float32))
        return acc, None

    out0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    out, _ = jax.lax.scan(
        delta_body, out0,
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)))
    delta = jnp.einsum("bhgqd,bhgqd->bhgq", do, out)   # (b,hkv,g,sq)

    def body(dq_acc, blk):
        k_blk, v_blk, blk_idx = blk
        kf = k_blk.astype(jnp.float32)
        vf = v_blk.astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
        if causal:
            ik = blk_idx * block_k + jnp.arange(block_k)
            mask = ik[None, :] <= iq[:, None]
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])           # (b,hkv,g,sq,blk)
        dv_blk = jnp.einsum("bhgqk,bhgqd->bkhd", p, do)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", do, vf)
        ds = p * (dp - delta[..., None])
        # logits are linear in k with coefficient qg (= q/√d), so dk uses
        # qg directly; dq needs the extra 1/√d (applied after the scan).
        dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0,
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)))
    dq = (dq / math.sqrt(d)).reshape(b, sq, hq, d).astype(q.dtype)
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(b, sk, hkv, d).astype(k.dtype)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(b, sk, hkv, d).astype(v.dtype)
    return dq, dk, dv


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(q, k, v, *, causal: bool = True, block_k: int = 512,
                      q_offset: int = 0):
    """Flash-style attention: online softmax forward + recomputing custom
    backward.  Memory O(Sq·block_k) in BOTH directions (plain autodiff of
    a blocked forward would still store the S² probabilities for the
    backward — measured 170 GB/device on the 4k×256 train cell;
    see EXPERIMENTS.md §Perf)."""
    sk = k.shape[1]
    while sk % block_k != 0:
        block_k //= 2
    return _flash_attention(q, k, v, causal, block_k, q_offset)


def chunk_prefill_attention(q, k_cache, v_cache, q_offset,
                            block_k: int = 512):
    """Chunked-prefill attention (DESIGN.md §9): a T-token prompt chunk at
    absolute offset ``q_offset`` (traced scalar ok) attends over the padded
    KV cache (B,S,Hkv,D), into which the chunk's own K/V have already been
    written.  Key j is visible iff j <= q_offset + i, so the result equals
    one-shot causal prefill restricted to these T query rows.

    On a TPU, a shape the fused kernel takes (``kernels/chunk_attention``:
    one launch, the logits kept in VMEM, only the key blocks the chunk can
    see) runs it; everywhere else, and for any other shape, the XLA sweep
    ``_chunk_sweep`` does.  The choice is made per platform at lowering
    (``lax.platform_dependent``) and per shape at trace time, which the
    registry counters ``chunk_attention_{pallas,xla}_total`` count."""
    t, hq, d = q.shape[1:]
    s, hkv = k_cache.shape[1:3]
    sweep = functools.partial(_chunk_sweep, block_k=block_k)
    if chunk_attention.tiles(t, s, d, hq // hkv) is None:
        obs.counter("chunk_attention_xla_total",
                    "chunk attentions traced with the XLA sweep alone "
                    "(a shape the kernel does not take)").inc()
        return sweep(q, k_cache, v_cache, q_offset)
    obs.counter("chunk_attention_pallas_total",
                "chunk attentions traced with the fused kernel as their "
                "TPU path").inc()
    return jax.lax.platform_dependent(
        q, k_cache, v_cache, q_offset,
        tpu=chunk_attention.chunk_attention, default=sweep)


def _chunk_sweep(q, k_cache, v_cache, q_offset, block_k: int = 512):
    """The XLA form of :func:`chunk_prefill_attention`, its reference and
    its path off the TPU.  Over the whole padded cache, like
    ``decode_attention`` — the traced offset cannot go through
    ``chunked_attention`` (``q_offset`` is a nondiff_argnum of the flash
    vjp, so it would recompile per offset).  A cache longer than
    ``block_k`` (and a multiple of it) is swept one block of keys at a time
    with an online softmax (``_fwd_blocks``), so the logits held at once
    are T x block_k, not T x S: a 2048-token chunk over a 17k-row cache
    would otherwise hold 4.6 GB of f32 logits."""
    b, t, hq, d = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    qg = _group_q(q, hkv).astype(jnp.float32) / math.sqrt(d)
    iq = q_offset + jnp.arange(t)
    if s > block_k and s % block_k == 0:
        n = s // block_k
        acc, _, l_f = _fwd_blocks(
            qg, k_cache.reshape(b, n, block_k, hkv, d),
            v_cache.reshape(b, n, block_k, hkv, d), iq, True, block_k)
        out = acc / jnp.maximum(l_f[..., None], 1e-30)  # (b,hkv,g,t,d)
        return jnp.moveaxis(out, 3, 1).reshape(b, t, hq, d).astype(q.dtype)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(s)[None, :] <= iq[:, None]
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, t, hq, d).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len):
    """One-step decode.  q (B,1,Hq,D); caches (B,S,Hkv,D); cache_len (B,)
    or scalar — number of valid cache entries (including the new token,
    which the caller must already have written)."""
    b, _, hq, d = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    qg = _group_q(q, hkv).astype(jnp.float32) / math.sqrt(d)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
    valid = jnp.arange(s)[None, :] < jnp.reshape(cache_len, (-1, 1))
    logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full GQA layer (projections + rope + attention + output).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    dim: int
    n_heads: int
    n_kv_heads: int
    head_dim: int = 0              # 0 => dim // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    mrope_sections: Optional[tuple] = None   # e.g. (16, 24, 24) for Qwen2-VL
    causal: bool = True
    block_k: int = 512
    use_chunked: bool = True
    rope: bool = True              # False: no position embedding (NoPE)
    softmax_scale: float = 0.0     # 0 => 1/sqrt(head_dim)

    @property
    def hd(self) -> int:
        return self.head_dim or self.dim // self.n_heads


def init_attention(key, cfg: AttentionConfig, dtype=jnp.float32):
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.dim, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.dim, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.dim, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.dim, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _project_qkv(params, x, cfg: AttentionConfig, policy: DTypePolicy):
    from repro.models.layers import apply_rope, apply_mrope  # local import
    p = policy.cast(params)
    xc = x.astype(policy.compute_dtype)
    b, s, _ = x.shape
    hd = cfg.hd
    q = xc @ p["wq"]
    k = xc @ p["wk"]
    v = xc @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


def _apply_positions(q, k, positions, cfg: AttentionConfig):
    """Rotary positions (unless the config has none) and a configured
    softmax scale, folded into q: the attention kernels divide the logits
    by sqrt(head_dim), so q carries ``softmax_scale * sqrt(head_dim)``."""
    from repro.models.layers import apply_rope, apply_mrope
    if cfg.softmax_scale:
        q = q * (cfg.softmax_scale * math.sqrt(cfg.hd))
    if positions is None or not cfg.rope:
        return q, k
    if cfg.mrope_sections is not None:
        if positions.ndim == 2:      # text-only: replicate plane ids
            positions = jnp.broadcast_to(positions[None],
                                         (3,) + positions.shape)
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def apply_attention(params, x, cfg: AttentionConfig, *, positions=None,
                    kv=None, policy: DTypePolicy = DEFAULT_POLICY):
    """Training / prefill forward.  x (B,S,D).  ``kv`` overrides K/V source
    (cross-attention: tuple of pre-projected (k, v))."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, policy)
    if kv is not None:
        k, v = kv
    else:
        q, k = _apply_positions(q, k, positions, cfg)
    if cfg.use_chunked and k.shape[1] > cfg.block_k:
        out = chunked_attention(q, k, v, causal=cfg.causal and kv is None,
                                block_k=cfg.block_k)
    else:
        out = full_attention(q, k, v, causal=cfg.causal and kv is None)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    p = policy.cast(params)
    return (out.astype(policy.compute_dtype) @ p["wo"]).astype(x.dtype)


def apply_attention_decode(params, x, cfg: AttentionConfig, cache, *,
                           positions=None,
                           policy: DTypePolicy = DEFAULT_POLICY):
    """One-token decode.  x (B,1,D); cache dict with k/v (B,S,Hkv,D) and
    length (B,) already-filled count.  Returns (y, new_cache)."""
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, cfg, policy)
    if positions is None:
        positions = cache["length"][:, None]
    q, k_new = _apply_positions(q, k_new, positions, cfg)
    idx = cache["length"]                                # (B,)
    # One-hot blend instead of dynamic_update_slice: DUS with a dynamic
    # index into a sharded seq dim forces an all-gather under SPMD; the
    # blend is elementwise and partitions cleanly when the KV cache is
    # sequence-sharded (32k/500k decode).  Bandwidth trade-off recorded in
    # EXPERIMENTS.md §Perf.
    oh = jax.nn.one_hot(idx, cache["k"].shape[1],
                        dtype=jnp.float32)[:, :, None, None]
    k_cache = (cache["k"].astype(jnp.float32) * (1.0 - oh)
               + k_new.astype(jnp.float32) * oh).astype(cache["k"].dtype)
    v_cache = (cache["v"].astype(jnp.float32) * (1.0 - oh)
               + v_new.astype(jnp.float32) * oh).astype(cache["v"].dtype)
    out = decode_attention(q, k_cache, v_cache, idx + 1)
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd)
    p = policy.cast(params)
    y = (out.astype(policy.compute_dtype) @ p["wo"]).astype(x.dtype)
    new_cache = {"k": k_cache, "v": v_cache, "length": idx + 1}
    return y, new_cache


def init_kv_cache(batch, max_len, cfg: AttentionConfig,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
