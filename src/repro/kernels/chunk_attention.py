"""Pallas TPU kernel for chunked-prefill attention over a padded KV cache.

A T-row prompt chunk at absolute offset ``q_offset`` attends over the
padded cache ``(B, S, Hkv, D)``, into which its own K/V are already
written: key ``j`` is visible to chunk row ``r`` iff ``j <= q_offset + r``
(DESIGN.md §9).  The XLA form (``models/attention._chunk_sweep``) sweeps
every key block of the cache and round-trips each block's logits through
HBM; this kernel computes the same attention in one launch per layer:

* **grid** ``(B, Hkv, T / tq, S / bk)``, the key axis innermost and
  sequential.  The G query heads that share a KV head are stacked in one
  query tile of ``tq · G`` rows (rows in ``(t, g)`` order), so each K/V
  block is read once per KV head and query tile; ``tq`` is sized so the
  stacked tile holds about ``BLOCK_ROWS`` rows whatever G;
* **offset without recompiling**: ``q_offset`` is a scalar-prefetch
  operand, so it stays traced and one program serves every offset;
* **online softmax**: the running max, sum and output accumulator live in
  f32 VMEM scratch; no logits reach HBM;
* **visible keys only**: the K/V ``index_map`` clamps the key-block index
  to the last block the query tile can see (:func:`kv_block`), so the
  steps past it fetch nothing new, and ``pl.when`` skips their compute.
  The causal mask is applied only on blocks that straddle the diagonal.

Precision: the MXU operands are what the XLA sweep's einsums get at the
ambient matmul precision (:func:`operand_dtype`): at the default, an f32
dot on the TPU takes bf16 operands, so q, k, v and p go in as bf16; at any
higher precision they go in as f32 at ``HIGHEST``.  Products accumulate in
f32, and the softmax statistics are f32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30                     # as the XLA sweep masks
# Tiles measured best on a v5e at 4 heads per KV head, head dim 64: 2048
# stacked query rows (512 chunk rows) by 1024 keys, among 512-4096 rows by
# 512-1024 keys.
BLOCK_ROWS = 2048                   # stacked query rows (tq · G) per tile
BLOCK_K = 1024                      # cache rows per key block
MAX_HEAD_DIM = 256                  # widest head the tiles are sized for
_LANES = 128                        # softmax statistics, lane-replicated
_VMEM_BYTES = 64 * 1024 * 1024

# Ambient matmul precisions at which an f32 dot on the TPU rounds its
# operands to bf16 (one MXU pass); any other takes f32 operands.
_ONE_PASS = (None, "default", "fastest", "bfloat16", "BF16_BF16_F32")


def operand_dtype():
    """MXU operand dtype at the ambient ``jax_default_matmul_precision``:
    bf16 where an f32 XLA dot would take one bf16 pass, else f32."""
    prec = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if prec in _ONE_PASS else jnp.float32


def last_visible_block(q_offset, i, tq: int, bk: int):
    """Key block that holds the last key query tile ``i`` sees: the one at
    position ``q_offset + (i + 1) * tq - 1``."""
    return (q_offset + (i + 1) * tq - 1) // bk


def kv_block(j, q_offset, i, tq: int, bk: int):
    """Key block fetched at grid step ``j`` of query tile ``i``: ``j``,
    clamped to the last visible block, so every step past it names the
    block already resident and no DMA is issued."""
    return jnp.minimum(j, last_visible_block(q_offset, i, tq, bk))


def tiles(t: int, s: int, d: int, g: int, block_rows: int = BLOCK_ROWS,
          block_k: int = BLOCK_K):
    """``(tq, bk)`` for a T-row chunk of ``g`` query heads per KV head over
    an S-row cache with head dim ``d``: ``tq`` the largest power of two
    with ``tq · g <= block_rows`` (T if shorter), ``bk`` ``block_k`` (S if
    shorter).  None where the kernel does not take the
    shape: the head dim is not a multiple of 8 up to ``MAX_HEAD_DIM``, or
    T (S) is longer than its tile and not a multiple of it."""
    if d % 8 or d > MAX_HEAD_DIM:
        return None
    tq = min(t, 1 << (max(block_rows // g, 1).bit_length() - 1))
    bk = min(s, block_k)
    if t % tq or s % bk:
        return None
    return tq, bk


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            tq: int, bk: int, g: int, precision):
    i, j = pl.program_id(2), pl.program_id(3)
    first = off_ref[0] + i * tq          # position of the tile's first row
    last = last_visible_block(off_ref[0], i, tq, bk)
    full = (first + 1) // bk             # blocks below it are wholly seen

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...]
        k = k_ref[...].astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(j * bk + col <= first + row, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p.astype(q.dtype), v_ref[...].astype(q.dtype),
                                 (((1,), (0,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + pv

    @pl.when(j < full)
    def _seen():
        step(masked=False)

    @pl.when((j >= full) & (j <= last))
    def _diagonal():
        step(masked=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def chunk_attention(q, k_cache, v_cache, q_offset, *, interpret: bool = False,
                    block_rows: int = BLOCK_ROWS, block_k: int = BLOCK_K):
    """Attention of a (B, T, Hq, D) chunk at offset ``q_offset`` (traced
    scalar ok) over (B, S, Hkv, D) caches; the shape must be one
    :func:`tiles` takes.  Returns (B, T, Hq, D) in q's dtype."""
    b, t, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    tq, bk = tiles(t, s, d, g, block_rows, block_k)
    cdt = operand_dtype()
    precision = (jax.lax.Precision.DEFAULT if cdt == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    # (B, Hkv, T·G, D): each KV head's query heads as (t, g) rows, scaled
    # as the XLA sweep scales them, then rounded to the operand dtype.
    qg = (q.astype(jnp.float32) / math.sqrt(d)).astype(cdt)
    qg = qg.reshape(b, t, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, t * g, d)
    kt = k_cache.transpose(0, 2, 1, 3)                   # (B, Hkv, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    off = jnp.reshape(q_offset, (1,)).astype(jnp.int32)
    rows = tq * g

    def q_map(bi, h, i, j, off_ref):
        return bi, h, i, 0

    def kv_map(bi, h, i, j, off_ref):
        return bi, h, kv_block(j, off_ref[0], i, tq, bk), 0

    out = pl.pallas_call(
        functools.partial(_kernel, tq=tq, bk=bk, g=g, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, t // tq, s // bk),
            in_specs=[pl.BlockSpec((None, None, rows, d), q_map),
                      pl.BlockSpec((None, None, bk, d), kv_map),
                      pl.BlockSpec((None, None, bk, d), kv_map)],
            out_specs=pl.BlockSpec((None, None, rows, d), q_map),
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, t * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(off, qg, kt, vt)
    return out.reshape(b, hkv, t, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, t, hq, d)
