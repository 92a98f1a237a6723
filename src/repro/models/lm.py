"""Unified causal language model covering every assigned architecture.

A model is described by :class:`LMConfig`:

* ``prelude`` — a list of ``(kind, n)`` stages applied once, in order
  (e.g. Kimi-K2's first dense layer);
* ``unit`` — a list of ``(kind, n)`` sub-stages forming a repeating unit;
* ``n_units`` — how many times the unit repeats.  The decoder executes
  ``prelude + unit × n_units``.
* ``shared_attn`` — Zamba2-style: one *weight-shared* attention block
  applied at the end of every unit.

Layer stacks are executed with ``lax.scan`` over stacked parameters (outer
scan over units, inner scan over each sub-stage), which keeps the HLO size
independent of depth — essential for compile times of 60–80-layer models
and for the multi-pod dry-run.

Block kinds:
  attn       pre-norm GQA attention + dense FFN
  attn_moe   pre-norm GQA attention + MoE FFN (aux loss accumulated)
  mamba      pre-norm Mamba2 mixer (no FFN, Zamba2 style)
  mamba_ffn  pre-norm Mamba2 mixer + dense FFN (Granite-4.0-H style)
  mlstm      pre-norm mLSTM mixer
  slstm      pre-norm sLSTM mixer
  gspn       pre-norm GSPN-2 sequence mixer (paper technique) + dense FFN
  xattn      self-attn + cross-attn + FFN (whisper decoder)

Each kind registers init / train-forward / decode-step / cache-init
functions in ``KINDS``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import gspn as gspn_core
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (DTypePolicy, dense_init,
                                 embed_init, init_rmsnorm, apply_rmsnorm,
                                 init_layernorm, apply_layernorm,
                                 init_swiglu, apply_swiglu,
                                 init_gelu_mlp, apply_gelu_mlp,
                                 cross_entropy_loss)


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    family: str                    # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0
    qkv_bias: bool = False
    rope_theta: float = 1e6
    mrope_sections: Optional[tuple] = None
    norm: str = "rmsnorm"
    mlp: str = "swiglu"
    tie_embeddings: bool = False
    max_seq: int = 4096
    # structure
    prelude: tuple = ()            # ((kind, n), ...)
    unit: tuple = ()               # ((kind, n), ...)
    n_units: int = 1
    shared_attn: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_expert_ff: int = 0
    capacity_factor: float = 1.25
    # SSM / xLSTM
    ssm_state: int = 64
    ssm_head_dim: int = 64
    gla_chunk: int = 256
    # GSPN mixer
    gspn_proxy_dim: int = 8
    gspn_row_width: int = 64
    gspn_impl: str = "auto"        # "sp" shards the folded-grid scans over
    gspn_seq_axis: str = "seq"     # the mesh's seq axis (DESIGN.md §8)
    gspn_sp_strategy: str = "auto"
    # Streamed compute dtype of the GSPN mixer's scans (DESIGN.md §10).
    # Defaults to f32 independently of ``compute_dtype`` so the mixer's
    # chunked≡one-shot equivalence stays exact unless a mixed-precision
    # policy (configs.base.with_precision) opts the scans into bf16.
    gspn_compute_dtype: Any = jnp.float32
    # encoder-decoder (audio)
    encoder_layers: int = 0
    enc_len: int = 1500
    # distribution / execution
    n_model_shards: int = 1
    remat: str = "unit"            # none|unit|dots
    attn_block_k: int = 512
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # Scan-carry / accumulator dtype (DESIGN.md §10): stays f32 under the
    # default mixed-precision policy even when params/compute are bf16.
    carry_dtype: Any = jnp.float32
    # Granite-style scalars: embeddings x embed_scale, every block branch
    # x residual_scale before it is added, logits / logit_divisor; the
    # attention softmax scale (0: 1/sqrt(head_dim)), rotary positions or
    # none (NoPE), and the norms' epsilon (None: each norm's default).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    attn_scale: float = 0.0
    rope: bool = True
    norm_eps: Optional[float] = None

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def policy(self) -> DTypePolicy:
        return DTypePolicy(self.param_dtype, self.compute_dtype,
                           self.carry_dtype)

    def stages(self):
        """Flattened (where, kind, n) list: prelude then unit."""
        return [("prelude", k, n) for k, n in self.prelude] + \
               [("unit", k, n) for k, n in self.unit]

    def layer_count(self) -> int:
        n = sum(n for _, n in self.prelude)
        n += self.n_units * sum(n for _, n in self.unit)
        if self.shared_attn:
            n += self.n_units  # shared block applications (1 weight set)
        return n


@dataclasses.dataclass
class Ctx:
    """Per-call execution context threaded through apply functions."""
    mesh: Any = None
    dp_axes: tuple = ("data",)
    model_axis: str = "model"

    def anchor(self, x):
        """Constrain activations to batch-over-dp sharding.  Anchoring at
        block boundaries keeps the SPMD partitioner in the FSDP regime
        (all-gather weights) instead of unsharding the batch to satisfy
        contraction-dim weight sharding (parallel/sharding.py note)."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import sanitize_spec
        spec = P(self.dp_axes) if len(self.dp_axes) > 1 else P(self.dp_axes[0])
        spec = sanitize_spec(
            P(*(spec + (None,) * (x.ndim - 1))), x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Config helpers for sub-modules.
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: LMConfig, causal=True, cross=False):
    return attn_mod.AttentionConfig(
        dim=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        mrope_sections=None if cross else cfg.mrope_sections,
        causal=causal, block_k=cfg.attn_block_k, rope=cfg.rope,
        softmax_scale=cfg.attn_scale)


def _moe_cfg(cfg: LMConfig):
    return moe_mod.MoEConfig(
        dim=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_ff=cfg.moe_d_ff or cfg.d_ff, n_shards=cfg.n_model_shards,
        capacity_factor=cfg.capacity_factor,
        shared_expert_ff=cfg.shared_expert_ff)


def _mamba_cfg(cfg: LMConfig):
    return ssm_mod.Mamba2Config(
        dim=cfg.d_model, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
        chunk=cfg.gla_chunk,
        norm_eps=1e-6 if cfg.norm_eps is None else cfg.norm_eps)


def _mlstm_cfg(cfg: LMConfig):
    return xlstm_mod.MLSTMConfig(dim=cfg.d_model, n_heads=cfg.n_heads,
                                 chunk=cfg.gla_chunk)


def _slstm_cfg(cfg: LMConfig):
    return xlstm_mod.SLSTMConfig(dim=cfg.d_model, n_heads=cfg.n_heads)


def _gspn_cfg(cfg: LMConfig):
    return gspn_core.GSPNSeqConfig(
        dim=cfg.d_model, proxy_dim=cfg.gspn_proxy_dim,
        row_width=cfg.gspn_row_width, impl=cfg.gspn_impl,
        seq_axis=cfg.gspn_seq_axis, sp_strategy=cfg.gspn_sp_strategy,
        param_dtype=cfg.param_dtype,
        compute_dtype=cfg.gspn_compute_dtype,
        carry_dtype=cfg.carry_dtype)


def _norm_init(cfg: LMConfig):
    return (init_rmsnorm if cfg.norm == "rmsnorm" else init_layernorm)(
        cfg.d_model, cfg.param_dtype)


def _norm_apply(cfg: LMConfig, p, x):
    fn = apply_rmsnorm if cfg.norm == "rmsnorm" else apply_layernorm
    return fn(p, x) if cfg.norm_eps is None else fn(p, x, cfg.norm_eps)


def _res(cfg: LMConfig, x, y):
    """Add a block branch to the residual stream, scaled as configured."""
    return x + y if cfg.residual_scale == 1.0 else x + cfg.residual_scale * y


def _embed(params, cfg: LMConfig, tokens):
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    return x if cfg.embed_scale == 1.0 else x * cfg.embed_scale


def _logits(params, cfg: LMConfig, x):
    """Final norm and the vocabulary head (the tied embedding or ``head``)."""
    x = _norm_apply(cfg, params["ln_f"], x)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["head"]).astype(cfg.compute_dtype)
    logits = x.astype(cfg.compute_dtype) @ head
    return logits if cfg.logit_divisor == 1.0 else logits / cfg.logit_divisor


def _ffn_init(key, cfg: LMConfig):
    if cfg.mlp == "swiglu":
        return init_swiglu(key, cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return init_gelu_mlp(key, cfg.d_model, cfg.d_ff, cfg.param_dtype)


def _ffn_apply(cfg: LMConfig, p, x):
    if cfg.mlp == "swiglu":
        return apply_swiglu(p, x, cfg.policy)
    return apply_gelu_mlp(p, x, cfg.policy)


# ---------------------------------------------------------------------------
# Block kinds.
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg: LMConfig, with_ffn=True, cross=False):
    ks = jax.random.split(key, 6)
    p = {"ln1": _norm_init(cfg),
         "attn": attn_mod.init_attention(ks[0], _attn_cfg(cfg),
                                         cfg.param_dtype)}
    if cross:
        p["ln_x"] = _norm_init(cfg)
        p["xattn"] = attn_mod.init_attention(ks[1], _attn_cfg(cfg, cross=True),
                                             cfg.param_dtype)
    if with_ffn:
        p["ln2"] = _norm_init(cfg)
        p["ffn"] = _ffn_init(ks[2], cfg)
    return p


def _apply_attn_block(p, x, cfg, ctx, positions, enc_kv=None, moe=False):
    aux = jnp.zeros((), jnp.float32)
    h = _norm_apply(cfg, p["ln1"], x)
    x = _res(cfg, x, attn_mod.apply_attention(
        p["attn"], h, _attn_cfg(cfg), positions=positions, policy=cfg.policy))
    if enc_kv is not None:
        h = _norm_apply(cfg, p["ln_x"], x)
        x = x + attn_mod.apply_attention(
            p["xattn"], h, _attn_cfg(cfg, cross=True), kv=enc_kv,
            policy=cfg.policy)
    if moe:
        h = _norm_apply(cfg, p["ln2"], x)
        y, aux = moe_mod.apply_moe(p["moe"], h, _moe_cfg(cfg),
                                   mesh=ctx.mesh, dp_axes=ctx.dp_axes,
                                   model_axis=ctx.model_axis,
                                   policy=cfg.policy)
        x = _res(cfg, x, y)
    elif "ffn" in p:
        h = _norm_apply(cfg, p["ln2"], x)
        x = _res(cfg, x, _ffn_apply(cfg, p["ffn"], h))
    return x, aux


def _apply_attn_block_decode(p, x, cfg, ctx, cache, enc_kv=None, moe=False):
    h = _norm_apply(cfg, p["ln1"], x)
    y, new_attn = attn_mod.apply_attention_decode(
        p["attn"], h, _attn_cfg(cfg), cache["attn"], policy=cfg.policy)
    x = _res(cfg, x, y)
    if enc_kv is not None:
        h = _norm_apply(cfg, p["ln_x"], x)
        x = x + attn_mod.apply_attention(
            p["xattn"], h, _attn_cfg(cfg, cross=True), kv=enc_kv,
            policy=cfg.policy)
    if moe:
        h = _norm_apply(cfg, p["ln2"], x)
        y, _ = moe_mod.apply_moe(p["moe"], h, _moe_cfg(cfg), mesh=ctx.mesh,
                                 dp_axes=ctx.dp_axes,
                                 model_axis=ctx.model_axis, policy=cfg.policy)
        x = _res(cfg, x, y)
    elif "ffn" in p:
        h = _norm_apply(cfg, p["ln2"], x)
        x = _res(cfg, x, _ffn_apply(cfg, p["ffn"], h))
    return x, {"attn": new_attn}


class Kind:
    """Registry record for a block kind.

    ``apply_prefill_chunk`` (optional) consumes a T-token prompt chunk at
    absolute offset ``off`` against an already-initialised decode cache and
    returns (y, new_cache) — the incremental-prefill contract the serving
    engine chunks prompts through (DESIGN.md §9).  Kinds without it force
    the engine onto the one-shot prefill path.
    """

    def __init__(self, init, apply, apply_decode, cache_init,
                 apply_prefill=None, apply_prefill_chunk=None):
        self.init = init
        self.apply = apply
        self.apply_decode = apply_decode
        self.cache_init = cache_init
        self.apply_prefill = apply_prefill
        self.apply_prefill_chunk = apply_prefill_chunk


def _mk_attn_kind(moe=False, cross=False):
    def init(key, cfg):
        p = _init_attn_block(key, cfg, with_ffn=not moe, cross=cross)
        if moe:
            p["ln2"] = _norm_init(cfg)
            p["moe"] = moe_mod.init_moe(jax.random.fold_in(key, 101),
                                        _moe_cfg(cfg), cfg.param_dtype)
        return p

    def apply(p, x, cfg, ctx, positions, enc_kv=None):
        return _apply_attn_block(p, x, cfg, ctx, positions,
                                 enc_kv=enc_kv if cross else None, moe=moe)

    def apply_decode(p, x, cfg, ctx, cache, enc_kv=None):
        return _apply_attn_block_decode(p, x, cfg, ctx, cache,
                                        enc_kv=enc_kv if cross else None,
                                        moe=moe)

    def cache_init(batch, max_len, cfg):
        return {"attn": attn_mod.init_kv_cache(batch, max_len, _attn_cfg(cfg),
                                               cfg.compute_dtype)}

    def apply_prefill(p, x, cfg, ctx, positions, max_len, enc_kv=None):
        b, s, _ = x.shape
        acfg = _attn_cfg(cfg)
        h = _norm_apply(cfg, p["ln1"], x)
        q, k, v = attn_mod._project_qkv(p["attn"], h, acfg, cfg.policy)
        q, k = attn_mod._apply_positions(q, k, positions, acfg)
        if acfg.use_chunked and k.shape[1] > acfg.block_k:
            out = attn_mod.chunked_attention(q, k, v, causal=True,
                                             block_k=acfg.block_k)
        else:
            out = attn_mod.full_attention(q, k, v, causal=True)
        out = out.reshape(b, s, acfg.n_heads * acfg.hd)
        pc = cfg.policy.cast(p["attn"])
        x = _res(cfg, x, (out.astype(cfg.policy.compute_dtype)
                          @ pc["wo"]).astype(x.dtype))
        pad = max_len - s
        cache = {"attn": {
            "k": jnp.pad(k.astype(cfg.compute_dtype),
                         ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v.astype(cfg.compute_dtype),
                         ((0, 0), (0, pad), (0, 0), (0, 0))),
            "length": jnp.full((b,), s, jnp.int32),
        }}
        if cross and enc_kv is not None:
            h = _norm_apply(cfg, p["ln_x"], x)
            x = x + attn_mod.apply_attention(
                p["xattn"], h, _attn_cfg(cfg, cross=True), kv=enc_kv,
                policy=cfg.policy)
        if moe:
            h = _norm_apply(cfg, p["ln2"], x)
            y, _ = moe_mod.apply_moe(p["moe"], h, _moe_cfg(cfg),
                                     mesh=ctx.mesh, dp_axes=ctx.dp_axes,
                                     model_axis=ctx.model_axis,
                                     policy=cfg.policy)
            x = _res(cfg, x, y)
        elif "ffn" in p:
            h = _norm_apply(cfg, p["ln2"], x)
            x = _res(cfg, x, _ffn_apply(cfg, p["ffn"], h))
        return x, cache

    def apply_prefill_chunk(p, x, cfg, ctx, cache, off, enc_kv=None,
                            n_valid=None):
        """Consume a (B, T) prompt chunk at offset ``off`` (traced scalar):
        write the chunk's K/V into the cache in place and attend over the
        cache with the offset causal mask — equal to one-shot prefill
        restricted to these T rows (DESIGN.md §9).  With ``n_valid`` the
        rows past it are padding: their K/V land past the cache length,
        where no query looks and decode overwrites them."""
        b, t, _ = x.shape
        acfg = _attn_cfg(cfg)
        h = _norm_apply(cfg, p["ln1"], x)
        q, k, v = attn_mod._project_qkv(p["attn"], h, acfg, cfg.policy)
        positions = jnp.broadcast_to(
            off + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        q, k = attn_mod._apply_positions(q, k, positions, acfg)
        kc = jax.lax.dynamic_update_slice(
            cache["attn"]["k"], k.astype(cache["attn"]["k"].dtype),
            (0, off, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["attn"]["v"], v.astype(cache["attn"]["v"].dtype),
            (0, off, 0, 0))
        out = attn_mod.chunk_prefill_attention(q, kc, vc, off)
        out = out.reshape(b, t, acfg.n_heads * acfg.hd)
        pc = cfg.policy.cast(p["attn"])
        x = _res(cfg, x, (out.astype(cfg.policy.compute_dtype)
                          @ pc["wo"]).astype(x.dtype))
        new_cache = {"attn": {
            "k": kc, "v": vc,
            "length": jnp.full((b,), 0, jnp.int32) + off + (
                t if n_valid is None else n_valid),
        }}
        if moe:
            h = _norm_apply(cfg, p["ln2"], x)
            y, _ = moe_mod.apply_moe(p["moe"], h, _moe_cfg(cfg),
                                     mesh=ctx.mesh, dp_axes=ctx.dp_axes,
                                     model_axis=ctx.model_axis,
                                     policy=cfg.policy)
            x = _res(cfg, x, y)
        elif "ffn" in p:
            h = _norm_apply(cfg, p["ln2"], x)
            x = _res(cfg, x, _ffn_apply(cfg, p["ffn"], h))
        return x, new_cache

    return Kind(init, apply, apply_decode, cache_init, apply_prefill,
                apply_prefill_chunk=None if cross else apply_prefill_chunk)


def _mk_mixer_kind(name):
    """A pre-norm sequence mixer; ``gspn`` and ``mamba_ffn`` (Granite-4.0-H
    style) follow it with a pre-norm FFN, ``mamba`` (Zamba2 style) and the
    xLSTM mixers do not."""
    mixer = "mamba" if name == "mamba_ffn" else name
    has_ffn = name in ("gspn", "mamba_ffn")

    def ffn(p, x, cfg):
        if not has_ffn:
            return x
        return _res(cfg, x, _ffn_apply(cfg, p["ffn"],
                                       _norm_apply(cfg, p["ln2"], x)))

    def init(key, cfg):
        k1, k2 = jax.random.split(key)
        p = {"ln1": _norm_init(cfg)}
        if mixer == "mamba":
            p["mix"] = ssm_mod.init_mamba2(k1, _mamba_cfg(cfg),
                                           cfg.param_dtype)
        elif mixer == "mlstm":
            p["mix"] = xlstm_mod.init_mlstm(k1, _mlstm_cfg(cfg),
                                            cfg.param_dtype)
        elif mixer == "slstm":
            p["mix"] = xlstm_mod.init_slstm(k1, _slstm_cfg(cfg),
                                            cfg.param_dtype)
        elif mixer == "gspn":
            p["mix"] = gspn_core.init_gspn_seq_mixer(k1, _gspn_cfg(cfg))
        if has_ffn:
            p["ln2"] = _norm_init(cfg)
            p["ffn"] = _ffn_init(k2, cfg)
        return p

    def apply(p, x, cfg, ctx, positions, enc_kv=None):
        h = _norm_apply(cfg, p["ln1"], x)
        if mixer == "mamba":
            y = ssm_mod.apply_mamba2(p["mix"], h, _mamba_cfg(cfg), cfg.policy)
        elif mixer == "mlstm":
            y = xlstm_mod.apply_mlstm(p["mix"], h, _mlstm_cfg(cfg),
                                      cfg.policy)
        elif mixer == "slstm":
            y = xlstm_mod.apply_slstm(p["mix"], h, _slstm_cfg(cfg),
                                      cfg.policy)
        else:
            y = gspn_core.apply_gspn_seq_mixer(
                p["mix"], h, _gspn_cfg(cfg),
                mesh=ctx.mesh if ctx is not None else None)
        return ffn(p, _res(cfg, x, y), cfg), jnp.zeros((), jnp.float32)

    def apply_decode(p, x, cfg, ctx, cache, enc_kv=None):
        h = _norm_apply(cfg, p["ln1"], x)
        if mixer == "mamba":
            y, new = ssm_mod.apply_mamba2_decode(p["mix"], h,
                                                 _mamba_cfg(cfg), cache,
                                                 cfg.policy)
        elif mixer == "mlstm":
            y, new = xlstm_mod.apply_mlstm_decode(p["mix"], h,
                                                  _mlstm_cfg(cfg), cache,
                                                  cfg.policy)
        elif mixer == "slstm":
            y, new = xlstm_mod.apply_slstm_decode(p["mix"], h,
                                                  _slstm_cfg(cfg), cache,
                                                  cfg.policy)
        else:
            y, new = gspn_decode_step(p["mix"], h, _gspn_cfg(cfg), cache)
        return ffn(p, _res(cfg, x, y), cfg), new

    def cache_init(batch, max_len, cfg):
        if mixer == "mamba":
            return ssm_mod.init_mamba2_cache(batch, _mamba_cfg(cfg),
                                             jnp.float32)
        if mixer == "mlstm":
            return xlstm_mod.init_mlstm_cache(batch, _mlstm_cfg(cfg))
        if mixer == "slstm":
            return xlstm_mod.init_slstm_cache(batch, _slstm_cfg(cfg))
        return init_gspn_decode_cache(batch, _gspn_cfg(cfg))

    def apply_prefill(p, x, cfg, ctx, positions, max_len, enc_kv=None):
        h = _norm_apply(cfg, p["ln1"], x)
        if mixer == "mamba":
            y, cache = ssm_mod.apply_mamba2_prefill(p["mix"], h,
                                                    _mamba_cfg(cfg),
                                                    cfg.policy)
        elif mixer == "mlstm":
            y, cache = xlstm_mod.apply_mlstm_prefill(p["mix"], h,
                                                     _mlstm_cfg(cfg),
                                                     cfg.policy)
        elif mixer == "slstm":
            y, cache = xlstm_mod.apply_slstm_prefill(p["mix"], h,
                                                     _slstm_cfg(cfg),
                                                     cfg.policy)
        else:
            y, cache = gspn_core.apply_gspn_seq_mixer(
                p["mix"], h, _gspn_cfg(cfg), return_cache=True,
                mesh=ctx.mesh if ctx is not None else None)
        return ffn(p, _res(cfg, x, y), cfg), cache

    def apply_prefill_chunk(p, x, cfg, ctx, cache, off, enc_kv=None,
                            n_valid=None):
        # The GSPN mixer resumes its chunked scan from the cached boundary
        # rows, Mamba-2 its SSD scan from the cached state and conv tail;
        # the xLSTM mixers' prefill starts from a zero state, so the
        # engine keeps them on one-shot prefill (supports_chunked_prefill).
        h = _norm_apply(cfg, p["ln1"], x)
        if mixer == "mamba":
            y, new = ssm_mod.apply_mamba2_decode(p["mix"], h,
                                                 _mamba_cfg(cfg), cache,
                                                 cfg.policy, n_valid=n_valid)
        else:
            if n_valid is not None:
                raise ValueError("the GSPN scan has no mask for padding")
            y, new = gspn_core.gspn_seq_prefill_chunk(
                p["mix"], h, _gspn_cfg(cfg), cache,
                mesh=ctx.mesh if ctx is not None else None)
        return ffn(p, _res(cfg, x, y), cfg), new

    return Kind(init, apply, apply_decode, cache_init, apply_prefill,
                apply_prefill_chunk if mixer in ("gspn", "mamba") else None)


KINDS = {
    "attn": _mk_attn_kind(moe=False),
    "attn_moe": _mk_attn_kind(moe=True),
    "xattn": _mk_attn_kind(moe=False, cross=True),
    "mamba": _mk_mixer_kind("mamba"),
    "mamba_ffn": _mk_mixer_kind("mamba_ffn"),
    "mlstm": _mk_mixer_kind("mlstm"),
    "slstm": _mk_mixer_kind("slstm"),
    "gspn": _mk_mixer_kind("gspn"),
}


# ---------------------------------------------------------------------------
# GSPN sequence-mixer decode (O(W) state — "last row" caching).
# ---------------------------------------------------------------------------

def init_gspn_decode_cache(batch, scfg: gspn_core.GSPNSeqConfig):
    w = scfg.row_width or 64
    cp = scfg.proxy_dim
    return {
        "prev_row": jnp.zeros((batch, cp, w), jnp.float32),
        "cur_row": jnp.zeros((batch, cp, w), jnp.float32),
        "row_state": jnp.zeros((batch, cp), jnp.float32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def gspn_decode_step(params, x, scfg: gspn_core.GSPNSeqConfig, cache):
    """One-token GSPN mixer step.  x (B,1,D).  Maintains the previous
    grid row (T→B pass) and the running within-row state — O(√L) memory."""
    b, _, d = x.shape
    cp = scfg.proxy_dim
    w = cache["prev_row"].shape[-1]
    xf = x[:, 0].astype(jnp.float32)                     # (B,D)

    x_p = xf @ params["down"].astype(jnp.float32)        # (B,Cp)
    tap_logits = xf @ params["w_taps"].astype(jnp.float32)   # (B,3)
    row_g = jax.nn.sigmoid(xf @ params["w_row"].astype(jnp.float32))  # (B,1)
    lam = jax.nn.sigmoid(xf @ params["w_lam"].astype(jnp.float32))    # (B,2Cp)
    u = xf @ params["w_u"].astype(jnp.float32)           # (B,2Cp)

    j = cache["pos"] % w                                 # (B,)
    # neighbours of column j in the previous row (boundary -> 0)
    def gather_col(rows, idx, valid):
        g = jnp.take_along_axis(
            rows, jnp.clip(idx, 0, w - 1)[:, None, None], axis=-1)[..., 0]
        return jnp.where(valid[:, None], g, 0.0)         # (B,Cp)

    h_l = gather_col(cache["prev_row"], j - 1, j - 1 >= 0)
    h_c = gather_col(cache["prev_row"], j, jnp.ones_like(j, bool))
    h_r = gather_col(cache["prev_row"], j + 1, j + 1 <= w - 1)

    # masked softmax over taps, matching normalize_taps boundary rules
    neg = jnp.finfo(jnp.float32).min
    mask = jnp.stack([jnp.where(j == 0, neg, 0.0),
                      jnp.zeros_like(j, jnp.float32),
                      jnp.where(j == w - 1, neg, 0.0)], axis=-1)
    taps = jax.nn.softmax(tap_logits + mask, axis=-1)    # (B,3)

    h_tb = (taps[:, 0:1] * h_l + taps[:, 1:2] * h_c + taps[:, 2:3] * h_r
            + lam[:, :cp] * x_p)                         # (B,Cp)
    # within-row: reset at row start
    at_row_start = (j == 0)[:, None]
    row_prev = jnp.where(at_row_start, 0.0, cache["row_state"])
    h_row = row_g * row_prev + lam[:, cp:] * x_p

    y = u[:, :cp] * h_tb + u[:, cp:] * h_row
    y = (y @ params["up"].astype(jnp.float32))[:, None]  # (B,1,D)

    cur = jnp.where(at_row_start[..., None],
                    jnp.zeros_like(cache["cur_row"]), cache["cur_row"])
    # write column j of cur_row
    onehot = jax.nn.one_hot(j, w, dtype=jnp.float32)     # (B,W)
    cur = cur * (1.0 - onehot[:, None, :]) + h_tb[..., None] * onehot[:, None, :]
    at_row_end = (j == w - 1)[:, None, None]
    new_prev = jnp.where(at_row_end, cur, cache["prev_row"])
    new_cache = {"prev_row": new_prev, "cur_row": cur,
                 "row_state": h_row, "pos": cache["pos"] + 1}
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Whisper-style encoder (stub frontend: embeddings provided).
# ---------------------------------------------------------------------------

def _init_encoder(key, cfg: LMConfig):
    def one(k):
        p = {"ln1": _norm_init(cfg),
             "attn": attn_mod.init_attention(
                 jax.random.fold_in(k, 0), _attn_cfg(cfg, causal=False),
                 cfg.param_dtype),
             "ln2": _norm_init(cfg),
             "ffn": _ffn_init(jax.random.fold_in(k, 1), cfg)}
        return p

    keys = jax.random.split(key, cfg.encoder_layers)
    stacked = jax.vmap(one)(keys)
    k2 = jax.random.fold_in(key, 99)
    return {"layers": stacked, "ln_f": _norm_init(cfg),
            "pos_embed": embed_init(k2, cfg.enc_len, cfg.d_model,
                                    cfg.param_dtype)}


def _apply_encoder(params, frames, cfg: LMConfig):
    """frames: (B, T, D) stub frame embeddings."""
    x = frames + params["pos_embed"].astype(frames.dtype)[None, :frames.shape[1]]
    acfg = _attn_cfg(cfg, causal=False)

    def body(x, layer):
        h = _norm_apply(cfg, layer["ln1"], x)
        x = x + attn_mod.apply_attention(layer["attn"], h, acfg,
                                         policy=cfg.policy)
        h = _norm_apply(cfg, layer["ln2"], x)
        x = x + _ffn_apply(cfg, layer["ffn"], h)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm_apply(cfg, params["ln_f"], x)


# ---------------------------------------------------------------------------
# Model init.
# ---------------------------------------------------------------------------

def init_lm(key, cfg: LMConfig):
    params = {}
    k_embed, k_head, k_stage, k_enc, k_shared = jax.random.split(key, 5)
    params["embed"] = embed_init(k_embed, cfg.vocab, cfg.d_model,
                                 cfg.param_dtype)
    params["ln_f"] = _norm_init(cfg)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, cfg.d_model, cfg.vocab,
                                    cfg.param_dtype)

    stages = {}
    for si, (where, kind, n) in enumerate(cfg.stages()):
        kf = KINDS[kind]
        base = jax.random.fold_in(k_stage, si)
        if where == "prelude":
            keys = jax.random.split(base, n)
            stacked = jax.vmap(lambda k: kf.init(k, cfg))(keys)
        else:
            keys = jax.random.split(base, cfg.n_units * n).reshape(
                cfg.n_units, n, 2)
            stacked = jax.vmap(jax.vmap(lambda k: kf.init(k, cfg)))(keys)
        stages[f"s{si}_{kind}"] = stacked
    params["stages"] = stages

    if cfg.shared_attn:
        params["shared_attn"] = KINDS["attn"].init(k_shared, cfg)
    if cfg.encoder_layers:
        params["encoder"] = _init_encoder(k_enc, cfg)
        kx = jax.random.fold_in(k_enc, 7)
        acfg = _attn_cfg(cfg)
        params["enc_kv_proj"] = {
            "wk": dense_init(kx, cfg.d_model,
                             cfg.n_kv_heads * acfg.hd, cfg.param_dtype),
            "wv": dense_init(jax.random.fold_in(kx, 1), cfg.d_model,
                             cfg.n_kv_heads * acfg.hd, cfg.param_dtype),
        }
    return params


def _encoder_kv(params, enc_out, cfg: LMConfig):
    b, t, _ = enc_out.shape
    acfg = _attn_cfg(cfg)
    pol = cfg.policy
    wk = params["enc_kv_proj"]["wk"].astype(pol.compute_dtype)
    wv = params["enc_kv_proj"]["wv"].astype(pol.compute_dtype)
    k = (enc_out.astype(pol.compute_dtype) @ wk).reshape(
        b, t, cfg.n_kv_heads, acfg.hd)
    v = (enc_out.astype(pol.compute_dtype) @ wv).reshape(
        b, t, cfg.n_kv_heads, acfg.hd)
    return (k, v)


# ---------------------------------------------------------------------------
# Forward (training / prefill).
# ---------------------------------------------------------------------------

def _maybe_remat(cfg: LMConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def apply_lm(params, cfg: LMConfig, tokens, *, ctx: Ctx = None,
             vision_embeds=None, enc_frames=None, positions=None):
    """Forward pass producing logits (B, S, V).

    tokens: (B, S) int32.  ``vision_embeds`` (B, S_vis, D) replace the
    embeddings of the first S_vis positions (Qwen2-VL stub frontend);
    ``enc_frames`` (B, T, D) drive the audio encoder (whisper stub).
    """
    ctx = ctx or Ctx()
    pol = cfg.policy
    x = _embed(params, cfg, tokens)
    if vision_embeds is not None:
        sv = vision_embeds.shape[1]
        x = jnp.concatenate(
            [vision_embeds.astype(pol.compute_dtype), x[:, sv:]], axis=1)
    x = ctx.anchor(x)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    enc_kv = None
    if cfg.encoder_layers and enc_frames is not None:
        enc_out = _apply_encoder(params["encoder"], enc_frames, cfg)
        enc_kv = _encoder_kv(params, enc_out, cfg)

    aux_total = jnp.zeros((), jnp.float32)

    def stage_scan(x, aux_total, stacked, kind):
        kf = KINDS[kind]

        def body(carry, layer_params):
            h, aux = carry
            h, a = kf.apply(layer_params, ctx.anchor(h), cfg, ctx, positions,
                            enc_kv=enc_kv)
            return (ctx.anchor(h), aux + a), None

        body = _maybe_remat(cfg, body)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)
        return x, aux_total

    stages = cfg.stages()
    for si, (where, kind, n) in enumerate(stages):
        stacked = params["stages"][f"s{si}_{kind}"]
        if where == "prelude":
            x, aux_total = stage_scan(x, aux_total, stacked, kind)

    unit_stages = [(si, kind) for si, (w, kind, n) in enumerate(stages)
                   if w == "unit"]
    if unit_stages:
        def unit_body(carry, unit_params):
            h, aux = carry
            for si, kind in unit_stages:
                kf = KINDS[kind]

                def body(c, lp, kf=kf):
                    hh, a0 = c
                    hh, a = kf.apply(lp, ctx.anchor(hh), cfg, ctx, positions,
                                     enc_kv=enc_kv)
                    return (ctx.anchor(hh), a0 + a), None

                body = _maybe_remat(cfg, body)
                (h, aux), _ = jax.lax.scan(body, (h, aux),
                                           unit_params[f"s{si}_{kind}"])
            if cfg.shared_attn:
                h, a = KINDS["attn"].apply(params["shared_attn"], h, cfg,
                                           ctx, positions)
                aux = aux + a
            return (h, aux), None

        unit_params = {f"s{si}_{kind}": params["stages"][f"s{si}_{kind}"]
                       for si, kind in unit_stages}
        (x, aux_total), _ = jax.lax.scan(unit_body, (x, aux_total),
                                         unit_params)

    return _logits(params, cfg, ctx.anchor(x)), aux_total


def lm_loss(params, cfg: LMConfig, batch, ctx: Ctx = None):
    """batch: dict(tokens (B,S), labels (B,S), [mask], [vision_embeds],
    [enc_frames])."""
    logits, aux = apply_lm(params, cfg, batch["tokens"], ctx=ctx,
                           vision_embeds=batch.get("vision_embeds"),
                           enc_frames=batch.get("enc_frames"))
    ce = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill: forward over the prompt that also fills the decode caches.
# ---------------------------------------------------------------------------

def lm_prefill(params, cfg: LMConfig, tokens, max_len: int, *,
               ctx: Ctx = None, enc_frames=None, vision_embeds=None):
    """Returns (logits (B,S,V), caches, enc_kv)."""
    ctx = ctx or Ctx()
    pol = cfg.policy
    x = _embed(params, cfg, tokens)
    if vision_embeds is not None:
        sv = vision_embeds.shape[1]
        x = jnp.concatenate(
            [vision_embeds.astype(pol.compute_dtype), x[:, sv:]], axis=1)
    x = ctx.anchor(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    enc_kv = None
    if cfg.encoder_layers and enc_frames is not None:
        enc_out = _apply_encoder(params["encoder"], enc_frames, cfg)
        enc_kv = _encoder_kv(params, enc_out, cfg)

    caches = {}
    stages = cfg.stages()
    for si, (where, kind, n) in enumerate(stages):
        if where != "prelude":
            continue
        kf = KINDS[kind]

        def body(h, lp, kf=kf):
            h, cache = kf.apply_prefill(lp, ctx.anchor(h), cfg, ctx,
                                        positions, max_len, enc_kv=enc_kv)
            return ctx.anchor(h), cache

        x, cache = jax.lax.scan(body, x, params["stages"][f"s{si}_{kind}"])
        caches[f"s{si}_{kind}"] = cache

    unit_stages = [(si, kind) for si, (w, kind, n) in enumerate(stages)
                   if w == "unit"]
    if unit_stages:
        def unit_body(h, unit_params):
            new_unit = {}
            for si, kind in unit_stages:
                kf = KINDS[kind]

                def body(hh, lp, kf=kf):
                    hh, cache = kf.apply_prefill(lp, ctx.anchor(hh), cfg, ctx,
                                                 positions, max_len,
                                                 enc_kv=enc_kv)
                    return ctx.anchor(hh), cache

                h, cache = jax.lax.scan(body, h,
                                        unit_params[f"s{si}_{kind}"])
                new_unit[f"s{si}_{kind}"] = cache
            if cfg.shared_attn:
                h, sh_cache = KINDS["attn"].apply_prefill(
                    params["shared_attn"], h, cfg, ctx, positions, max_len)
                new_unit["shared_attn"] = sh_cache
            return h, new_unit

        unit_params = {f"s{si}_{kind}": params["stages"][f"s{si}_{kind}"]
                       for si, kind in unit_stages}
        x, unit_caches = jax.lax.scan(unit_body, x, unit_params)
        caches.update(unit_caches)

    return _logits(params, cfg, ctx.anchor(x)), caches, enc_kv


# ---------------------------------------------------------------------------
# Chunked prefill: consume the prompt in fixed-size chunks against live
# decode caches (DESIGN.md §9).  Shares weights with lm_prefill /
# lm_decode_step — it is the same stage walk with apply_prefill_chunk.
# ---------------------------------------------------------------------------

def _scan_stage(body, x, stacked):
    """Walk a prelude stage's layers, ``body(x, (layer_params, cache))``
    giving ``(x, new_cache)``, and return ``(x, new stacked caches)``.

    The stacked caches are the loop's carry, each layer's update written
    back in place (a ``lax.scan`` would build the new caches in a fresh
    stacked buffer and copy it out, holding two copies of every stage's
    state: 4.3 GB more than the pool at granite-4.0-h-micro's 32 slots).
    A stage of one layer is applied directly."""
    params, caches = stacked
    n = jax.tree.leaves(caches)[0].shape[0]
    if n == 1:
        x, new = body(x, jax.tree.map(lambda a: a[0], stacked))
        return x, jax.tree.map(lambda a, c: c[None].astype(a.dtype),
                               caches, new)

    def step(i, carry):
        h, cs = carry
        h, new = body(h, jax.tree.map(lambda a: a[i], (params, cs)))
        return h, jax.tree.map(lambda a, c: a.at[i].set(c.astype(a.dtype)),
                               cs, new)
    return jax.lax.fori_loop(0, n, step, (x, caches))


def supports_chunked_prefill(cfg: LMConfig) -> bool:
    """True iff every stage kind of ``cfg`` implements the incremental
    prefill contract (attention families, the GSPN mixer and Mamba-2).
    xLSTM mixers and encoder-decoder models fall back to one-shot
    prefill."""
    if cfg.encoder_layers:
        return False
    kinds = {kind for _, kind, _ in cfg.stages()}
    if cfg.shared_attn:
        kinds.add("attn")
    if any(KINDS[k].apply_prefill_chunk is None for k in kinds):
        return False
    if "gspn" in kinds and cfg.gspn_row_width <= 0:
        return False           # fold geometry must not depend on length
    return True


def supports_padded_chunks(cfg: LMConfig) -> bool:
    """True iff a prompt chunk may end in padding (``lm_prefill_chunk``'s
    ``n_valid``): attention keeps it past the cache length and Mamba-2
    gives it no step.  The GSPN scan has no such mask, and padding would
    take expert capacity from the tokens of a mixture of experts."""
    kinds = {kind for _, kind, _ in cfg.stages()}
    return (supports_chunked_prefill(cfg)
            and kinds <= {"attn", "mamba", "mamba_ffn"})


def prefill_chunk_alignment(cfg: LMConfig) -> int:
    """Chunk boundaries must start at GSPN grid-row boundaries
    (gspn_seq_prefill_chunk contract), and start at Mamba-2 scan-chunk
    boundaries so every prompt chunk runs whole SSD chunks; chunk sizes
    are rounded to a multiple of both (1 without either kind)."""
    kinds = {kind for _, kind, _ in cfg.stages()}
    align = 1
    if "gspn" in kinds:
        align = max(1, cfg.gspn_row_width)
    if kinds & {"mamba", "mamba_ffn"}:
        align = math.lcm(align, cfg.gla_chunk)
    return align


def lm_prefill_chunk(params, cfg: LMConfig, tokens, caches, off, *,
                     ctx: Ctx = None, with_logits: bool = True,
                     n_valid=None):
    """Consume prompt tokens (B, T) starting at absolute offset ``off``
    (scalar int32, traced — one compile per chunk LENGTH, not per offset)
    against ``caches`` shaped like :func:`init_lm_cache` output.  Returns
    (logits (B, T, V), new_caches).  Chaining chunks and then decoding is
    numerically equivalent to :func:`lm_prefill` over the whole prompt
    (pinned at 1e-5 by tests/test_serve_engine.py).

    ``with_logits=False`` (static) returns (None, new_caches), skipping
    the final norm + vocab-head matmul — only the LAST chunk's logits
    feed sampling, so intermediate chunks in the serve hot path need not
    pay an O(T·V) head projection each.

    ``n_valid`` (traced scalar, models with :func:`supports_padded_chunks`):
    only the first ``n_valid`` tokens are the prompt's, the rest padding
    that leaves the caches as if the chunk had ended at ``n_valid``; the
    logits are then those of the last prompt token alone, (B, 1, V)."""
    ctx = ctx or Ctx()
    off = jnp.asarray(off, jnp.int32)
    x = ctx.anchor(_embed(params, cfg, tokens))
    new_caches = {}
    stages = cfg.stages()

    for si, (where, kind, n) in enumerate(stages):
        if where != "prelude":
            continue
        kf = KINDS[kind]

        def body(h, inp, kf=kf):
            lp, cache = inp
            h, new = kf.apply_prefill_chunk(lp, ctx.anchor(h), cfg, ctx,
                                            cache, off, n_valid=n_valid)
            return ctx.anchor(h), new

        x, new = _scan_stage(body, x, (params["stages"][f"s{si}_{kind}"],
                                       caches[f"s{si}_{kind}"]))
        new_caches[f"s{si}_{kind}"] = new

    unit_stages = [(si, kind) for si, (w, kind, n) in enumerate(stages)
                   if w == "unit"]
    if unit_stages:
        def unit_body(h, inp):
            unit_params, unit_caches = inp
            new_unit = {}
            for si, kind in unit_stages:
                kf = KINDS[kind]

                def body(hh, pc, kf=kf):
                    lp, cache = pc
                    hh, new = kf.apply_prefill_chunk(lp, ctx.anchor(hh), cfg,
                                                     ctx, cache, off,
                                                     n_valid=n_valid)
                    return ctx.anchor(hh), new

                h, new = jax.lax.scan(
                    body, h, (unit_params[f"s{si}_{kind}"],
                              unit_caches[f"s{si}_{kind}"]))
                new_unit[f"s{si}_{kind}"] = new
            if cfg.shared_attn:
                h, new_sh = KINDS["attn"].apply_prefill_chunk(
                    params["shared_attn"], h, cfg, ctx,
                    unit_caches["shared_attn"], off, n_valid=n_valid)
                new_unit["shared_attn"] = new_sh
            return h, new_unit

        unit_params = {f"s{si}_{kind}": params["stages"][f"s{si}_{kind}"]
                       for si, kind in unit_stages}
        unit_caches = {k: caches[k] for k in
                       [f"s{si}_{kind}" for si, kind in unit_stages]}
        if cfg.shared_attn:
            unit_caches["shared_attn"] = caches["shared_attn"]
        x, new_unit = jax.lax.scan(unit_body, x, (unit_params, unit_caches))
        new_caches.update(new_unit)

    if not with_logits:
        return None, new_caches
    if n_valid is not None:
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    return _logits(params, cfg, ctx.anchor(x)), new_caches


# ---------------------------------------------------------------------------
# Decode (one token) with stacked caches mirroring the stage structure.
# ---------------------------------------------------------------------------

def init_lm_cache(cfg: LMConfig, batch: int, max_len: int):
    caches = {}
    for si, (where, kind, n) in enumerate(cfg.stages()):
        kf = KINDS[kind]
        one = lambda: kf.cache_init(batch, max_len, cfg)
        if where == "prelude":
            caches[f"s{si}_{kind}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *([one()] * n)) if n > 1 else \
                jax.tree.map(lambda a: a[None], one())
        else:
            base = one()
            caches[f"s{si}_{kind}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None, None], (cfg.n_units, n) + a.shape).copy(), base)
    if cfg.shared_attn:
        caches["shared_attn"] = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[None], (cfg.n_units,) + a.shape).copy(),
            KINDS["attn"].cache_init(batch, max_len, cfg))
    return caches


def lm_decode_step(params, cfg: LMConfig, token, caches, *, ctx: Ctx = None,
                   enc_kv=None):
    """token: (B, 1) int32.  Returns (logits (B,1,V), new_caches)."""
    ctx = ctx or Ctx()
    x = ctx.anchor(_embed(params, cfg, token))
    new_caches = {}
    stages = cfg.stages()

    for si, (where, kind, n) in enumerate(stages):
        if where != "prelude":
            continue
        kf = KINDS[kind]

        def body(h, inp):
            lp, cache = inp
            h, new = kf.apply_decode(lp, h, cfg, ctx, cache, enc_kv=enc_kv)
            return h, new

        x, new = _scan_stage(body, x, (params["stages"][f"s{si}_{kind}"],
                                       caches[f"s{si}_{kind}"]))
        new_caches[f"s{si}_{kind}"] = new

    unit_stages = [(si, kind) for si, (w, kind, n) in enumerate(stages)
                   if w == "unit"]
    if unit_stages:
        def unit_body(h, inp):
            unit_params, unit_caches = inp
            new_unit = {}
            for si, kind in unit_stages:
                kf = KINDS[kind]

                def body(hh, pc, kf=kf):
                    lp, cache = pc
                    hh, new = kf.apply_decode(lp, hh, cfg, ctx, cache,
                                              enc_kv=enc_kv)
                    return hh, new

                h, new = jax.lax.scan(
                    body, h, (unit_params[f"s{si}_{kind}"],
                              unit_caches[f"s{si}_{kind}"]))
                new_unit[f"s{si}_{kind}"] = new
            if cfg.shared_attn:
                h, new_sh = KINDS["attn"].apply_decode(
                    params["shared_attn"], h, cfg, ctx,
                    unit_caches["shared_attn"])
                new_unit["shared_attn"] = new_sh
            return h, new_unit

        unit_params = {f"s{si}_{kind}": params["stages"][f"s{si}_{kind}"]
                       for si, kind in unit_stages}
        unit_caches = {k: caches[k] for k in
                       [f"s{si}_{kind}" for si, kind in unit_stages]}
        if cfg.shared_attn:
            unit_caches["shared_attn"] = caches["shared_attn"]
        x, new_unit = jax.lax.scan(unit_body, x, (unit_params, unit_caches))
        new_caches.update(new_unit)

    return _logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# Parameter counting.
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return sum(int(a.size) for a in jax.tree.leaves(params))


def count_active_params(cfg: LMConfig) -> int:
    """6·N·D convention: N = active params (MoE: top-k experts only)."""
    total = 0
    d = cfg.d_model
    hd = cfg.hd
    attn_p = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd \
        + cfg.n_heads * hd * d
    ffn_p = (3 if cfg.mlp == "swiglu" else 2) * d * cfg.d_ff

    for where, kind, n in cfg.stages():
        reps = n if where == "prelude" else n * cfg.n_units
        if kind == "attn":
            total += reps * (attn_p + ffn_p)
        elif kind == "attn_moe":
            mcfg = _moe_cfg(cfg)
            total += reps * (attn_p + moe_mod.moe_active_param_count(mcfg))
        elif kind == "xattn":
            total += reps * (2 * attn_p + ffn_p)
        elif kind in ("mamba", "mamba_ffn"):
            mc = _mamba_cfg(cfg)
            total += reps * (d * (2 * mc.d_inner + 2 * mc.d_state
                                  + mc.n_heads) + mc.d_inner * d
                             + (ffn_p if kind == "mamba_ffn" else 0))
        elif kind == "mlstm":
            mc = _mlstm_cfg(cfg)
            total += reps * (d * (4 * mc.d_inner + 2 * mc.n_heads)
                             + mc.d_inner * d)
        elif kind == "slstm":
            sc = _slstm_cfg(cfg)
            total += reps * (4 * d * d + 4 * d * sc.head_dim + d * d)
        elif kind == "gspn":
            total += reps * (gspn_seq_param_count(cfg) + ffn_p)
    if cfg.shared_attn:
        total += attn_p + ffn_p          # one weight set
    total += cfg.vocab * d               # embed
    if not cfg.tie_embeddings:
        total += cfg.vocab * d
    return total


def gspn_seq_param_count(cfg: LMConfig) -> int:
    cp = cfg.gspn_proxy_dim
    d = cfg.d_model
    return d * cp + d * 3 + d + d * 2 * cp * 2 + cp * d
