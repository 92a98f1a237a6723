"""GSPN-2 vision backbone (the paper's own architecture, §5.2).

Hierarchical 4-stage design: conv stem → [GSPN2 block × depth_i] with
2× downsampling between stages → pooled classifier head.  Each block is
LPU (depthwise 3×3, per CMT) → GSPN-2 attention (channel-shared taps +
compressive proxy, paper §4.2) → FFN, all pre-norm with residuals —
mirroring the paper's ImageNet configuration (C_proxy = 2, LPU at block
and FFN entry).

The attention module's four directional scans run through the fused
opposite-pair dispatch (two kernel launches per block instead of four —
DESIGN.md §2); ``GSPNVisionConfig.impl`` selects the kernel path
(``auto``/``pallas``/``multidir``/``xla``, see ``repro.kernels.ops``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import gspn as gspn_core
from repro.models.layers import (DTypePolicy, dense_init,
                                 init_layernorm, apply_layernorm,
                                 init_gelu_mlp, apply_gelu_mlp,
                                 init_dwconv2d, apply_dwconv2d)


@dataclasses.dataclass(frozen=True)
class GSPNVisionConfig:
    name: str = "gspn2-t"
    img_size: int = 224
    in_chans: int = 3
    n_classes: int = 1000
    dims: Sequence[int] = (64, 128, 320, 512)
    depths: Sequence[int] = (3, 4, 12, 5)
    proxy_dim: int = 2                 # paper ImageNet setting
    mlp_ratio: float = 4.0
    channel_shared: bool = True        # GSPN-2 compact channel propagation
    chunk: int | None = None           # GSPN-local
    impl: str = "auto"                 # "sp" shards each scan over seq_axis
    seq_axis: str = "seq"            # mesh axis for impl="sp" (DESIGN.md §8)
    sp_strategy: str = "auto"
    param_dtype: jnp.dtype = jnp.float32

    @property
    def policy(self):
        return DTypePolicy(self.param_dtype, jnp.float32)


def _conv_init(key, k, cin, cout, dtype):
    scale = 1.0 / math.sqrt(k * k * cin)
    w = jax.random.truncated_normal(key, -2, 2, (k, k, cin, cout),
                                    jnp.float32) * scale
    return {"w": w.astype(dtype), "b": jnp.zeros((cout,), dtype)}


def _conv_apply(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), p["w"].astype(jnp.float32),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return (y + p["b"].astype(jnp.float32)).astype(x.dtype)


def _gspn_attn_cfg(cfg: GSPNVisionConfig, dim: int):
    return gspn_core.GSPNAttentionConfig(
        dim=dim, proxy_dim=cfg.proxy_dim,
        channel_shared=cfg.channel_shared, chunk=cfg.chunk, impl=cfg.impl,
        seq_axis=cfg.seq_axis, sp_strategy=cfg.sp_strategy,
        param_dtype=cfg.param_dtype)


def _init_block(key, cfg: GSPNVisionConfig, dim: int):
    ks = jax.random.split(key, 4)
    hidden = int(dim * cfg.mlp_ratio)
    return {
        "lpu": init_dwconv2d(ks[0], dim, 3, cfg.param_dtype),
        "ln1": init_layernorm(dim, cfg.param_dtype),
        "gspn": gspn_core.init_gspn_attention(ks[1], _gspn_attn_cfg(cfg, dim)),
        "lpu2": init_dwconv2d(ks[2], dim, 3, cfg.param_dtype),
        "ln2": init_layernorm(dim, cfg.param_dtype),
        "mlp": init_gelu_mlp(ks[3], dim, hidden, cfg.param_dtype),
    }


def activation_spec(cfg: GSPNVisionConfig, ctx, shape):
    """Layout of a (B, H, W, C) activation on ``ctx.mesh``: batch over dp
    AND channels over the model axis.  A dp-only anchor killed the 10.7
    GB/step of reshard all-gathers but forfeited channel TP (measured 12×
    redundant compute on img_train_224); anchoring both dims keeps the
    partitioner in the batch×channel hybrid layout that matches the FFN
    weight sharding.  With ``impl="sp"`` the image rows are also split
    over the seq axis, so every layer's activations, not only the scans,
    are divided among the devices (the path that lets a grid exceed one
    device's memory)."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import sanitize_spec
    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    rows = cfg.seq_axis if cfg.impl == "sp" else None
    spec = (dp, rows) + (None,) * (len(shape) - 3) + (ctx.model_axis,)
    return sanitize_spec(P(*spec), shape, ctx.mesh)


def _anchor(x, ctx, cfg: GSPNVisionConfig):
    """Constrain an activation to :func:`activation_spec`."""
    if ctx is None or ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, activation_spec(cfg, ctx, x.shape)))


def _apply_block(p, x, cfg: GSPNVisionConfig, dim: int, ctx=None):
    x = _anchor(x, ctx, cfg)
    x = x + apply_dwconv2d(p["lpu"], x)                       # LPU
    h = apply_layernorm(p["ln1"], x)
    # impl="sp" shards every directional scan over the mesh's seq axis
    # (one boundary-column exchange per scan, DESIGN.md §8) — the path
    # that lets high-resolution grids exceed one device's memory.
    x = x + gspn_core.apply_gspn_attention(
        p["gspn"], h, _gspn_attn_cfg(cfg, dim),
        mesh=ctx.mesh if ctx is not None else None)
    x = _anchor(x, ctx, cfg)
    x = x + apply_dwconv2d(p["lpu2"], x)                      # LPU before FFN
    h = apply_layernorm(p["ln2"], x)
    b, hh, ww, c = h.shape
    y = apply_gelu_mlp(p["mlp"], h.reshape(b, hh * ww, c), cfg.policy)
    return _anchor(x + y.reshape(b, hh, ww, c), ctx, cfg)


def init_vision(key, cfg: GSPNVisionConfig):
    params = {}
    k_stem, k_stages, k_head = jax.random.split(key, 3)
    params["stem"] = _conv_init(k_stem, 4, cfg.in_chans, cfg.dims[0],
                                cfg.param_dtype)
    stages = []
    for si, (dim, depth) in enumerate(zip(cfg.dims, cfg.depths)):
        ks = jax.random.split(jax.random.fold_in(k_stages, si), depth)
        blocks = jax.vmap(lambda k: _init_block(k, cfg, dim))(ks)
        stage = {"blocks": blocks}
        if si + 1 < len(cfg.dims):
            stage["down"] = _conv_init(jax.random.fold_in(k_stages, 100 + si),
                                       2, dim, cfg.dims[si + 1],
                                       cfg.param_dtype)
        stages.append(stage)
    params["stages"] = stages
    params["ln_f"] = init_layernorm(cfg.dims[-1], cfg.param_dtype)
    params["head"] = dense_init(k_head, cfg.dims[-1], cfg.n_classes,
                                cfg.param_dtype)
    return params


def apply_vision(params, x, cfg: GSPNVisionConfig, ctx=None):
    """x: (B, H, W, 3) -> logits (B, n_classes)."""
    x = _anchor(_conv_apply(params["stem"], x, 4), ctx, cfg)
    for si, (dim, depth) in enumerate(zip(cfg.dims, cfg.depths)):
        stage = params["stages"][si]

        def body(h, block_params, dim=dim):
            return _apply_block(block_params, h, cfg, dim, ctx=ctx), None

        x, _ = jax.lax.scan(body, x, stage["blocks"])
        if "down" in stage:
            x = _anchor(_conv_apply(stage["down"], x, 2), ctx, cfg)
    x = apply_layernorm(params["ln_f"], x)
    x = jnp.mean(x, axis=(1, 2))
    return (x.astype(jnp.float32)
            @ params["head"].astype(jnp.float32))


def vision_loss(params, cfg: GSPNVisionConfig, batch, ctx=None):
    logits = apply_vision(params, batch["images"], cfg, ctx=ctx)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    return nll, {"ce": nll}


def vision_macs(cfg: GSPNVisionConfig) -> int:
    """Approximate multiply-accumulates for one image (Table 2 analogue)."""
    h = w = cfg.img_size // 4
    macs = (cfg.img_size // 4) ** 2 * 16 * cfg.in_chans * cfg.dims[0]
    for si, (dim, depth) in enumerate(zip(cfg.dims, cfg.depths)):
        n = h * w
        acfg = _gspn_attn_cfg(cfg, dim)
        nd = len(acfg.directions)
        cp = acfg.proxy_dim
        per_block = (
            n * dim * 9 * 2                               # two LPUs
            + n * gspn_core.gspn_attention_param_count(acfg)  # projections
            + nd * n * cp * 4                             # scan FMAs
            + 2 * n * dim * int(dim * cfg.mlp_ratio))     # MLP
        macs += depth * per_block
        if si + 1 < len(cfg.dims):
            macs += (h // 2) * (w // 2) * 4 * dim * cfg.dims[si + 1]
            h, w = h // 2, w // 2
    macs += cfg.dims[-1] * cfg.n_classes
    return macs
