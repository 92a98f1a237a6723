"""Plain reference of the GSPN-2 vision backbone (arXiv:2512.07884,
section 5.2 and Table 2) and of its AdamW training step, in float32
``jax.numpy`` at the highest matmul precision.

Backbone: a 4x4 stride-4 convolution stem, four stages of blocks with a
2x2 stride-2 convolution between stages, a final LayerNorm, global mean
pooling and a linear head.  Each block is pre-norm::

    x = x + DWConv3x3(x)                       (local perception unit)
    x = x + GSPN2(LayerNorm(x))
    x = x + DWConv3x3(x)
    x = x + MLP(LayerNorm(x))                  (fc, GELU, fc; ratio 4)

GSPN2 (compact channel propagation): ``x_p = h W_down`` (proxy channels),
per direction d of (top-to-bottom, bottom-to-top, left-to-right,
right-to-left) three tap logits shared by the proxy channels, a gate
``lam_d = sigmoid(h W_lam)`` and an output weight ``u_d = h W_u``.  In
the scan geometry of direction d (rows in scan order, the neighbours of
column j are j-1, j, j+1 of the previous row)::

    (wl, wc, wr) = softmax(taps) with the left tap masked at the first
                   column and the right tap at the last
    a[i, j] = wl a[i-1, j-1] + wc a[i-1, j] + wr a[i-1, j+1]
              + lam_d x_p[i, j],      a[-1] = 0

and the module's output is ``(sum_d u_d a_d) W_up``.  Departure noted:
GELU is the tanh approximation, as the program states it.

The reference takes the weights the benchmark made and imports nothing
of the program.  ``num`` (``benchlib.quant.Numerics``) says how it
computes: ``quant.REFERENCE`` (float32, highest matmul precision) for the
reference, a step below it for a control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchlib.quant import REFERENCE

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ORDER = ("tb", "bt", "lr", "rl")


def _conv(x, w, b, stride, num, groups=1):
    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(stride, stride),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HI)
    return num.product(conv, x, w) + b


def _ln(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _mm(a, w, num):
    return num.product(functools.partial(jnp.matmul, precision=HI), a, w)


def _orient(a, d):
    """(B, H, W, ...) in image orientation -> scan geometry of ``d``."""
    if d in ("lr", "rl"):
        a = jnp.swapaxes(a, 1, 2)
    if d in ("bt", "rl"):
        a = jnp.flip(a, 1)
    return a


def _unorient(a, d):
    if d in ("bt", "rl"):
        a = jnp.flip(a, 1)
    if d in ("lr", "rl"):
        a = jnp.swapaxes(a, 1, 2)
    return a


def _scan(x, t, lam, rnd):
    """x, lam (B, H, W, C); t (B, H, W, 3) logits, in scan geometry;
    ``rnd`` rounds the streams and the carry."""
    w = t.shape[2]
    j = jnp.arange(w)
    neg = jnp.finfo(jnp.float32).min
    mask = jnp.stack([jnp.where(j == 0, neg, 0.0), jnp.zeros(w),
                      jnp.where(j == w - 1, neg, 0.0)], -1)
    z = rnd(jax.nn.softmax(t + mask, axis=-1))

    def row(prev, inp):
        zi, xi, li = inp
        zero = jnp.zeros_like(prev[:, :1])
        left = jnp.concatenate([zero, prev[:, :-1]], axis=1)
        right = jnp.concatenate([prev[:, 1:], zero], axis=1)
        a = rnd(zi[..., 0:1] * left + zi[..., 1:2] * prev
                + zi[..., 2:3] * right + li * xi)
        return a, a

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (z, rnd(x), rnd(lam)))
    _, out = jax.lax.scan(row, jnp.zeros_like(x[:, 0]), rows)
    return jnp.moveaxis(out, 0, 1)


def gspn2(p, h, cp, channel_shared, num):
    x_p = _mm(h, p["down"], num)
    taps = _mm(h, p["w_taps"], num)
    lam = jax.nn.sigmoid(_mm(h, p["w_lam"], num))
    u = _mm(h, p["w_u"], num)
    out = 0.0
    for d_idx, d in enumerate(ORDER):
        sl = slice(cp * d_idx, cp * (d_idx + 1))
        if channel_shared:
            t = taps[..., 3 * d_idx:3 * d_idx + 3]
            a = _scan(_orient(x_p, d), _orient(t, d),
                      _orient(lam[..., sl], d), num.round_scan)
        else:
            t = taps[..., 3 * cp * d_idx:3 * cp * (d_idx + 1)]
            t = t.reshape(t.shape[:3] + (cp, 3))
            a = jnp.stack([
                _scan(_orient(x_p[..., c:c + 1], d),
                      _orient(t[..., c, :], d),
                      _orient(lam[..., sl][..., c:c + 1], d),
                      num.round_scan)[..., 0]
                for c in range(cp)], axis=-1)
        out = out + u[..., sl] * _unorient(a, d)
    return _mm(out, p["up"], num)


def block(p, x, cp, channel_shared, num):
    c = x.shape[-1]
    x = x + _conv(x, p["lpu"]["w"], p["lpu"]["b"], 1, num, groups=c)
    x = x + gspn2(p["gspn"], _ln(x, p["ln1"]), cp, channel_shared, num)
    x = x + _conv(x, p["lpu2"]["w"], p["lpu2"]["b"], 1, num, groups=c)
    m = p["mlp"]
    h = jax.nn.gelu(_mm(_ln(x, p["ln2"]), m["fc1"], num) + m["b1"],
                    approximate=True)
    return x + _mm(h, m["fc2"], num) + m["b2"]


def forward(params, cfg, images, num=REFERENCE):
    """images (B, S, S, 3) -> logits (B, n_classes), float32."""
    cp, shared = cfg["proxy_dim"], cfg["channel_shared"]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = _conv(images.astype(jnp.float32), params["stem"]["w"],
              params["stem"]["b"], 4, num)
    for stage in params["stages"]:
        x, _ = jax.lax.scan(lambda h, p: (block(p, h, cp, shared, num), None),
                            x, stage["blocks"])
        if "down" in stage:
            x = _conv(x, stage["down"]["w"], stage["down"]["b"], 2, num)
    x = jnp.mean(_ln(x, params["ln_f"]), axis=(1, 2))
    return _mm(x, params["head"], num)


def loss(params, cfg, images, labels, num=REFERENCE):
    logp = jax.nn.log_softmax(forward(params, cfg, images, num), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _key(cfg):
    """The configuration's keys the forward pass reads (hashable)."""
    return (("proxy_dim", cfg["proxy_dim"]),
            ("channel_shared", cfg["channel_shared"]))


def _blocks(a, rows):
    if a.shape[0] % rows:
        raise ValueError(f"batch {a.shape[0]} is not a multiple of {rows}")
    return a.reshape((a.shape[0] // rows, rows) + a.shape[1:])


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def _loss_grad(params, cfg_items, images, labels, rows, num):
    cfg = dict(cfg_items)

    def one(acc, blk):
        val, g = jax.value_and_grad(loss)(params, cfg, blk[0], blk[1], num)
        return jax.tree.map(lambda s, x: s + x.astype(jnp.float32), acc,
                            (val, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params))
    (total, grads), _ = jax.lax.scan(
        one, zero, (_blocks(images, rows), _blocks(labels, rows)))
    k = images.shape[0] // rows
    return total / k, jax.tree.map(lambda a: a / k, grads)


def loss_and_grad(params, cfg, images, labels, block_rows, num=REFERENCE):
    """Mean loss and its gradient over the batch, ``block_rows`` images
    at a time (the mean of equal blocks' means), so that it fits."""
    return _loss_grad(params, _key(cfg), images, labels, block_rows, num)


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _logits(params, cfg_items, images, rows, num):
    out = jax.lax.map(lambda x: forward(params, dict(cfg_items), x, num),
                      _blocks(images, rows))
    return out.reshape((-1,) + out.shape[2:])


def logits(params, cfg, images, block_rows, num=REFERENCE):
    return _logits(params, _key(cfg), images, block_rows, num)


# ---------------------------------------------------------------------------
# AdamW (decoupled weight decay), as the configuration states it.
# ---------------------------------------------------------------------------

NO_DECAY = ("scale", "bias", "b1", "b2")


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def decays(path, leaf) -> bool:
    """Weight decay on leaves of two or more axes whose path names no norm
    gain or bias."""
    name = _path(path)
    return leaf.ndim >= 2 and not any(t in name for t in NO_DECAY)


def lr_at(opt, step):
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * t)))


@functools.partial(jax.jit, static_argnums=(0,))
def _adamw(opt_items, params, grads, state, lr, bc1, bc2):
    opt = dict(opt_items)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m, v = state
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(path, p, mm, vv):
        d = (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        if decays(path, p):
            d = d + opt["weight_decay"] * p
        return p - lr * d

    params = jax.tree_util.tree_map_with_path(upd, params, m, v)
    return params, (m, v), grads


def adamw_state(params):
    """Zero first and second moments."""
    return jax.jit(lambda p: (jax.tree.map(jnp.zeros_like, p),) * 2)(params)


def adamw_step(opt, params, grads, state, step):
    """One update at step ``step`` (1-based): global-norm clipping, then
    AdamW with bias correction; returns (params, state, clipped grads).
    ``state`` is the (m, v) pair of trees."""
    return _adamw(tuple(sorted(opt.items())), params, grads, state,
                  lr_at(opt, step), 1 - opt["b1"] ** step,
                  1 - opt["b2"] ** step)
