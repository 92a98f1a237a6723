"""Plain reference of Granite-4.0-H (``granitemoehybrid`` without experts),
in float32 ``jax.numpy`` at the highest matmul precision.

The configuration's ``layer_types`` orders the layers; every layer is
pre-norm with two scaled residual branches (r = ``residual_multiplier``)::

    x = x + r * Mixer(RMSNorm(x));  x = x + r * SwiGLU(RMSNorm(x))

The embeddings are multiplied by ``embedding_multiplier`` and the logits
(the tied embedding as the head, after a final RMSNorm) divided by
``logits_scaling``.

Attention: GQA with no position embedding (``position_embedding_type``
"nope") and softmax scale ``attention_multiplier``; causal.

Mamba-2 (one group, so B and C are shared by the heads)::

    [z, xBC, dt] = h W_in
    xBC = silu(causal depthwise conv of width d_conv over xBC, with bias)
    [x, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      (per head, S_{-1} = 0)
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z)) W_out                (gate before the norm)

The recurrence runs as a per-token ``lax.scan``; attention runs one block
of queries at a time against every key, so neither needs the L x L matrix
of all heads at once.  Departures from the published model: none in the
equations; the weights are random (made by the benchmark).

The reference takes the weights the benchmark made (the program's
parameter layout is the checkpoint format) and imports nothing of the
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchlib.quant import fp8, identity  # noqa: F401  (fp8: the control)

QUERY_BLOCK = 512           # attention query rows per block
HI = jax.lax.Precision.HIGHEST


def consts(cfg: dict) -> tuple:
    """The configuration's numbers the forward pass reads, hashable."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "rms_norm_eps",
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling")
    return tuple((k, cfg[k]) for k in keys) + (
        ("layer_types", tuple(cfg["layer_types"])),)


def _mm(q, a, b):
    return jnp.matmul(q(a), q(b.astype(jnp.float32)), precision=HI)


def _rmsnorm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _swiglu(p, h, q):
    return _mm(q, jax.nn.silu(_mm(q, h, p["gate"])) * _mm(q, h, p["up"]),
               p["down"])


def attention(p, h, c, q):
    """h (B, L, D), L a multiple of QUERY_BLOCK (or shorter than it)."""
    b, l, d = h.shape
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // hq
    qs = _mm(q, h, p["wq"]).reshape(b, l, hkv, hq // hkv, hd)
    ks = _mm(q, h, p["wk"]).reshape(b, l, hkv, hd)
    vs = _mm(q, h, p["wv"]).reshape(b, l, hkv, hd)
    qb = min(QUERY_BLOCK, l)
    kpos = jnp.arange(l)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qs, i * qb, qb, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ks, precision=HI)
        s = s * c["attention_multiplier"]
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", w, vs, precision=HI)

    out = jax.lax.map(block, jnp.arange(l // qb))       # (n, B, qb, ...)
    out = jnp.moveaxis(out, 0, 1).reshape(b, l, hq * hd)
    return _mm(q, out, p["wo"])


def mamba2(p, h, c, q):
    b, l, _ = h.shape
    nh, hd, ns = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    ng, kc = c["mamba_n_groups"], c["mamba_d_conv"]
    if ng != 1:
        raise ValueError("the reference covers one group (n_groups 1)")
    di = nh * hd
    proj = _mm(q, h, p["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * ns], \
        proj[..., 2 * di + 2 * ns:]
    w = p["conv"]["w"].astype(jnp.float32)[:, 0]          # (kc, C)
    pad = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + l] * w[j] for j in range(kc))
    xbc = jax.nn.silu(conv + p["conv"]["b"].astype(jnp.float32))
    x = xbc[..., :di].reshape(b, l, nh, hd)
    bm, cm = xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt + p["dt_bias"])              # (B, L, H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))         # (H,)

    def step(s, inp):
        xt, bt, ct, dtt = inp                  # (B,H,P) (B,N) (B,N) (B,H)
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt,
                          precision=HI))
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=HI)

    s0 = jnp.zeros((b, nh, hd, ns), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1)                             # (B, L, H, P)
    y = y + p["d_skip"][:, None] * x
    y = y.reshape(b, l, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"]["scale"], c["rms_norm_eps"])
    return _mm(q, y, p["out_proj"])


def layer(kind, p, x, c, q):
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    if kind == "attention":
        x = x + r * attention(p["attn"], h, c, q)
    else:
        x = x + r * mamba2(p["mix"], h, c, q)
    return x + r * _swiglu(p["ffn"], _rmsnorm(x, p["ln2"]["scale"], eps), q)


def _runs(layer_types):
    runs = []
    for t in layer_types:
        if runs and runs[-1][0] == t:
            runs[-1][1] += 1
        else:
            runs.append([t, 1])
    return runs


@functools.partial(jax.jit, static_argnums=(1, 3))
def hidden(params, cst, tokens, q=identity):
    """tokens (B, L) int32 -> (B, L, D) f32, the layers in ``layer_types``
    order; each run of layers of one kind is one compiled scan over the
    program's stacked stage, so one layer's activations live at a time."""
    c = dict(cst)
    x = params["embed"][tokens].astype(jnp.float32)
    x = x * c["embedding_multiplier"]
    stages = sorted(params["stages"].items(),
                    key=lambda kv: int(kv[0].split("_")[0][1:]))
    runs = _runs(c["layer_types"])
    if len(runs) != len(stages):
        raise ValueError(f"{len(runs)} runs of layer_types, {len(stages)} "
                         f"stages in the weights")
    for (kind, n), (_name, st) in zip(runs, stages):
        if jax.tree.leaves(st)[0].shape[0] != n:
            raise ValueError(f"stage {_name} does not hold {n} layers")
        x, _ = jax.lax.scan(lambda h, p, k=kind: (layer(k, p, h, c, q), None),
                            x, st)
    return x


@jax.jit
def rows_at(hid, pos):
    """hid (B, L, D), pos (B, K) -> the hidden rows at ``pos``, (B*K, D)."""
    sel = jnp.take_along_axis(hid, pos[..., None], axis=1)
    return sel.reshape(-1, hid.shape[-1])


@functools.partial(jax.jit, static_argnums=(1, 3))
def head_logits(params, cst, hid, q=identity):
    """The logits (..., V) of hidden rows ``hid`` (..., D)."""
    c = dict(cst)
    out = _mm(q, _rmsnorm(hid, params["ln_f"]["scale"], c["rms_norm_eps"]),
              params["embed"].T)
    return out / c["logits_scaling"]


def logits(params, cst, tokens):
    """Full logits (B, L, V) of a short sequence (tests)."""
    return head_logits(params, cst, hidden(params, cst, tokens))
