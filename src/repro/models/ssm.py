"""State-space / gated-linear-attention token mixers.

:func:`chunked_gla` is the shared computational core — a chunk-parallel
evaluation of the gated linear recurrence

    S_t = a_t * S_{t-1} + k_t v_t^T          (per head; a_t scalar decay)
    y_t = q_t^T S_t

used by both Mamba2 (SSD: a_t = exp(A·dt_t)) and mLSTM (a_t = sigmoid
forget gate).  The chunked form computes intra-chunk contributions with a
masked (L×L) decay matrix and carries inter-chunk state with a short
``lax.scan`` — O(S·L) memory instead of O(S²), sequential depth S/L.

Decode-mode helpers advance the recurrent state one token at a time.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import (DTypePolicy, DEFAULT_POLICY, dense_init,
                                 init_rmsnorm, apply_rmsnorm,
                                 init_causal_conv1d, apply_causal_conv1d)


def chunked_gla(q, k, v, log_decay, chunk: int = 256, initial_state=None):
    """Gated linear attention, chunk-parallel.

    q, k: (B, S, H, Dk); v: (B, S, H, Dv); log_decay: (B, S, H) (≤ 0);
    ``initial_state`` (B, H, Dk, Dv), the state before the first token
    (zero when None) — a prompt chunk resumes from the state the previous
    chunk left.  Returns y: (B, S, H, Dv) f32 and final state (B, H, Dk, Dv).
    """
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    l = min(chunk, s)
    while s % l != 0:
        l //= 2
    nc = s // l

    qf = q.astype(jnp.float32).reshape(b, nc, l, h, dk)
    kf = k.astype(jnp.float32).reshape(b, nc, l, h, dk)
    vf = v.astype(jnp.float32).reshape(b, nc, l, h, dv)
    ld = log_decay.astype(jnp.float32).reshape(b, nc, l, h)
    cum = jnp.cumsum(ld, axis=2)                      # inclusive within chunk

    # Intra-chunk: att[i,j] = (q_i·k_j) exp(cum_i - cum_j), j <= i.
    att = jnp.einsum("bnihd,bnjhd->bnhij", qf, kf)
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (b,nc,l_i,l_j,h)
    dec = jnp.moveaxis(dec, -1, 2)                        # (b,nc,h,l_i,l_j)
    mask = jnp.tril(jnp.ones((l, l), bool))
    att = jnp.where(mask, att * jnp.exp(jnp.where(mask, dec, 0.0)), 0.0)
    y_intra = jnp.einsum("bnhij,bnjhd->bnihd", att, vf)

    # Inter-chunk state scan: k_sc[j] = k_j * exp(cum_L - cum_j).
    k_sc = kf * jnp.exp(cum[:, :, -1:, :] - cum)[..., None]
    chunk_kv = jnp.einsum("bnjhd,bnjhe->bnhde", k_sc, vf)   # (b,nc,h,dk,dv)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (b,nc,h)

    def scan_body(state, inp):
        kv_c, dec_c = inp                                   # (b,h,dk,dv),(b,h)
        new = state * dec_c[..., None, None] + kv_c
        return new, state                                   # emit state BEFORE chunk

    s0 = (jnp.zeros((b, h, dk, dv), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    final_state, states_before = jax.lax.scan(
        scan_body, s0,
        (jnp.moveaxis(chunk_kv, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    states_before = jnp.moveaxis(states_before, 0, 1)       # (b,nc,h,dk,dv)

    q_sc = qf * jnp.exp(cum)[..., None]                     # q_i exp(cum_i)
    y_inter = jnp.einsum("bnihd,bnhde->bnihe", q_sc, states_before)

    y = (y_intra + y_inter).reshape(b, s, h, dv)
    return y, final_state


def gla_decode_step(state, q, k, v, log_decay):
    """One-token GLA update.  state (B,H,Dk,Dv); q/k/v (B,H,D*);
    log_decay (B,H).  Returns (y (B,H,Dv), new_state)."""
    a = jnp.exp(log_decay.astype(jnp.float32))[..., None, None]
    new_state = state * a + jnp.einsum(
        "bhd,bhe->bhde", k.astype(jnp.float32), v.astype(jnp.float32))
    y = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), new_state)
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba2 block (SSD).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    dim: int
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    conv_k: int = 4
    chunk: int = 256
    norm_eps: float = 1e-6

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


def init_mamba2(key, cfg: Mamba2Config, dtype=jnp.float32):
    di, ns, nh = cfg.d_inner, cfg.d_state, cfg.n_heads
    ks = jax.random.split(key, 5)
    conv_dim = di + 2 * ns
    return {
        "in_proj": dense_init(ks[0], cfg.dim,
                              2 * di + 2 * ns + nh, dtype),
        "conv": init_causal_conv1d(ks[1], conv_dim, cfg.conv_k, dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "out_proj": dense_init(ks[2], di, cfg.dim, dtype),
    }


def _mamba2_inner(params, x, cfg: Mamba2Config, policy, conv_state=None,
                  ssm_state=None, n_valid=None):
    """Shared forward over x (B, S, D).  Without states it starts from zero
    state (prefill); with them it resumes: one token runs the decode
    update, a longer chunk the chunked scan from ``ssm_state``.  The conv
    tail (the last ``conv_k - 1`` raw xBC rows) carries across calls, also
    for chunks shorter than the tail.

    ``n_valid`` (traced scalar): only the first ``n_valid`` rows of a
    resumed chunk are tokens, the rest padding.  Padded rows get dt = 0,
    so the state passes through them unchanged, and the conv tail ends at
    the last token; their outputs are not meaningful."""
    b, s, _ = x.shape
    di, ns, nh, hd = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.head_dim
    p = policy.cast(params)
    proj = (x.astype(policy.compute_dtype) @ p["in_proj"]).astype(jnp.float32)
    z, xbc, dt_raw = jnp.split(proj, [di, 2 * di + 2 * ns], axis=-1)
    if conv_state is None:
        conv_state = jnp.zeros((b, cfg.conv_k - 1, xbc.shape[-1]),
                               jnp.float32)
    raw = xbc
    xbc, new_conv = apply_causal_conv1d(params["conv"], xbc, conv_state)
    if n_valid is not None:
        new_conv = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([conv_state.astype(jnp.float32), raw], axis=1),
            n_valid, cfg.conv_k - 1, axis=1)
    xbc = jax.nn.silu(xbc.astype(jnp.float32))
    x_ssm, bmat, cmat = jnp.split(xbc, [di, di + ns], axis=-1)

    dt = jax.nn.softplus(dt_raw + params["dt_bias"])        # (B,S,H)
    if n_valid is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < n_valid, dt, 0.0)
    log_a = -jnp.exp(params["a_log"])                        # (H,) < 0
    log_decay = log_a * dt                                   # (B,S,H)

    xh = x_ssm.reshape(b, s, nh, hd)
    v = xh * dt[..., None]                                   # fold dt into v
    k = jnp.broadcast_to(bmat[:, :, None, :], (b, s, nh, ns))
    q = jnp.broadcast_to(cmat[:, :, None, :], (b, s, nh, ns))

    if ssm_state is not None and s == 1:
        y, final_state = gla_decode_step(
            ssm_state, q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0])
        y = y[:, None]
    else:
        with jax.named_scope("mamba2.ssd"):
            y, final_state = chunked_gla(k=k, q=q, v=v, log_decay=log_decay,
                                         chunk=cfg.chunk,
                                         initial_state=ssm_state)

    y = y + params["d_skip"][None, None, :, None] * xh
    y = y.reshape(b, s, di)
    # Gated RMSNorm as published (Mamba-2's RMSNormGated with
    # norm_before_gate=False): the gate multiplies before the norm.
    y = apply_rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = (y.astype(policy.compute_dtype) @ p["out_proj"]).astype(x.dtype)
    return out, new_conv, final_state


def apply_mamba2(params, x, cfg: Mamba2Config,
                 policy: DTypePolicy = DEFAULT_POLICY):
    out, _, _ = _mamba2_inner(params, x, cfg, policy)
    return out


def apply_mamba2_prefill(params, x, cfg: Mamba2Config,
                         policy: DTypePolicy = DEFAULT_POLICY):
    """Forward over the prompt, returning the streaming cache."""
    out, new_conv, final_state = _mamba2_inner(params, x, cfg, policy)
    cache = {"conv": new_conv.astype(jnp.float32), "ssm": final_state}
    return out, cache


def apply_mamba2_decode(params, x, cfg: Mamba2Config, cache,
                        policy: DTypePolicy = DEFAULT_POLICY, n_valid=None):
    """x (B,T,D) resumed from cache {'conv': (B,k-1,conv_dim), 'ssm':
    (B,H,Dk,Dv)}: one decode token (T = 1) or a prompt chunk, of which
    the first ``n_valid`` rows are tokens (all when None)."""
    out, new_conv, new_ssm = _mamba2_inner(
        params, x, cfg, policy, conv_state=cache["conv"],
        ssm_state=cache["ssm"], n_valid=n_valid)
    return out, {"conv": new_conv.astype(cache["conv"].dtype),
                 "ssm": new_ssm}


def init_mamba2_cache(batch, cfg: Mamba2Config, dtype=jnp.float32):
    conv_dim = cfg.d_inner + 2 * cfg.d_state
    return {
        "conv": jnp.zeros((batch, cfg.conv_k - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.d_state, cfg.head_dim),
                         jnp.float32),
    }
