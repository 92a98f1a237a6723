"""Plain reference of the GSPN language model (DESIGN.md section 4), in
float32 ``jax.numpy`` at the highest matmul precision.

Each of ``n_layers`` layers is pre-norm::

    x = x + GSPNMixer(RMSNorm(x));  x = x + SwiGLU(RMSNorm(x))

and the vocabulary head is the tied embedding.  The mixer folds the
sequence row-major into a grid of ``row_width`` columns and projects each
token (width d_model) to ``proxy`` channels::

    x_p = h W_down;  t = h W_taps (3 logits);  g = sigmoid(h W_row)
    lam = sigmoid(h W_lam) (2 x proxy);  u = h W_u (2 x proxy)

Pass 1 (top to bottom over grid rows, taps shared by the channels)::

    (wl, wc, wr) = softmax(t) with the left tap masked at column 0 and
                   the right tap at the last column
    a[i, j] = wl a[i-1, j-1] + wc a[i-1, j] + wr a[i-1, j+1]
              + lam_1 x_p[i, j],                a[-1] = 0

Pass 2 (left to right within each grid row)::

    r[i, j] = g[i, j] r[i, j-1] + lam_2 x_p[i, j],  r[i, -1] = 0

and the mixer's output is ``(u_1 a + u_2 r) W_up``.  Both passes read only
earlier tokens, so the model is causal, and padding the sequence at its
end changes nothing before the padding.  Departures from the published
Qwen2 block: the GSPN mixer replaces attention (this repository's
design), so there are no attention heads and no rotary embedding.

The reference takes the weights the benchmark made (the program's
parameter layout is the checkpoint format) and imports nothing of the
program.  One compiled scan runs the layers, keeping one layer's
activations at a time, so it fits the chip next to the served weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchlib.quant import fp8, identity  # noqa: F401  (fp8: the control)

HEAD_BLOCK = 512            # logit rows per head program
EPS = 1e-6                  # RMSNorm epsilon


def _mm(q, a, b):
    return jnp.matmul(q(a), q(b.astype(jnp.float32)),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + EPS) * scale.astype(jnp.float32)


def _taps(t):
    """(..., W, 3) logits -> row-stochastic (wl, wc, wr), each (..., W)."""
    w = t.shape[-2]
    j = jnp.arange(w)
    neg = jnp.finfo(jnp.float32).min
    mask = jnp.stack([jnp.where(j == 0, neg, 0.0), jnp.zeros(w),
                      jnp.where(j == w - 1, neg, 0.0)], axis=-1)
    z = jax.nn.softmax(t + mask, axis=-1)
    return z[..., 0], z[..., 1], z[..., 2]


def _linear_scan(a, b, axis):
    """h_k = a_k h_{k-1} + b_k along ``axis``, h_{-1} = 0."""
    def combine(x, y):
        return x[0] * y[0], y[0] * x[1] + y[1]
    return jax.lax.associative_scan(combine, (a, b), axis=axis)[1]


def mixer(p, h, row_width, q=identity):
    """h: (B, L, D), L a multiple of ``row_width``."""
    b, l, _ = h.shape
    cp = p["down"].shape[-1]
    rows = l // row_width
    x_p = _mm(q, h, p["down"])
    t = _mm(q, h, p["w_taps"])
    g = jax.nn.sigmoid(_mm(q, h, p["w_row"]))
    lam = jax.nn.sigmoid(_mm(q, h, p["w_lam"]))
    u = _mm(q, h, p["w_u"])

    def grid(a):
        return a.reshape(b, rows, row_width, a.shape[-1])

    x_p, t, g, lam, u = map(grid, (x_p, t, g, lam, u))
    wl, wc, wr = (a[..., None] for a in _taps(t))      # (B, H, W, 1)
    zero = jnp.zeros((b, 1, cp), jnp.float32)
    prev = jnp.zeros((b, row_width, cp), jnp.float32)
    tb = []
    for i in range(rows):
        left = jnp.concatenate([zero, prev[:, :-1]], axis=1)   # a[i-1, j-1]
        right = jnp.concatenate([prev[:, 1:], zero], axis=1)   # a[i-1, j+1]
        prev = (wl[:, i] * left + wc[:, i] * prev + wr[:, i] * right
                + lam[:, i, :, :cp] * x_p[:, i])
        tb.append(prev)
    tb = jnp.stack(tb, axis=1)
    row = _linear_scan(jnp.broadcast_to(g, x_p.shape), lam[..., cp:] * x_p,
                       axis=2)
    y = u[..., :cp] * tb + u[..., cp:] * row
    return _mm(q, y.reshape(b, l, cp), p["up"])


def layer(p, x, row_width, q=identity):
    x = x + mixer(p["mix"], _rmsnorm(x, p["ln1"]["scale"]), row_width, q)
    h = _rmsnorm(x, p["ln2"]["scale"])
    f = p["ffn"]
    return x + _mm(q, jax.nn.silu(_mm(q, h, f["gate"])) * _mm(q, h, f["up"]),
                   f["down"])


def _head(embed, ln_f, hid, q, tokens):
    """Per row: the best logit, the logit of ``tokens`` and the argmax."""
    logits = _mm(q, _rmsnorm(hid, ln_f), embed.T)
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best, mine, jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def hidden(params, tokens, row_width, q=identity):
    """tokens (B, L) int32, L a multiple of row_width -> (B, L, D) f32.
    One compiled scan over the stacked (units, layers, ...) weights."""
    x = params["embed"][tokens].astype(jnp.float32)
    for _name, st in sorted(params["stages"].items()):
        st = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), st)
        x, _ = jax.lax.scan(lambda h, p: (layer(p, h, row_width, q), None),
                            x, st)
    return x


@jax.jit
def rows_at(hid, pos):
    """hid (B, L, D), pos (B, K) -> the hidden rows at ``pos``, (B*K, D)."""
    sel = jnp.take_along_axis(hid, pos[..., None], axis=1)
    return sel.reshape(-1, hid.shape[-1])


@functools.partial(jax.jit, static_argnums=(3,))
def head_rows(params, hid_rows, tokens, q=identity):
    """Best logit, the logit of ``tokens`` and the argmax, per row of
    ``hid_rows`` (N, D; N a multiple of HEAD_BLOCK), one block of rows
    at a time so that the logits of one block are live at once."""
    blocks = hid_rows.reshape(-1, HEAD_BLOCK, hid_rows.shape[-1])
    toks = tokens.reshape(-1, HEAD_BLOCK)
    embed, scale = params["embed"], params["ln_f"]["scale"]
    out = jax.lax.map(lambda a: _head(embed, scale, a[0], q, a[1]),
                      (blocks, toks))
    return tuple(o.reshape(-1) for o in out)
