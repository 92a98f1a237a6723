"""Bytes and operations of a Mamba-2 + attention hybrid (Granite-4.0-H),
from the published configuration alone.

Nothing here reads the program: the counts follow the configuration's
``config.json`` keys, so a change to the program can not move them.

Decode step (one token for each of ``rows`` occupied slots): the least
bytes it must move are every weight once (bf16), the SSM state and conv
tail of each occupied row read and written (f32, the carry dtype), and
the KV of the ``kv_tokens`` positions the rows attend to read once
(bf16).

Prefill of ``t`` tokens at offset ``o`` (a chunk, or a whole prompt at
o = 0): 2 operations per weight a token meets (the vocabulary head only
where logits are computed), plus per Mamba-2 layer the SSD chunk work
(scan chunks of ``l`` = min(mamba_chunk_size, t) tokens, halved until
they divide t, as the chunked form computes them)::

    C B^T per chunk        2 l^2 N G         (G groups, N d_state)
    masked (C B^T) X       2 l^2 H P         (H heads of dim P)
    chunk states B^T X     2 l N H P
    inter-chunk C S        2 l N H P

and per attention layer the logits and the weighted sum over the keys
each query sees: 4 H_q d_h (t o + t (t + 1) / 2).
"""

from __future__ import annotations

import dataclasses

WEIGHT_BYTES = 2            # bf16 weights
STATE_BYTES = 4             # f32 SSM state and conv tail
KV_BYTES = 2                # bf16 K and V


@dataclasses.dataclass(frozen=True)
class HybridCounts:
    d: int
    vocab: int
    ffn: int
    heads: int
    kv_heads: int
    head_dim: int
    m_heads: int
    m_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    chunk: int
    n_mamba: int
    n_attn: int
    tied: bool

    @classmethod
    def of(cls, cfg: dict) -> "HybridCounts":
        types = list(cfg["layer_types"])
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, vocab=cfg["vocab_size"],
                   ffn=cfg["shared_intermediate_size"], heads=h,
                   kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
                   m_heads=cfg["mamba_n_heads"],
                   m_head_dim=cfg["mamba_d_head"],
                   d_state=cfg["mamba_d_state"],
                   groups=cfg["mamba_n_groups"], d_conv=cfg["mamba_d_conv"],
                   chunk=cfg["mamba_chunk_size"],
                   n_mamba=types.count("mamba"),
                   n_attn=types.count("attention"),
                   tied=bool(cfg["tie_word_embeddings"]))

    # -- parameters -------------------------------------------------------
    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    def mamba_matmul_params(self) -> int:
        di = self.d_inner
        return self.d * (2 * di + 2 * self.groups * self.d_state
                         + self.m_heads) + di * self.d

    def attn_matmul_params(self) -> int:
        hd = self.head_dim
        return 2 * self.d * self.heads * hd + 2 * self.d * self.kv_heads * hd

    def ffn_params(self) -> int:
        return 3 * self.d * self.ffn

    def params(self) -> int:
        """Every parameter: the matmuls, the Mamba-2 conv (with bias),
        per-head scalars and gated-norm gain, the norms, the embedding."""
        mamba = (self.mamba_matmul_params() + self.conv_dim * (self.d_conv + 1)
                 + 3 * self.m_heads + self.d_inner)
        per_layer_norms = 2 * self.d
        n = (self.n_mamba * (mamba + self.ffn_params() + per_layer_norms)
             + self.n_attn * (self.attn_matmul_params() + self.ffn_params()
                              + per_layer_norms))
        n += self.vocab * self.d + self.d              # embedding, final norm
        if not self.tied:
            n += self.vocab * self.d
        return n

    # -- decode ------------------------------------------------------------
    def state_bytes_per_row(self) -> tuple[int, int]:
        """(SSM state, conv tail) bytes of one slot over the Mamba layers."""
        ssm = self.n_mamba * self.m_heads * self.m_head_dim * self.d_state
        conv = self.n_mamba * (self.d_conv - 1) * self.conv_dim
        return ssm * STATE_BYTES, conv * STATE_BYTES

    def kv_bytes_per_token(self) -> int:
        return self.n_attn * 2 * self.kv_heads * self.head_dim * KV_BYTES

    def decode_bytes(self, rows: int, kv_tokens: int) -> float:
        ssm, conv = self.state_bytes_per_row()
        return float(self.params() * WEIGHT_BYTES + 2 * rows * (ssm + conv)
                     + kv_tokens * self.kv_bytes_per_token())

    # -- prefill -----------------------------------------------------------
    def ssd_flops(self, t: int) -> float:
        """SSD chunk work of one Mamba-2 layer over ``t`` tokens."""
        l = min(self.chunk, t)
        while t % l:
            l //= 2
        n, hp = self.d_state, self.d_inner
        per_chunk = (2 * l * l * n * self.groups + 2 * l * l * hp
                     + 4 * l * n * hp)
        return float(per_chunk * (t // l))

    def attention_flops(self, t: int, offset: int) -> float:
        keys = t * offset + t * (t + 1) // 2
        return float(4 * self.heads * self.head_dim * keys)

    def prefill_flops(self, t: int, offset: int, logits: bool) -> float:
        mm = (self.n_mamba * (self.mamba_matmul_params() + self.ffn_params())
              + self.n_attn * (self.attn_matmul_params() + self.ffn_params()))
        f = 2.0 * t * mm
        f += self.n_mamba * self.ssd_flops(t)
        f += self.n_attn * self.attention_flops(t, offset)
        if logits:
            f += self.head_flops(t)
        return f

    def head_flops(self, rows: int) -> float:
        """The vocabulary head over ``rows`` positions."""
        return 2.0 * rows * self.vocab * self.d
