"""granite-4.0-h-micro — Mamba-2 + NoPE GQA hybrid [hf: ibm-granite/granite-4.0-h-micro].

40L d_model=2048; ``layer_types`` puts attention (32 q heads, 8 KV heads,
head dim 64, no position embedding, softmax scale 1/64) at layers 5, 15,
25 and 35 and Mamba-2 (64 heads of dim 64, d_state 128, one group, conv
4) at the other 36; every mixer is followed by a SwiGLU FFN of width
8192.  Embeddings x 12, each branch x 0.22 before it is added, logits / 8;
RMSNorm eps 1e-5, vocab 100352, tied embeddings.  About 3.19 B params.
"""

from repro.configs.base import ArchEntry, register
from repro.models.lm import LMConfig

_KIND = {"mamba": "mamba_ffn", "attention": "attn"}

LAYER_TYPES = tuple("attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40))


def stages_of(layer_types) -> tuple:
    """``layer_types`` as run-length ``(kind, n)`` stages, in order."""
    out = []
    for t in layer_types:
        kind = _KIND[t]
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return tuple(out)


def full(n_model_shards: int = 1) -> LMConfig:
    return LMConfig(
        name="granite-4.0-h-micro", family="hybrid",
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, vocab=100352, tie_embeddings=True, max_seq=131072,
        prelude=stages_of(LAYER_TYPES),
        ssm_state=128, ssm_head_dim=64, gla_chunk=256,
        embed_scale=12.0, residual_scale=0.22, logit_divisor=8.0,
        attn_scale=0.015625, rope=False, norm_eps=1e-5,
        n_model_shards=n_model_shards,
    )


def reduced() -> LMConfig:
    return LMConfig(
        name="granite-4.0-h-reduced", family="hybrid",
        n_layers=7, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=256, tie_embeddings=True, max_seq=512,
        prelude=stages_of(("mamba", "mamba", "attention", "mamba",
                           "mamba", "attention", "mamba")),
        ssm_state=16, ssm_head_dim=16, gla_chunk=8,
        embed_scale=12.0, residual_scale=0.22, logit_divisor=8.0,
        attn_scale=0.1, rope=False, norm_eps=1e-5, remat="none",
    )


register(ArchEntry(
    name="granite-4.0-h-micro", family="hybrid", full=full, reduced=reduced,
    skip_shapes={},   # Mamba-2 decode is O(1); 4 of 40 layers hold KV
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro"))
