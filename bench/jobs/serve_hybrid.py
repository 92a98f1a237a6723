"""Serving job for a Mamba-2 + attention hybrid (Granite-4.0-H): the
engine's pool holds two kinds of state per slot, O(1) SSM state and conv
tail beside O(``max_len``) attention KV, and long prompts are prefilled in
chunks that resume the SSM state.

The window, the warm-up, the sample of requests checked and the
end-to-end numbers are the serving job's own (``bench/jobs/serve.py``,
imported by path): open-loop arrivals timed from their due time, every
program warmed in set-up, a drain after the window.  What differs is the
model: the configuration file holds the published ``config.json`` keys,
read here into the program's configuration, and its plain reference is
``bench/refs/granite_hybrid.py``.

``correct``: a sample of finished requests (the serving job's
``sample``) is replayed after the window through the engine's own
programs (``ServeEngine.score``: the prompt through the chunked prefill,
the served tokens fed to the batched decode step), which hands out the
logits from which each served token was chosen, for the first
``check_tokens`` tokens of each.  Two readings:

- ``logit_rel``: the widest relative L2 distance, over those positions,
  between the program's logits and the plain reference's
  (``bench/refs/granite_hybrid.py``), each row over the whole vocabulary.
  It reads every position, whether or not a token flips; the control is
  the reference computed with float8 matmul operands.
- ``served_gap``: the widest gap by which a served token's logit lies
  below the best of the replayed logits at its position: the window
  served what the programs compute (greedy), read from the same rows.

The log gives the distinct tokens among the checked ones, so a stream that
repeats one token shows.

Records for the per-layer readers: the pool's bytes by kind (the
program's ``serve_state_bytes_*`` gauges) and the byte and operation
counts of ``benchlib/hybrid_counts.py``; the engine's ``serve.decode_step``
spans carry ``rows`` and ``kv_tokens``, its ``serve.prefill_chunk`` spans
``tokens`` and ``offset``.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import hybrid_counts, registry, seeding, traffic, weights
from benchlib.harness import Check, JobOutput, memory_peak_bytes, scratch_dir
from benchlib.trace import Recorder

serve = registry.job("serve")

# Mamba-2's initialisation of its per-head scalars (the configuration's
# ``assumed``): A uniform in [1, 16], dt log-uniform in [0.001, 0.1].
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def program_config(cfg: dict):
    """The program's configuration from the published keys."""
    from repro.configs.base import with_precision
    from repro.configs.granite_4_0_h_micro import stages_of
    from repro.models.lm import LMConfig
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    fixed = {"mamba_expand": 2, "mamba_d_conv": 4, "mamba_n_groups": 1,
             "num_local_experts": 0, "position_embedding_type": "nope",
             "normalization_function": "rmsnorm", "hidden_act": "silu",
             "mamba_conv_bias": True, "mamba_proj_bias": False,
             "attention_bias": False}
    for k, v in fixed.items():
        if cfg[k] != v:
            raise ValueError(f"{k} = {cfg[k]!r}; the program computes {v!r}")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != 2 * d:
        raise ValueError("mamba_n_heads x mamba_d_head must be 2 x hidden")
    return with_precision(LMConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_seq=cfg["max_position_embeddings"],
        prelude=stages_of(cfg["layer_types"]),
        ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        gla_chunk=cfg["mamba_chunk_size"],
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        logit_divisor=float(cfg["logits_scaling"]),
        attn_scale=float(cfg["attention_multiplier"]), rope=False,
        norm_eps=float(cfg["rms_norm_eps"])), cfg["precision"])


def _mamba_scalars(key, mix):
    """A_log, dt_bias, D and the conv kernel of one stacked Mamba-2 stage,
    as Mamba-2 initialises them."""
    ka, kd, kc = jax.random.split(key, 3)
    a_log = mix["a_log"]
    a = jax.random.uniform(ka, a_log.shape, jnp.float32, *A_RANGE)
    lo, hi = (math.log(x) for x in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(kd, mix["dt_bias"].shape, jnp.float32,
                                    lo, hi))
    w = mix["conv"]["w"]
    conv_w = jax.random.normal(kc, w.shape, jnp.float32) / math.sqrt(
        w.shape[-3])
    return dict(mix, a_log=jnp.log(a).astype(a_log.dtype),
                dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(
                    mix["dt_bias"].dtype),
                d_skip=jnp.ones_like(mix["d_skip"]),
                conv=dict(mix["conv"], w=conv_w.astype(w.dtype)))


def make_weights(pcfg, seed: int):
    """The benchmark's seeded weights (``benchlib.weights``), with the
    Mamba-2 per-head scalars and conv kernel set as Mamba-2 sets them."""
    from repro.models.lm import init_lm
    shapes = jax.eval_shape(lambda k: init_lm(k, pcfg), jax.random.PRNGKey(0))
    key = seeding.key(seed, "weights")
    params = weights.make(key, shapes)

    def fix(params):
        stages = dict(params["stages"])
        for i, (name, st) in enumerate(sorted(stages.items())):
            if "a_log" in st.get("mix", {}):
                stages[name] = dict(st, mix=_mamba_scalars(
                    jax.random.fold_in(key, 1000 + i), st["mix"]))
        return dict(params, stages=stages)
    return jax.jit(fix, donate_argnums=0)(params)


@jax.jit
def _rel_rows(got, want):
    """Per row, |got - want| / |want| in the L2 norm."""
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.linalg.norm(want, axis=-1))


def logit_rel(params, cfg: dict, mix: dict, cases, rows=None, q=None):
    """Per case (prompt, tokens), the reference's logits at the position
    each token was chosen from, against ``rows`` (the program's, float32,
    one per token) or, with ``q`` (the control), against the reference
    computed under ``q``: the relative L2 distance of each row, all rows
    in one array.  Shapes are fixed by the mix, so the reference
    compiles once."""
    ref = registry.reference(cfg["model"])
    cst = ref.consts(cfg)
    blk = ref.QUERY_BLOCK
    length = -(-traffic.max_len(mix) // blk) * blk
    per, cap = mix["reference_rows"], mix["check_tokens"]
    out = []
    for s in range(0, len(cases), per):
        part = cases[s:s + per]
        toks = np.zeros((per, length), np.int32)
        pos = np.zeros((per, cap), np.int32)
        for i, (prompt, served) in enumerate(part):
            seq = np.concatenate([prompt, served[:-1]])
            toks[i, :len(seq)] = seq
            p = len(prompt)
            pos[i, :len(served)] = np.arange(p - 1, p - 1 + len(served))
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        want = ref.head_logits(params, cst, ref.rows_at(
            ref.hidden(params, cst, toks), pos))
        if q is not None:
            got = ref.head_logits(params, cst, ref.rows_at(
                ref.hidden(params, cst, toks, q), pos), q)
        for i, (_, served) in enumerate(part):
            a, n = i * cap, len(served)
            mine = got[a:a + n] if q is not None else jnp.asarray(rows[s + i])
            out.append(np.asarray(_rel_rows(mine, want[a:a + n])))
        del want
    return np.concatenate(out) if out else np.zeros(0)


def served_gaps(cases, rows):
    """Per served token, the best replayed logit at its position minus
    the token's own, all tokens in one array."""
    out = [r.max(-1) - r[np.arange(len(t)), t] for (_, t), r in zip(cases,
                                                                   rows)]
    return np.concatenate(out) if out else np.zeros(0)


def occupancy(served, seconds: float) -> dict:
    """Time means over the window of the slots decoding (a request from
    its first token to its last) and of the KV rows they hold (prompt and
    tokens so far)."""
    t0, end = served.t0, served.t0 + seconds
    slots = rows = 0.0
    for uid, times in served.times.items():
        n = served.prompt_len[uid]
        for i, (a, b) in enumerate(zip(times, times[1:])):
            dt = max(0.0, min(b, end) - max(a, t0))
            slots += dt
            rows += dt * (n + i + 1)
    return {"decoding_slots": slots / seconds, "kv_rows": rows / seconds}


def controls(seed: int, cfg: dict, mix: dict, records: dict) -> dict:
    """The control: the reference in float8 put in the program's place, on
    the prompts and tokens a run served."""
    ref = registry.reference(cfg["model"])
    params = make_weights(program_config(cfg), seed)
    rel = logit_rel(params, cfg, mix, records["cases"], q=ref.fp8)
    return {"fp8": {"logit_rel": float(rel.max())}}


def run(ctx) -> JobOutput:
    from repro import obs
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    pcfg = program_config(cfg)
    params = make_weights(pcfg, ctx.seed)
    reqs = traffic.serve_schedule(mix, ctx.seconds, ctx.seed,
                                  cfg["vocab_size"])
    served = serve.Served(t0=0.0, seconds=ctx.seconds,
                          due={r.uid: r.due for r in reqs}, submitted={},
                          tokens={}, times={}, ticks=0, lateness=[],
                          prompt_len={r.uid: len(r.prompt) for r in reqs})
    want_tokens = {r.uid: r.max_new_tokens for r in reqs}

    def stream(uid, tok):
        if uid < 0:
            return
        served.tokens.setdefault(uid, []).append(tok)
        served.times.setdefault(uid, []).append(time.perf_counter())
        if len(served.tokens[uid]) >= want_tokens[uid]:
            served.done[uid] = True

    engine = serve.make_engine(params, pcfg, mix, ctx.seed, stream=stream)
    if not engine.prefill_chunk:
        raise RuntimeError(f"{pcfg.name}: the engine took one-shot prefill")
    n_warm = serve.warm(engine, traffic.prompt_lengths(mix, ctx.seconds),
                        engine.prefill_chunk, cfg["vocab_size"], ctx.seed)
    engine = ctx.hook("engine", engine)
    setup_s = ctx.setup_done()
    pool_bytes = engine.pool.bytes_by_kind()
    ctx.log(f"set-up {setup_s:.3f} s; engine: {engine.bs} slots, chunk "
            f"{engine.prefill_chunk}, max_len {engine.max_len}; warmed with "
            f"{n_warm} requests; pool bytes by kind {pool_bytes}")
    ctx.log(f"traffic: {len(reqs)} requests due in {ctx.seconds} s, "
            f"longest (prompt, output) {traffic.longest(mix, ctx.seconds)}")
    trace_at = None
    if ctx.trace:
        obs.enable(ring=1 << 20)
        trace_at = (mix["trace_start"] * ctx.seconds, mix["trace_seconds"])
    c0 = ctx.compiles.snapshot()
    serve.drive(engine, reqs, ctx.seconds, drain_s=mix["drain_s"],
                drain=True, trace_at=trace_at,
                trace_dir=scratch_dir("trace") / ctx.cell.name, served=served)
    c1 = ctx.compiles.snapshot()
    e2e, missing, n_ttft, n_itl = serve.end_to_end(served, reqs, ctx.seconds)
    late = sorted(served.lateness)
    ctx.log("end to end: " + ", ".join(f"{k} {v:.3f}" for k, v in e2e.items()
                                       if v is not None))
    ctx.log(f"window: {served.ticks} ticks, {len(served.submitted)} "
            f"submitted, {sum(1 for u in served.times)} with a first "
            f"token, {sum(served.done.values())} finished; ttft over "
            f"{n_ttft} requests, itl over {n_itl} gaps; generator late "
            f"p50 {1e3 * late[len(late) // 2]:.3f} ms max "
            f"{1e3 * late[-1]:.3f} ms; compiles inside the window: "
            f"{c1[0] - c0[0]} (cache misses {c1[1] - c0[1]})")
    occ = occupancy(served, ctx.seconds)
    ctx.log(f"occupancy: {occ['decoding_slots']:.2f} of {engine.bs} slots "
            f"decoding, {occ['kv_rows']:.0f} of "
            f"{engine.bs * engine.max_len} KV rows held (time means over "
            f"the window)")
    records = {"served": served, "cases": None, "pool_bytes": pool_bytes,
               "counts": hybrid_counts.HybridCounts.of(cfg),
               "compiles_in_window": c1[0] - c0[0], "occupancy": occ}
    if ctx.trace:
        records["obs"] = obs.records()
        obs.disable()
    peak = memory_peak_bytes()
    picked = serve.sample(served, ctx.seed, mix["check_requests"])
    prompts = {r.uid: r.prompt for r in reqs}
    cases = [(prompts[u], np.asarray(served.tokens[u][:mix["check_tokens"]],
                                     np.int32)) for u in picked]
    records["cases"] = cases
    engine.reset()          # requests still decoding after the drain
    rows = engine.score(cases) if cases else []
    del engine
    gc.collect()
    gaps = served_gaps(cases, rows)
    rel = logit_rel(params, cfg, mix, cases, rows)
    del rows
    toks = np.concatenate([t for _, t in cases]) if cases else np.zeros(0)
    ctx.log(f"checked {len(cases)} requests (uids {picked}), {toks.size} "
            f"tokens, {np.unique(toks).size} distinct; logit_rel max "
            f"{rel.max() if rel.size else None} median "
            f"{np.median(rel) if rel.size else None}; served_gap max "
            f"{gaps.max() if gaps.size else None}")
    # no finished request to check reads as the largest distance there is
    limits = cfg["checks"]["serve"]
    e2e = {k: v for k, v in e2e.items() if v is not None}
    return JobOutput(
        attempted=len(reqs),
        failed=missing,
        end_to_end={"setup_s": setup_s, **e2e},
        checks={"logit_rel": Check(float(rel.max()) if rel.size else 1e30,
                                   limits["logit_rel"]),
                "served_gap": Check(float(gaps.max()) if gaps.size else 1e30,
                                    limits["served_gap"])},
        memory_peak_bytes=peak, records=records,
        trace=(Recorder(scratch_dir("trace") / ctx.cell.name).file()
               if ctx.trace else None))
