"""Serving job: ``ServeEngine`` under open-loop traffic for the window.

Set-up makes the weights from the seed, builds the engine as
``repro.launch.serve`` does, and warms exactly the programs this mix's
traffic will run: one one-shot prefill per prompt length up to the chunk,
the full chunk and one last chunk per tail length above it, the decode
step, and every slot of the pool.

The window is the engine's open-loop load generator, copied here so every
request is timed from the moment it was due (a late submit behind a long
``tick`` counts against the request).  Tokens are timed as the engine
streams them.  After the window the engine keeps ticking, with no new
arrivals, until every request due in the window has its first token (or
``drain_s`` has passed), so time to first token covers all requests.

``correct``: a sample of finished requests drawn from the seed, with the
one that served the most tokens and the one with the longest prompt in
it, goes through the plain reference once (prompt and served tokens), and
the reading is the widest gap by which a served token's logit lies below
the reference's best at its position.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import registry, seeding, traffic, weights
from benchlib.harness import Check, JobOutput, memory_peak_bytes, scratch_dir
from benchlib.trace import Recorder

LM_KEYS = ("name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
           "d_ff", "vocab", "tie_embeddings", "gspn_proxy_dim",
           "gspn_row_width", "n_units")


def program_config(cfg: dict):
    from repro.configs.base import with_precision
    from repro.models.lm import LMConfig
    kw = {k: cfg[k] for k in LM_KEYS}
    kw["unit"] = tuple((k, n) for k, n in cfg["unit"])
    return with_precision(LMConfig(**kw), cfg["precision"])


def make_weights(pcfg, seed: int):
    from repro.models.lm import init_lm
    shapes = jax.eval_shape(lambda k: init_lm(k, pcfg), jax.random.PRNGKey(0))
    return weights.make(seeding.key(seed, "weights"), shapes)


def make_engine(params, pcfg, mix: dict, seed: int, stream=None):
    from repro.serve.engine import ServeEngine
    eng = mix["engine"]
    return ServeEngine(params, pcfg, batch_size=eng["slots"],
                       max_len=traffic.max_len(mix),
                       temperature=eng["temperature"],
                       prefill_chunk=eng["prefill_chunk"], scheduler="fcfs",
                       seed=seeding.words(seed, "engine")[0], stream=stream)


def warm(engine, lengths, chunk: int, vocab: int, seed: int):
    """Run every program the window will run once: prompts of each length
    up to the chunk, one prompt per distinct last-chunk length above it,
    and enough requests at once to use every slot."""
    from repro.serve.engine import Request
    rng = seeding.rng(seed, "inputs")
    want = sorted({n for n in lengths if n <= chunk}
                  | {chunk + (n - 1) % chunk + 1 for n in lengths
                     if n > chunk})
    want += [min(lengths)] * max(0, engine.bs - len(want))
    for i, n in enumerate(want):
        engine.submit(Request(uid=-1 - i, prompt=rng.integers(
            0, vocab, n, dtype=np.int32), max_new_tokens=2))
    engine.run()
    engine.reset()
    return len(want)


@dataclasses.dataclass
class Served:
    """What the generator saw: per request its due time (seconds after the
    window opened), submit time and the time of every streamed token."""
    t0: float
    seconds: float
    due: dict
    submitted: dict
    tokens: dict              # uid -> [token ids]
    times: dict               # uid -> [perf_counter of each token]
    ticks: int
    lateness: list            # submit delay behind the due time, seconds
    prompt_len: dict = dataclasses.field(default_factory=dict)
    done: dict = dataclasses.field(default_factory=dict)  # uid -> finished
    trace_window: tuple = ()  # (start, end) perf_counter of the trace


def drive(engine, reqs, seconds: float, *, drain_s: float, drain: bool,
          trace_at=None, trace_dir=None, served: Served):
    """Open-loop load (after ``repro.serve.engine.drive``): submit each
    request when due, tick the engine in between.  ``trace_at`` =
    (start, length) in seconds traces that part of the window."""
    from repro.serve.engine import Request
    n = len(reqs)
    nxt = 0
    rec = None
    t0 = served.t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_at is not None and not served.trace_window \
                and now >= trace_at[0]:
            rec = Recorder(trace_dir).__enter__()
            served.trace_window = (time.perf_counter(),)
        elif rec is not None and now >= trace_at[0] + trace_at[1]:
            rec.__exit__(None, None, None)
            rec = None
            served.trace_window += (time.perf_counter(),)
        while nxt < n and reqs[nxt].due <= now:
            r = reqs[nxt]
            with jax.profiler.TraceAnnotation("submit"):
                engine.submit(Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=r.max_new_tokens))
            served.submitted[r.uid] = now
            served.lateness.append(now - r.due)
            nxt += 1
        if now >= seconds and rec is None and (
                not drain or now >= seconds + drain_s
                or all(served.times.get(r.uid) for r in reqs)):
            break
        if engine.idle:
            wait = (reqs[nxt].due - now) if nxt < n else 0.002
            with jax.profiler.TraceAnnotation("generator.wait"):
                time.sleep(max(0.0, min(wait, 0.002)))
            continue
        with jax.profiler.TraceAnnotation("engine.tick"):
            engine.tick()
        served.ticks += 1
    return served


def sample(served: Served, seed: int, k: int) -> list[int]:
    """Finished requests to check: the one that served the most tokens,
    the one with the longest prompt, and others drawn from the seed."""
    done = [u for u, toks in served.tokens.items() if served.done.get(u)]
    if not done:
        return []
    pick = {max(done, key=lambda u: (len(served.tokens[u]), u)),
            max(done, key=lambda u: (served.prompt_len[u], u))}
    rest = sorted(set(done) - pick)
    rng = seeding.rng(seed, "sample")
    extra = rng.choice(len(rest), size=min(max(k - len(pick), 0), len(rest)),
                       replace=False) if rest else []
    return sorted(pick | {rest[i] for i in extra})


def served_gaps(params, cfg: dict, mix: dict, cases, q=None):
    """Per case (prompt, served tokens): the reference's best logit minus
    the logit of each served token, at each served position.  With ``q``
    (the control), the token at each position is the one the reference
    computed under ``q`` puts first, and the gap is read in float32.
    Shapes are fixed by the mix (the longest sequence, the most served
    tokens), so the reference compiles once."""
    ref = registry.reference(cfg["model"])
    w = cfg["gspn_row_width"]
    length = -(-traffic.max_len(mix) // w) * w
    rows, cap = mix["reference_rows"], mix["output"]["max"]
    cap = -(-cap // ref.HEAD_BLOCK) * ref.HEAD_BLOCK
    gaps = []
    for s in range(0, len(cases), rows):
        part = cases[s:s + rows]
        toks = np.zeros((rows, length), np.int32)
        pos = np.zeros((rows, cap), np.int32)
        want = np.zeros((rows, cap), np.int32)
        valid = np.zeros((rows, cap), bool)
        for i, (prompt, served) in enumerate(part):
            seq = np.concatenate([prompt, served[:-1]])
            toks[i, :len(seq)] = seq
            n, p = len(served), len(prompt)
            pos[i, :n] = np.arange(p - 1, p - 1 + n)
            want[i, :n] = served
            valid[i, :n] = True
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        sel = ref.rows_at(ref.hidden(params, toks, w), pos)
        want = jnp.asarray(want.reshape(-1))
        if q is not None:
            qsel = ref.rows_at(ref.hidden(params, toks, w, q), pos)
            want = ref.head_rows(params, qsel, want, q)[2]
            del qsel
        best, mine, _ = ref.head_rows(params, sel, want)
        gaps.append(np.asarray(best - mine)[valid.reshape(-1)])
        del sel
    return np.concatenate(gaps) if gaps else np.zeros(0)


def controls(seed: int, cfg: dict, mix: dict, records: dict) -> dict:
    """The control: the reference in float8 put in the program's place, on
    the prompts and tokens a run served."""
    ref = registry.reference(cfg["model"])
    params = make_weights(program_config(cfg), seed)
    gaps = served_gaps(params, cfg, mix, records["cases"], q=ref.fp8)
    return {"fp8": {"logit_gap": float(gaps.max())}}


def end_to_end(served: Served, reqs, seconds: float):
    """Time to first token from each request's due time (median, and the
    95th percentile for the log) and the 95th percentile of the gaps
    between tokens that end inside the window."""
    t0, end = served.t0, served.t0 + seconds
    ttft, missing = [], 0
    last = max((t[-1] for t in served.times.values() if t), default=end)
    for r in reqs:
        t = served.times.get(r.uid)
        if t:
            ttft.append(t[0] - (t0 + r.due))
        else:
            missing += 1
            ttft.append(max(last, end) - (t0 + r.due))
    itl = [b - a for t in served.times.values()
           for a, b in zip(t, t[1:]) if b <= end]
    return {
        "ttft_p50_ms": 1e3 * traffic.percentile(ttft, 50),
        "ttft_p95_ms": 1e3 * traffic.percentile(ttft, 95),
        "itl_p95_ms": 1e3 * traffic.percentile(itl, 95) if itl else None,
    }, missing, len(ttft), len(itl)


def run(ctx) -> JobOutput:
    from repro import obs
    from repro.kernels import autotune
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    pcfg = program_config(cfg)
    params = make_weights(pcfg, ctx.seed)
    reqs = traffic.serve_schedule(mix, ctx.seconds, ctx.seed, cfg["vocab"])
    served = Served(t0=0.0, seconds=ctx.seconds,
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

    engine = make_engine(params, pcfg, mix, ctx.seed, stream=stream)
    n_warm = warm(engine, traffic.prompt_lengths(mix, ctx.seconds),
                  engine.prefill_chunk, cfg["vocab"], ctx.seed)
    engine = ctx.hook("engine", engine)
    setup_s = ctx.setup_done()
    ctx.log(f"set-up {setup_s:.3f} s; engine: {engine.bs} slots, chunk "
            f"{engine.prefill_chunk}, max_len {engine.max_len}; warmed with "
            f"{n_warm} requests; "
            f"kernel plans: {autotune.plans_summary()}")
    ctx.log(f"traffic: {len(reqs)} requests due in {ctx.seconds} s, "
            f"longest (prompt, output) {traffic.longest(mix, ctx.seconds)}")
    trace_at = None
    if ctx.trace:
        obs.enable(ring=1 << 20)
        trace_at = (mix["trace_start"] * ctx.seconds, mix["trace_seconds"])
    c0 = ctx.compiles.snapshot()
    drive(engine, reqs, ctx.seconds, drain_s=mix["drain_s"], drain=True,
          trace_at=trace_at,
          trace_dir=scratch_dir("trace") / ctx.cell.name, served=served)
    c1 = ctx.compiles.snapshot()
    e2e, missing, n_ttft, n_itl = end_to_end(served, reqs, ctx.seconds)
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
    records = {"served": served, "cases": None,
               "compiles_in_window": c1[0] - c0[0]}
    if ctx.trace:
        records["obs"] = obs.records()
        obs.disable()
    peak = memory_peak_bytes()
    picked = sample(served, ctx.seed, mix["check_requests"])
    prompts = {r.uid: r.prompt for r in reqs}
    cases = [(prompts[u], np.asarray(served.tokens[u], np.int32))
             for u in picked]
    records["cases"] = cases
    del engine
    gc.collect()
    gaps = served_gaps(params, cfg, mix, cases)
    ctx.log(f"checked {len(cases)} requests, {gaps.size} served tokens "
            f"(uids {picked})")
    # no finished request to check reads as the largest gap there is
    widest = float(gaps.max()) if gaps.size else 1e30
    e2e = {k: v for k, v in e2e.items() if v is not None}
    return JobOutput(
        attempted=len(reqs),
        failed=missing,
        end_to_end={"setup_s": setup_s, **e2e},
        checks={"logit_gap": Check(widest, cfg["checks"]["serve"]
                                   ["logit_gap"])},
        memory_peak_bytes=peak, records=records,
        trace=(Recorder(scratch_dir("trace") / ctx.cell.name).file()
               if ctx.trace else None))
