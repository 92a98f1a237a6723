"""Arithmetic the per-layer readers share.  Each returns None where the
run holds nothing to read, and the metric is then left out."""

from __future__ import annotations

from benchlib import counts, traffic

# The serving engine's jitted programs as the device trace names them:
# both prefill paths (one-shot and chunk) are lambdas in ``ServeEngine``.
PREFILL_PROGRAMS = ("jit__lambda",)
DECODE_PROGRAMS = ("jit__decode_fn",)


def idle_share(run):
    t = run.trace
    if t is None or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)


def program_ns(run, names) -> tuple[float, int]:
    t = run.trace
    return (sum(t.module_ns.get(n, 0.0) for n in names),
            sum(t.module_calls.get(n, 0) for n in names))


def obs_spans(run, name: str, window=None):
    """The program's own ``repro.obs`` spans of ``name``, optionally only
    those that start inside ``window`` (perf_counter seconds)."""
    recs = run.records.get("obs") or []
    out = []
    for r in recs:
        if r.name != name:
            continue
        if window is not None and not window[0] <= r.ts / 1e9 <= window[1]:
            continue
        out.append(r)
    return out


def prefill_work(run):
    """(tokens, operations) of the prefill calls the engine made inside
    the traced part of the window.  One-shot prefill computes logits at
    every position; a chunk only when it is the prompt's last."""
    served = run.records.get("served")
    if served is None or len(served.trace_window) != 2:
        return None
    cfg = run.cell.config
    with_head = counts.lm_flops_per_token(cfg, head=True)
    without = counts.lm_flops_per_token(cfg, head=False)
    tokens, flops = 0, 0.0
    for r in obs_spans(run, "serve.prefill", served.trace_window):
        n = r.args["prompt_tokens"]
        tokens += n
        flops += n * with_head
    for r in obs_spans(run, "serve.prefill_chunk", served.trace_window):
        n = r.args["tokens"]
        last = r.args["offset"] + n >= served.prompt_len[r.args["uid"]]
        tokens += n
        flops += n * (with_head if last else without)
    return tokens, flops


def queue_waits(run):
    """Seconds from each request's due time to its admission (the
    program's ``request.admitted`` event), for every request due before
    the trace started (stopping the profiler stalls the host for seconds,
    which would land on the requests due around it); a request never
    admitted waits until the run ends."""
    served = run.records.get("served")
    events = obs_spans(run, "request.admitted")
    if served is None or not events:
        return None
    cut = (served.trace_window[0] - served.t0 if served.trace_window
           else served.seconds)
    admitted = {r.args["uid"]: r.ts / 1e9 for r in events}
    end = max(admitted.values())
    return [admitted.get(u, end) - (served.t0 + due)
            for u, due in served.due.items() if due < cut]


def p95(values):
    return traffic.percentile(values, 95)
