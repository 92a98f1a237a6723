"""Observability subsystem tests (DESIGN.md §13): tracing semantics
(no-op fast path, ring bound, thread interleaving), Chrome trace-event
schema, metrics-registry edge cases (inclusive bucket bounds, Prometheus
export), the serve-engine instrumentation contract, the autotune plan
funnel, schema-2 benchmark stats, the report CLI — and the pin that
keeps disabled tracing under 2% of a decode step."""

import json
import pathlib
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import autotune
from repro.kernels.spec import ScanSpec
from repro.obs import report

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:          # benchmarks/ is a namespace package
    sys.path.insert(0, str(ROOT))

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _obs_reset():
    """Process-global state (trace buffer, registry, resolved-plan map)
    starts and ends clean for every test."""
    saved_plans = dict(autotune._RESOLVED)
    obs.disable()
    obs.clear()
    obs.REGISTRY.reset()
    autotune._RESOLVED.clear()
    yield
    obs.disable()
    obs.clear()
    obs.REGISTRY.reset()
    autotune._RESOLVED.clear()
    autotune._RESOLVED.update(saved_plans)


# ---------------------------------------------------------------------------
# Tracing core.
# ---------------------------------------------------------------------------

def test_disabled_trace_is_a_shared_noop_singleton():
    assert obs.trace("a") is obs.trace("b", x=1) is obs.NOOP_SPAN
    with obs.trace("a", x=1) as sp:
        sp.set(y=2)                     # annotating a noop is legal
    obs.event("e", x=1)
    obs.async_begin("request", 1)
    obs.async_end("request", 1)
    assert obs.records() == []          # nothing touched the buffer
    obs.enable()
    assert obs.trace("a") is not obs.NOOP_SPAN


def _host_names(xplane_dir) -> set:
    """Event names on the host plane of the one ``.xplane.pb`` under
    ``xplane_dir``."""
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(xplane_dir).glob("**/*.xplane.pb")
    planes = ProfileData.from_file(str(path)).planes
    return {e.name for p in planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}


def test_enabled_spans_land_in_the_profilers_host_plane(tmp_path):
    """An enabled span opens a profiler annotation of exactly its name;
    a disabled one is the no-op singleton and reaches no trace."""
    import time
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.05)                # the tracer drops spans opened at once
        obs.enable()
        with obs.trace("serve.tick", uid=3):
            with obs.trace("serve.emit"):
                jnp.ones(4).block_until_ready()
        obs.disable()
        assert obs.trace("serve.admit") is obs.NOOP_SPAN
        with obs.trace("serve.admit"):
            pass
        time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    names = _host_names(tmp_path)
    assert {"serve.tick", "serve.emit"} <= names
    assert "serve.admit" not in names
    assert [r.name for r in obs.spans()] == ["serve.emit", "serve.tick"]


def test_span_records_duration_and_late_attrs():
    obs.enable()
    with obs.trace("phase", size=3) as sp:
        sp.set(plan="fwd:t64-d1")
    (rec,) = obs.spans("phase")
    assert rec.ph == "X" and rec.dur >= 0
    assert rec.args == {"size": 3, "plan": "fwd:t64-d1"}
    assert obs.spans("other") == []


def test_ring_buffer_bounds_memory_keeping_newest():
    obs.enable(ring=8)
    for i in range(20):
        obs.event("e", i=i)
    recs = obs.records()
    assert len(recs) == 8
    assert [r.args["i"] for r in recs] == list(range(12, 20))


def test_threaded_spans_interleave_and_nest_per_thread():
    obs.enable()
    n_threads, n_iters = 6, 25

    def work(i):
        for j in range(n_iters):
            with obs.trace("outer", worker=i):
                with obs.trace("inner", worker=i, j=j):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outer, inner = obs.spans("outer"), obs.spans("inner")
    assert len(outer) == len(inner) == n_threads * n_iters
    # nesting is reconstructed from (tid, ts, dur) intervals: every inner
    # span must lie inside an outer interval recorded by ITS OWN thread
    by_tid = {}
    for r in outer:
        by_tid.setdefault(r.tid, []).append((r.ts, r.ts + r.dur))
    for r in inner:
        assert any(a <= r.ts and r.ts + r.dur <= b
                   for a, b in by_tid[r.tid]), "inner escaped its outer"


def test_chrome_trace_event_schema(tmp_path):
    obs.enable()
    with obs.trace("serve.decode_step", batch=2):
        pass
    obs.event("request.queued", uid=7)
    obs.async_begin("request", 7, prompt_tokens=3)
    obs.async_end("request", 7, finish_reason="eos")
    payload = json.loads(json.dumps(obs.chrome_trace()))  # serialisable
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    assert [e["ph"] for e in evs] == ["X", "i", "b", "e"]
    for e in evs:
        assert {"ph", "name", "pid", "tid", "ts", "cat"} <= set(e)
        assert isinstance(e["ts"], float) and e["ts"] >= 0.0  # µs from epoch
    x, i, b, e = evs
    assert "dur" in x and x["dur"] >= 0.0 and x["args"]["batch"] == 2
    assert i["args"]["uid"] == 7 and "dur" not in i
    for ev in (b, e):                   # async pairs: string id, own cat
        assert ev["id"] == "7" and ev["cat"] == "request"
    # the saved artifact is what the report CLI (and Perfetto) consume
    path = obs.save_chrome_trace(tmp_path / "t.json")
    assert json.loads(pathlib.Path(path).read_text()) == payload


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------

def test_histogram_inclusive_upper_bounds_underflow_overflow():
    h = obs.Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5,      # below edges[0]: first bucket doubles as underflow
              1.0,      # EXACT boundary: stays in its edge's bucket
              1.5, 2.0,  # bucket 1 (2.0 inclusive)
              4.0,      # bucket 2
              4.0001):  # past the last edge: +Inf overflow
        h.observe(v)
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6 and h.sum == pytest.approx(13.0001)
    assert h.min == 0.5 and h.max == 4.0001
    assert h.quantile(0.5) == 2.0       # cumulative crosses rank in bucket 1
    assert h.quantile(1.0) == 4.0001    # overflow reports max observed
    with pytest.raises(ValueError):
        obs.Histogram("bad", buckets=(2.0, 1.0))   # not increasing
    with pytest.raises(ValueError):
        obs.Histogram("bad", buckets=())


def test_registry_typing_and_reset():
    obs.counter("reqs_total").inc(2)
    assert obs.counter("reqs_total").value == 2   # get-or-create: same obj
    with pytest.raises(TypeError):
        obs.gauge("reqs_total")                   # name/type clash
    with pytest.raises(ValueError):
        obs.counter("reqs_total").inc(-1)         # counters never decrease
    obs.REGISTRY.reset()
    assert obs.counter("reqs_total").value == 0   # accessors re-create


def test_prometheus_export_is_cumulative_with_inf_sum_count():
    h = obs.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.001, 0.05, 99.0):
        h.observe(v)
    obs.counter("reqs_total", "served requests").inc(2)
    obs.gauge("depth").set(3)
    text = obs.prometheus()
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.001"} 2' in text   # underflow + boundary
    assert 'lat_seconds_bucket{le="0.01"} 2' in text    # cumulative
    assert 'lat_seconds_bucket{le="0.1"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_sum" in text and "lat_seconds_count 4" in text
    assert "# HELP reqs_total served requests" in text
    assert "reqs_total 2" in text and "depth 3.0" in text
    snap = obs.snapshot()
    assert snap["counters"]["reqs_total"] == 2
    assert snap["histograms"]["lat_seconds"]["counts"] == [2, 0, 1, 1]


def test_save_metrics_writes_json_or_prom_by_suffix(tmp_path):
    obs.counter("c_total").inc()
    p_json = obs.save_metrics(tmp_path / "m.json")
    assert json.loads(pathlib.Path(p_json).read_text())["counters"] == \
        {"c_total": 1}
    p_prom = obs.save_metrics(tmp_path / "m.prom")
    assert "c_total 1" in pathlib.Path(p_prom).read_text()


# ---------------------------------------------------------------------------
# Autotune plan funnel (the decode-step span annotation).
# ---------------------------------------------------------------------------

def test_plan_resolutions_are_recorded_once_and_summarised():
    obs.enable()
    plan = autotune.plan_for_spec(
        ScanSpec(direction="fwd", interpret=True), 64, 64, c=8)
    evs = [r for r in obs.records() if r.name == "kernel.plan"]
    assert len(evs) == 1 and evs[0].ph == "i"
    assert evs[0].args["row_tile"] == plan.row_tile
    assert evs[0].args["source"] in ("cache", "heuristic")
    autotune.plan_for_spec(ScanSpec(direction="fwd", interpret=True),
                           64, 64, c=8)
    assert len([r for r in obs.records()
                if r.name == "kernel.plan"]) == 1    # same key: no re-emit
    s = autotune.plans_summary()
    assert "h64|w64|c8|fwd" in s
    assert f"t{plan.row_tile}-d{plan.pipeline_depth}" in s


# ---------------------------------------------------------------------------
# Serve-engine instrumentation (the ISSUE acceptance shape).
# ---------------------------------------------------------------------------

def _gspn_cfg():
    from repro.models.lm import LMConfig
    return LMConfig(
        name="g", family="gspn", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=64, prelude=(("gspn", 1),),
        unit=(("attn", 1),), n_units=1, gspn_proxy_dim=4, gspn_row_width=8,
        remat="none", compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine():
    from repro.models.lm import init_lm
    from repro.serve.engine import ServeEngine
    cfg = _gspn_cfg()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, batch_size=2, max_len=64,
                       prefill_chunk=8)


def test_engine_emits_request_to_kernel_spans(engine):
    from repro.serve.engine import Request
    engine.reset()
    obs.enable()
    engine.submit(Request(uid=0, prompt=np.arange(24) % 64,
                          max_new_tokens=4))   # 24 > chunk 8: 3 chunks
    engine.submit(Request(uid=1, prompt=np.arange(6) % 64, max_new_tokens=3))
    engine.submit(Request(uid=2, prompt=np.arange(6) % 64, max_new_tokens=3))
    res = engine.run()
    assert sorted(res) == [0, 1, 2]

    recs = obs.records()
    begins = [r for r in recs if r.ph == "b" and r.name == "request"]
    ends = [r for r in recs if r.ph == "e" and r.name == "request"]
    assert {r.aid for r in begins} == {0, 1, 2} == {r.aid for r in ends}
    for e in ends:
        assert e.args["finish_reason"] in ("eos", "length")
        b = next(r for r in begins if r.aid == e.aid)
        assert b.ts <= e.ts             # lifecycle ordering

    chunks = obs.spans("serve.prefill_chunk")
    assert [c.args["index"] for c in chunks] == [0, 1, 2]
    assert all(c.args["uid"] == 0 for c in chunks)
    steps = obs.spans("serve.decode_step")
    assert steps, "no decode-step spans recorded"
    assert all("batch" in s.args for s in steps)

    m = engine.metrics                  # compat view + derived mean
    assert m["decode_steps"] == len(steps)
    assert m["prefill_chunks"] == 3
    assert m["queue_depth_max"] >= 1    # uid 2 had to wait for a slot
    assert m["queue_depth_mean"] >= 0.0
    snap = obs.snapshot()               # same counters, global registry
    assert snap["counters"]["serve_requests_submitted_total"] == 3
    assert snap["counters"]["serve_requests_finished_total"] == 3
    assert snap["counters"]["serve_decode_steps_total"] == len(steps)
    assert snap["histograms"]["serve_ttft_seconds"]["count"] == 3


# Each tick phase and the span that must enclose it on the same thread.
TICK_PHASES = {"serve.admit": "serve.tick", "serve.cache_init": "serve.admit",
               "serve.prefill": "serve.admit",
               "serve.first_token": "serve.tick",
               "serve.cache_commit": "serve.tick",
               "serve.prefill_chunk": "serve.tick",
               "serve.prefill_sync": "serve.prefill_chunk",
               "serve.decode_step": "serve.tick",
               "serve.token_read": "serve.decode_step",
               "serve.emit": "serve.decode_step"}


def _run_mixed(engine):
    """One chunked prompt (24 > chunk 8), two one-shot prompts, one of
    which waits for a slot; returns the recorded spans."""
    from repro.serve.engine import Request
    engine.reset()
    obs.enable()
    engine.submit(Request(uid=0, prompt=np.arange(24) % 64,
                          max_new_tokens=4))
    engine.submit(Request(uid=1, prompt=np.arange(6) % 64, max_new_tokens=3))
    engine.submit(Request(uid=2, prompt=np.arange(6) % 64, max_new_tokens=3))
    engine.run()
    obs.disable()
    return obs.spans()


def test_engine_tick_phases_nest_in_their_tick(engine):
    spans = _run_mixed(engine)
    assert {s.name for s in spans} == set(TICK_PHASES) | {"serve.tick"}

    def within(s, o):
        return (o is not s and o.tid == s.tid and o.ts <= s.ts
                and s.ts + s.dur <= o.ts + o.dur)

    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(ticks) == engine.metrics["ticks"]
    for s in spans:
        if s.name in TICK_PHASES:
            assert any(within(s, o) for o in spans
                       if o.name == TICK_PHASES[s.name]), s.name
            assert any(within(s, t) for t in ticks), s.name
    # one span per phase and tick, never one per slot
    for t in ticks:
        names = [s.name for s in spans if within(s, t)]
        for once in ("serve.admit", "serve.decode_step", "serve.token_read",
                     "serve.emit", "serve.prefill_chunk"):
            assert names.count(once) <= 1, (once, names)
        assert names.count("serve.admit") == 1
    # every admission samples one first token and commits one cache
    assert len([s for s in spans if s.name == "serve.first_token"]) == 3
    assert len([s for s in spans if s.name == "serve.cache_commit"]) == 3
    assert len([s for s in spans if s.name == "serve.cache_init"]) == 1


def test_disabled_engine_makes_no_span_or_annotation(engine, monkeypatch):
    """With tracing off the instrumentation creates nothing beyond the
    shared no-op singleton: no Span and no profiler annotation."""
    from repro.obs import tracing
    from repro.serve.engine import Request

    def refuse(*a, **k):
        raise AssertionError("disabled tracing created an object")

    monkeypatch.setattr(tracing, "Span", refuse)
    monkeypatch.setattr(tracing, "_Annotation", refuse)
    engine.reset()
    engine.submit(Request(uid=0, prompt=np.arange(24) % 64,
                          max_new_tokens=3))
    engine.submit(Request(uid=1, prompt=np.arange(6) % 64, max_new_tokens=3))
    assert sorted(engine.run()) == [0, 1]
    assert obs.records() == []


def test_queue_depth_not_counted_on_admission_tick(engine):
    """The satellite fix: depth is sampled AFTER _admit(), so a request
    admitted the tick it arrived never inflates the mean (the old
    pre-admit sample double-counted every retire-and-replace tick)."""
    from repro.serve.engine import Request
    engine.reset()
    engine.submit(Request(uid=0, prompt=np.arange(6) % 64, max_new_tokens=3))
    engine.run()
    m = engine.metrics
    assert m["depth_samples"] == m["ticks"] > 0
    assert m["queue_depth_max"] == 0    # never actually waited a tick out
    assert m["queue_depth_mean"] == 0.0


def test_disabled_tracing_overhead_under_2pct_of_decode_step(engine):
    """The DESIGN.md §13 pin: with tracing off, the per-call cost of the
    instrumentation (flag check + shared singleton) times a generous
    calls-per-step budget stays under 2% of a measured decode step."""
    from repro.serve.engine import Request
    engine.reset()
    assert not obs.enabled()
    engine.submit(Request(uid=0, prompt=np.arange(6) % 64,
                          max_new_tokens=24))
    engine.tick()                       # admit + compile the decode path
    step_times = []
    while engine.slot_req[0] is not None and len(step_times) < 16:
        t0 = obs.monotonic()
        engine.tick()
        step_times.append(obs.monotonic() - t0)
    engine.run()
    engine.reset()
    step_times.sort()
    step_s = step_times[len(step_times) // 2]

    n = 10000                           # best-of-5: intrinsic cost, not
    best = float("inf")                 # scheduler noise
    for _ in range(5):
        t0 = obs.monotonic()
        for _ in range(n):
            with obs.trace("x", a=1, b=2):
                pass
            obs.event("y", z=3)
        best = min(best, obs.monotonic() - t0)
    per_call = best / (2 * n)
    # every span, event and async record one tick can make (the mixed
    # run: admissions, chunks, first tokens, decode, retirements) fits
    calls_per_step = 32
    spans = _run_mixed(engine)
    engine.reset()
    ticks = [(s.ts, s.ts + s.dur) for s in spans if s.name == "serve.tick"]
    recs = obs.records()
    most = max(sum(1 for r in recs if a <= r.ts <= b) for a, b in ticks)
    assert most <= calls_per_step, most
    assert per_call * calls_per_step < 0.02 * step_s, (
        f"disabled-tracing overhead {per_call * calls_per_step * 1e6:.2f}us "
        f"vs 2% of decode step {0.02 * step_s * 1e6:.2f}us")


# ---------------------------------------------------------------------------
# Benchmark schema 2 (time_fn stats) + gate read-compat.
# ---------------------------------------------------------------------------

def test_time_fn_stats_flow_into_schema2_payload(monkeypatch):
    import benchmarks.common as common
    from benchmarks import run as bench_run
    monkeypatch.setattr(common, "ROWS", [])
    monkeypatch.setattr(common, "ROW_STATS", [])
    monkeypatch.setattr(common, "LAST_STATS", None)
    common.time_fn(lambda: jnp.arange(8), iters=5, warmup=0)
    st = common.LAST_STATS
    assert st["iters"] == 5
    assert st["p10_us"] <= st["p50_us"] <= st["p90_us"]
    common.emit("obs/timed", 1.0, "d=1")
    common.emit("obs/derived", 2.0)     # no fresh time_fn: stats is None
    assert common.LAST_STATS is None    # emit consumed it
    payload = bench_run.build_payload(common.ROWS, smoke=True,
                                      row_stats=common.ROW_STATS)
    assert payload["schema"] == 2
    assert payload["rows"][0]["stats"]["iters"] == 5
    assert payload["rows"][1]["stats"] is None


def test_gate_reads_schema_1_and_2(tmp_path):
    from benchmarks import gate
    for payload in (
            {"schema": 1, "rows": [{"name": "a", "us_per_call": 1.0,
                                    "derived": ""}]},
            {"schema": 2, "rows": [{"name": "a", "us_per_call": 1.0,
                                    "derived": "", "stats": None}]}):
        p = tmp_path / "r.json"
        p.write_text(json.dumps(payload))
        assert gate.index_rows(gate.load_report(p)) == {"a": 1.0}


# ---------------------------------------------------------------------------
# Report CLI.
# ---------------------------------------------------------------------------

def test_report_cli_summarises_trace_and_metrics(tmp_path, capsys):
    obs.enable()
    with obs.trace("kernel.launch", kernel="gspn_pair_fwd"):
        pass
    obs.event("kernel.plan")
    obs.async_begin("request", 1)
    obs.async_end("request", 1)
    trace_path = obs.save_chrome_trace(tmp_path / "t.json")
    obs.counter("c_total").inc(3)
    obs.histogram("h_seconds").observe(0.004)
    metrics_path = obs.save_metrics(tmp_path / "m.json")

    assert report.main([trace_path]) == 0
    out = capsys.readouterr().out
    assert "1 spans, 2 async, 1 instant" in out
    assert "kernel.launch" in out
    assert report.main([metrics_path]) == 0
    out = capsys.readouterr().out
    assert "c_total" in out and "h_seconds" in out and "p90" in out

    bad = tmp_path / "bad.json"
    bad.write_text('{"neither": 1}')
    assert report.main([str(bad)]) == 1
    assert report.main([str(tmp_path / "missing.json")]) == 1
