"""The serve engine's ticks read from the program's own spans
(``benchlib.ticks``, ``bench/metrics/tick_idle_ms.py``,
``launches_per_tick.py``), on a small trace recorded on a TPU v5e chip by
``bench/tools/record_ticks_fixture.py``; and the readers that were there
before, on the traces recorded before the program's spans reached the
profiler, pinned to the values they read there."""

from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

from benchlib import counts, peaks, registry, ticks, trace  # noqa: E402

import tiny  # noqa: E402

TICKS = DATA / "qwen2gspn.serve_chat.ticks.xplane.pb.gz"
SERVE = DATA / "qwen2gspn.serve_chat.xplane.pb.gz"
TRAIN = DATA / "gspn2t.train_224.xplane.pb.gz"

# Each phase of the engine's tick and the span that encloses it.
PHASES = {"serve.admit": "serve.tick", "serve.cache_init": "serve.admit",
          "serve.prefill": "serve.admit", "serve.first_token": "serve.tick",
          "serve.cache_commit": "serve.tick",
          "serve.prefill_chunk": "serve.tick",
          "serve.prefill_sync": "serve.prefill_chunk",
          "serve.decode_step": "serve.tick",
          "serve.token_read": "serve.decode_step",
          "serve.emit": "serve.decode_step"}
SPANS = set(trace.HOST_SPANS) | {"host.other", "serve.tick"} | set(PHASES)
NEW = ("tick_idle_ms", "launches_per_tick")


class Run:
    def __init__(self, cell, trace_summary, records=None):
        self.cell, self.trace, self.records = cell, trace_summary, records
        self.peaks = peaks.peaks("TPU v5 lite")


def read(metric, run):
    return registry.metric_reader(metric).read(run)


@pytest.fixture
def on_file(monkeypatch):
    """Point the tick readers at a recorded trace in place of the one a
    run leaves in the checkout."""
    def point(path):
        monkeypatch.setattr(ticks, "trace_file", lambda run: path)
    return point


def test_idle_gaps_are_split_and_attributed_to_the_innermost_span():
    assert ticks._idle([(2, 4), (3, 5), (7, 8)], 0, 10) == \
        [(0, 2), (5, 7), (8, 10)]
    assert ticks._idle([], 0, 3) == [(0, 3)]
    spans = [("engine.tick", 0, 10), ("serve.tick", 1, 8),
             ("serve.emit", 5, 2)]
    got = ticks._attribute([(0, 0.5), (2, 4), (5.5, 6), (9.5, 10), (11, 12)],
                           spans)
    assert got == {"engine.tick": 1.0, "serve.tick": 2, "serve.emit": 0.5,
                   "host.other": 1}


def test_every_tick_phase_nests_in_a_tick_of_its_thread():
    pd = trace.load(TICKS)
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
             for line in host.lines for e in line.events
             if e.name in SPANS]

    def enclosed(s, outer):
        return any(o[0] == outer and o[3] == s[3] and o[1] <= s[1]
                   and s[2] <= o[2] for o in spans)

    assert {s[0] for s in spans} >= set(PHASES) | {"serve.tick"}
    for s in spans:
        if s[0] in PHASES:
            assert enclosed(s, PHASES[s[0]]), s
            assert enclosed(s, "serve.tick"), s
        if s[0] == "serve.tick":
            assert enclosed(s, "engine.tick"), s


def test_tick_metrics_read_positive(on_file):
    on_file(TICKS)
    t = ticks.reduce(TICKS)
    run = Run(registry.find_cell("qwen2gspn.serve_chat"), trace.reduce(TICKS))
    got = {m: read(m, run) for m in NEW}
    assert t.ticks > 0
    assert got["tick_idle_ms"] == pytest.approx(t.idle_ns / 1e6 / t.ticks)
    assert got["launches_per_tick"] == sum(t.launches.values()) / t.ticks
    assert all(v > 0 for v in got.values())
    # the engine's own programs run inside the ticks
    assert t.launches.get("jit__decode_fn", 0) > 0
    assert t.launches.get("jit__lambda", 0) > 0


def test_idle_inside_ticks_is_part_of_the_windows_idle():
    t, whole = ticks.reduce(TICKS), trace.reduce(TICKS)
    assert 0 < t.idle_ns <= t.window_idle_ns
    assert t.window_idle_ns == pytest.approx(whole.window_ns - whole.busy_ns,
                                             rel=1e-9)
    assert sum(t.idle_gaps.values()) == pytest.approx(t.window_idle_ns,
                                                      rel=1e-9)
    assert set(t.idle_gaps) <= SPANS


def test_program_spans_take_the_idle_inside_the_benchmarks_ticks():
    t = ticks.reduce(TICKS)
    program = sum(v for k, v in t.idle_gaps.items()
                  if k.startswith(ticks.PROGRAM_SPANS))
    assert program > 0.9 * (program + t.idle_gaps.get("engine.tick", 0))
    assert t.program_share() == pytest.approx(program / t.window_idle_ns)


@pytest.mark.parametrize("path", [SERVE, TRAIN])
def test_traces_without_program_spans_attribute_as_before(path, on_file):
    """A trace of a program whose spans are not on the profiler's clock
    (these were recorded before they were) reduces to no ticks, the tick
    readers give nothing, and the idle gaps fall as ``trace.reduce`` puts
    them."""
    on_file(path)
    t, whole = ticks.reduce(path), trace.reduce(path)
    assert t.ticks == 0 and t.launches == {}
    assert t.idle_gaps == pytest.approx(whole.idle_gaps)
    run = Run(registry.find_cell("qwen2gspn.serve_chat"), whole)
    assert [read(m, run) for m in NEW] == [None, None]
    run.trace = None
    assert [read(m, run) for m in NEW] == [None, None]


# What each reader read on the traces recorded before this reduction
# existed, with the readers and reduction of that tree.
BEFORE = {"decode_step_ms": 0.010735133333333334,
          "idle_share.serve": 99.72971855133716,
          "scan_roofline": 0.10724752044241109,
          "scan_device_share": 28.222004299777467,
          "step_mfu": 0.050761421319796954,
          "idle_share.vision": 97.75815198390823}


@pytest.mark.parametrize("metric", sorted(BEFORE))
def test_existing_readers_read_as_before_on_the_old_traces(metric):
    if metric in ("decode_step_ms", "idle_share.serve"):
        run = Run(registry.find_cell("qwen2gspn.serve_chat"),
                  trace.reduce(SERVE), {})
    else:
        cfg, mix = tiny.vision_config(), tiny.vision_traffic("train_224")
        run = Run(tiny.cell("gspn2t.train_224", cfg, mix),
                  trace.reduce(TRAIN),
                  {"trace_steps": 2, "images_per_s": 100.0,
                   "flops_per_image": 1e9,
                   "scan_calls": counts.vision_scan_calls(
                       dict(cfg, img_size=mix["img_size"]), mix["batch"],
                       train=True)})
    assert read(metric, run) == BEFORE[metric]
