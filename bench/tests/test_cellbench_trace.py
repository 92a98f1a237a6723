"""The trace reduction, on small traces recorded on a TPU v5e chip by
``bench/tools/record_fixture.py`` (the tests' tiny training and serving
cells, traced as a run with ``--trace 1`` traces them)."""

from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

from benchlib import counts, peaks, registry, trace  # noqa: E402

import tiny  # noqa: E402

TRAIN = DATA / "gspn2t.train_224.xplane.pb.gz"
SERVE = DATA / "qwen2gspn.serve_chat.xplane.pb.gz"
SPANS = set(trace.HOST_SPANS) | {"host.other"}


def test_merged_intervals():
    assert trace._merged([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [[0, 4], [5, 6]]


def test_train_trace_reduces_to_whole_steps():
    t = trace.reduce(TRAIN)
    assert t.devices == 1
    assert 0 < t.busy_ns <= t.window_ns
    # two traced steps of the tiny cell: one train-step program each, and
    # per block two forward and two adjoint scan-kernel launches
    assert t.module_calls["jit_train_step"] == 2
    cfg, mix = tiny.vision_config(), tiny.vision_traffic("train_224")
    per_step = len(counts.vision_scan_calls(
        dict(cfg, img_size=mix["img_size"]), mix["batch"], train=True))
    assert t.kernel_calls == 2 * per_step
    assert 0 < t.kernel_ns < t.busy_ns
    assert set(t.idle_gaps) <= SPANS
    idle = sum(t.idle_gaps.values())
    assert idle == pytest.approx(t.window_ns - t.busy_ns, rel=1e-6)
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])


def test_serve_trace_names_the_engine_programs():
    t = trace.reduce(SERVE)
    assert 0 < t.busy_ns <= t.window_ns
    assert t.module_calls.get("jit__decode_fn", 0) > 0
    assert set(t.idle_gaps) <= SPANS
    assert any(name == "engine.tick" for name, _, _ in t.host_spans)


def test_readers_on_the_train_trace():
    t = trace.reduce(TRAIN)
    cfg, mix = tiny.vision_config(), tiny.vision_traffic("train_224")
    cell = tiny.cell("gspn2t.train_224", cfg, mix)

    class Run:
        pass

    run = Run()
    run.cell, run.trace, run.peaks = cell, t, peaks.peaks("TPU v5 lite")
    run.records = {"trace_steps": 2, "images_per_s": 100.0,
                   "flops_per_image": 1e9,
                   "scan_calls": counts.vision_scan_calls(
                       dict(cfg, img_size=mix["img_size"]), mix["batch"],
                       train=True)}
    got = {m: registry.metric_reader(m).read(run)
           for m in ("scan_roofline", "scan_device_share", "step_mfu",
                     "idle_share.vision")}
    assert 0 < got["scan_roofline"] <= 100
    assert 0 < got["scan_device_share"] < 100
    assert got["step_mfu"] == pytest.approx(100 * 100 * 1e9 / 197e12)
    assert 0 <= got["idle_share.vision"] < 100
    run.trace = None
    assert registry.metric_reader("scan_roofline").read(run) is None
