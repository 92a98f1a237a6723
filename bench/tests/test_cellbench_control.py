"""The controls at a size a test run holds: the reference one precision
below the configuration's, put in the program's place, reads farther from
the float32 reference than the program does (on the chip, at the cells'
own sizes, ``bench/tools/control.py`` reads the same numbers)."""

from __future__ import annotations

import importlib.util
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import tiny  # noqa: E402

_spec = importlib.util.spec_from_file_location("bench_control_tool",
                                               BENCH / "tools" / "control.py")
control = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(control)


@pytest.mark.parametrize("name,cfg,mix", [
    ("qwen2gspn.serve_chat", tiny.lm_config, tiny.serve_traffic),
    ("gspn2t.train_224", tiny.vision_config,
     lambda: tiny.vision_traffic("train_224")),
    ("gspn2t.infer_1024", tiny.vision_config,
     lambda: tiny.vision_traffic("infer_1024")),
], ids=["serve", "train", "infer"])
def test_control_reads_farther_than_the_program(name, cfg, mix):
    """Each of the job's controls reads more than three times what the
    program reads on at least one of the cell's numbers."""
    cell = tiny.cell(name, cfg(), mix())
    r = control.readings(cell, 2**31 + 99, 0.5)
    assert r["control"], r
    for got in r["control"].values():
        assert any(got[k] > 3 * v for k, v in r["program"].items()), r
