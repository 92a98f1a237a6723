"""Tiny cells for the CPU tests: the same jobs, references and readers as
the chip cells, at sizes a test run holds."""

from __future__ import annotations

import copy
import json
import pathlib

from benchlib import registry

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _load(path):
    return json.loads((BENCH / path).read_text())


def lm_config() -> dict:
    cfg = _load("configs/qwen2-1.5b-gspn.json")
    cfg.update(n_layers=2, d_model=48, n_heads=4, n_kv_heads=2, d_ff=128,
               vocab=512, gspn_proxy_dim=4, gspn_row_width=8,
               unit=[["gspn", 2]])
    cfg["checks"] = {"serve": {"logit_gap": 0.05}}
    return cfg


def serve_traffic(name="serve_chat") -> dict:
    mix = _load(f"traffic/{name}.json")
    mix["arrivals"]["rate_per_s"] = 12.0
    mix["prompt"].update(median=16, min=8, max=40, round_up_to=8)
    mix["output"].update(median=4, min=2, max=8)
    mix["engine"].update(slots=4, prefill_chunk=8)
    mix.update(drain_s=20, trace_seconds=0.3, check_requests=3,
               reference_rows=2)
    return mix


def vision_config() -> dict:
    cfg = _load("configs/gspn2-t.json")
    cfg.update(n_classes=10, dims=[8, 16, 24, 32], depths=[1, 1, 2, 1])
    cfg["checks"] = {"train": {"loss": 1e-4, "grad": 1e-3, "change": 1e-2},
                     "infer": {"logits": 1e-4}}
    return cfg


def vision_traffic(name: str) -> dict:
    mix = _load(f"traffic/{name}.json")
    mix.update(img_size=32, batch=4, reference_rows=2, trace_steps=2)
    return mix


def cell(name: str, config: dict, mix: dict) -> registry.Cell:
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    real = registry.find_cell(name)
    return registry.Cell(name=name, config_name=real.config_name,
                         traffic_name=real.traffic_name, chips=1,
                         config=copy.deepcopy(config),
                         traffic=copy.deepcopy(mix),
                         end_to_end=real.end_to_end,
                         per_layer=real.per_layer) if spec else None
