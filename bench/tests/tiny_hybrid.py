"""Tiny cells for the CPU tests of the hybrid serving job and the
sharded training job: the same jobs, references and readers as the chip
cells, at sizes a test run holds."""

from __future__ import annotations

import copy

from benchlib import registry

import tiny


def hybrid_config() -> dict:
    """Granite-4.0-H's keys at a test's size: Mamba-2 runs around two
    attention layers, the published scalars but the logits' divisor."""
    cfg = tiny._load("configs/granite-4.0-h-micro.json")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8, shared_intermediate_size=128,
               vocab_size=512, num_hidden_layers=7,
               layer_types=["mamba", "mamba", "attention", "mamba", "mamba",
                            "attention", "mamba"], logits_scaling=1)
    # sound runs read logit_rel 0.022-0.025 and served_gap 0; a dropped SSM
    # state 0.11-0.22, the float8 control 0.22-0.29, a served token that
    # is not the programs' argmax a gap of order one logit
    cfg["checks"] = {"serve": {"logit_rel": 0.06, "served_gap": 0.01}}
    return cfg


def hybrid_traffic() -> dict:
    mix = tiny._load("traffic/serve_doc.json")
    mix["arrivals"]["rate_per_s"] = 12.0
    mix["prompt"].update(median=24, min=12, max=40, round_up_to=4)
    mix["output"].update(median=8, min=4, max=16)
    mix["engine"].update(slots=4, prefill_chunk=8)
    mix.update(drain_s=20, trace_seconds=0.3, check_requests=3,
               reference_rows=2, check_tokens=12)
    return mix


def hybrid_cell() -> registry.Cell:
    return tiny.cell("granite4h.serve_doc", hybrid_config(), hybrid_traffic())


def sp_cell(chips: int = 4) -> registry.Cell:
    """The sharded training job on ``chips`` devices at 64 x 64 (a cell
    that ``BENCHMARK.json`` does not list yet, so built here)."""
    mix = tiny._load("traffic/train_sp.json")
    mix.update(img_size=64, batch=1, trace_steps=2)
    mix["checks"] = {"loss": 1e-4, "grad": 1e-3, "change": 1e-2}
    return registry.Cell(name="gspn2t.train_sp", config_name="gspn2-t",
                         traffic_name="train_sp", chips=chips,
                         config=copy.deepcopy(tiny.vision_config()),
                         traffic=mix, end_to_end=(), per_layer=())
