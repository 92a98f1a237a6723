"""Drive whole runs of the tiny cells on the CPU (past the harness's look
for a chip) and see ``correct`` come out true on the sound program and
false with the timed path broken underneath, once for each fault the
cells can have.  The cells run on one chip, so no exchange between chips
can be left out."""

from __future__ import annotations

import importlib.util
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import tiny  # noqa: E402

_spec = importlib.util.spec_from_file_location("bench_run_faults",
                                               BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

SEED = 2**31 + 1234


def run(cell, tamper=None, seconds=0.5):
    return bench_run.execute(cell, SEED, seconds, False, tamper=tamper,
                             t_process=time.perf_counter())


def serve_cell():
    return tiny.cell("qwen2gspn.serve_chat", tiny.lm_config(),
                     tiny.serve_traffic())


def train_cell():
    return tiny.cell("gspn2t.train_224", tiny.vision_config(),
                     tiny.vision_traffic("train_224"))


def infer_cell():
    return tiny.cell("gspn2t.infer_1024", tiny.vision_config(),
                     tiny.vision_traffic("infer_1024"))


def only(what, wrap):
    """A tamper that breaks the object the job names ``what``."""
    def tamper(name, obj):
        return wrap(obj) if name == what else obj
    return tamper


def altered_tokens(engine):
    vocab = engine.cfg.vocab
    decode = engine._decode

    def wrong(*args):
        nxt, caches = decode(*args)
        return (nxt + 1) % vocab, caches

    engine._decode = wrong
    return engine


def unchanged_state(step):
    def frozen(state, batch):
        _, loss = step(state, batch)
        return state, loss
    return frozen


def half_batch(step):
    def half(state, batch):
        n = batch["images"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return half


def altered_answer(step):
    def wrong(params, batch):
        params, logits = step(params, batch)
        return params, logits.at[0].set(jnp.roll(logits[0], 1))
    return wrong


@pytest.mark.parametrize("make,tamper", [
    (serve_cell, None),
    (train_cell, None),
    (infer_cell, None),
], ids=["serve", "train", "infer"])
def test_sound_runs_are_correct(make, tamper):
    result = run(make(), tamper)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("make,tamper", [
    (serve_cell, only("engine", altered_tokens)),
    (train_cell, only("train_step", unchanged_state)),
    (train_cell, only("train_step", half_batch)),
    (infer_cell, only("infer_step", altered_answer)),
], ids=["serve-token-altered", "train-state-unchanged", "train-half-batch",
        "infer-answer-altered"])
def test_a_broken_timed_path_is_not_correct(make, tamper):
    result = run(make(), tamper)
    assert not result["correct"], result["checks"]
    assert jax.devices()[0].platform == "cpu"
