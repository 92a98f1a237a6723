"""The hybrid serving cell and the sharded training job on the CPU: whole
runs of the tiny cells (``correct`` true on the sound program, false with
a chunk boundary that drops the SSM state), the float8 control, the byte
and operation counts of ``benchlib/hybrid_counts.py`` pinned against the
program and checked by hand, the per-layer readers, and the exchange's
share read from HLO metadata and a recorded trace."""

from __future__ import annotations

import importlib.util
import pathlib
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import tiny_hybrid  # noqa: E402
from benchlib import peaks, registry  # noqa: E402
from benchlib.hybrid_counts import HybridCounts  # noqa: E402
from repro.models import lm  # noqa: E402

_spec = importlib.util.spec_from_file_location("bench_run_hybrid",
                                               BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)
_spec = importlib.util.spec_from_file_location("bench_control_hybrid",
                                               BENCH / "tools" / "control.py")
control = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(control)

SEED = 2**31 + 1234
FULL = registry.load_json(BENCH / "configs" / "granite-4.0-h-micro.json")


def run(cell, tamper=None, seconds=0.5):
    return bench_run.execute(cell, SEED, seconds, False, tamper=tamper,
                             t_process=time.perf_counter())


def dropped_ssm_state(name, engine):
    """Fault: every prompt chunk after the first starts from a zero SSM
    state (the conv tail and the KV are kept).  The zeroing is compiled
    here, before the window, for the one-row cache a prompt's chunks
    carry, so the window runs no new program."""
    if name != "engine":
        return engine
    chunk_fn = engine._prefill_chunk_fn
    zero = jax.jit(lambda caches: {
        k: dict(v, ssm=jnp.zeros_like(v["ssm"])) if "ssm" in v else v
        for k, v in caches.items()})
    zero(lm.init_lm_cache(engine.cfg, 1, engine.max_len))

    def drop(params, chunk, caches, off, n_valid, last):
        if int(off) > 0:
            caches = zero(caches)
        return chunk_fn(params, chunk, caches, off, n_valid, last)

    engine._prefill_chunk_fn = drop
    return engine


def altered_tokens(name, engine):
    """Fault: the window streams the token after the one the decode step
    chose (the replay's own steps are left alone)."""
    if name != "engine":
        return engine
    decode, vocab = engine._decode, engine.cfg.vocab

    def wrong(*args):
        out = decode(*args)
        return out if len(args) > 4 else ((out[0] + 1) % vocab,) + out[1:]
    engine._decode = wrong
    return engine


@pytest.mark.parametrize("tamper,seconds,failing", [
    (None, 2.0, set()), (dropped_ssm_state, 0.5, {"logit_rel"}),
    (altered_tokens, 0.5, {"served_gap"})],
    ids=["sound", "ssm-state-dropped", "tokens-altered"])
def test_hybrid_run_is_correct_only_when_sound(tamper, seconds, failing):
    result = run(tiny_hybrid.hybrid_cell(), tamper, seconds)
    assert result["correct"] is (not failing), result["checks"]
    over = {k for k, c in result["checks"].items() if c["value"] > c["limit"]}
    assert over == failing, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert jax.devices()[0].platform == "cpu"


def test_hybrid_control_reads_farther_than_the_program():
    r = control.readings(tiny_hybrid.hybrid_cell(), 2**31 + 99, 0.5)
    got, prog = r["control"]["fp8"]["logit_rel"], r["program"]["logit_rel"]
    limit = tiny_hybrid.hybrid_config()["checks"]["serve"]["logit_rel"]
    assert prog < limit < got and got > 3 * prog, r


def test_program_config_is_the_programs_full():
    from repro.configs.base import with_precision
    from repro.configs.granite_4_0_h_micro import full
    job = registry.job("serve_hybrid")
    assert job.program_config(FULL) == with_precision(full(), "bf16")


# -- counts --------------------------------------------------------------------

def test_hybrid_counts_match_the_program():
    """Parameters, and the pool's SSM, conv and KV bytes per slot, as the
    program holds them (at the published sizes and at the tiny cell's)."""
    from repro.models.lm import init_lm
    from repro.serve.cache import StateCachePool
    job = registry.job("serve_hybrid")
    for cfg in (FULL, tiny_hybrid.hybrid_config()):
        pcfg = job.program_config(cfg)
        c = HybridCounts.of(cfg)
        shapes = jax.eval_shape(lambda k, p=pcfg: init_lm(k, p),
                                jax.random.PRNGKey(0))
        assert c.params() == sum(int(a.size) for a in jax.tree.leaves(shapes))
    cfg = tiny_hybrid.hybrid_config()
    c = HybridCounts.of(cfg)
    slots, max_len = 3, 40
    pool = StateCachePool(job.program_config(cfg), slots, max_len)
    by = pool.bytes_by_kind()
    ssm, conv = c.state_bytes_per_row()
    assert by["ssm"] == slots * ssm and by["conv"] == slots * conv
    assert by["kv"] == slots * max_len * c.kv_bytes_per_token()


def test_hybrid_counts_by_hand():
    """One Mamba-2 layer (2 heads of 2, d_state 3, chunk 4) and one
    attention layer (2 heads of 2, 1 KV head), hidden 4, FFN 5, vocab 7."""
    cfg = {"hidden_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "mamba_n_heads": 2, "mamba_d_head": 2,
           "mamba_d_state": 3, "mamba_n_groups": 1, "mamba_d_conv": 4,
           "mamba_chunk_size": 4, "shared_intermediate_size": 5,
           "vocab_size": 7, "tie_word_embeddings": True,
           "layer_types": ["mamba", "attention"]}
    c = HybridCounts.of(cfg)
    # in_proj 4 x (2*4 + 2*3 + 2) = 64, out_proj 4 x 4 = 16
    assert c.mamba_matmul_params() == 80
    # q and o 4 x 4 each, k and v 4 x 2 each
    assert c.attn_matmul_params() == 48
    # conv (4 + 1) x 10, A_log/dt_bias/D 6, norm gain 4; FFN 60; norms 8;
    # embedding 28 and the final norm 4
    assert c.params() == (80 + 50 + 6 + 4 + 60 + 8) + (48 + 60 + 8) + 32
    assert c.state_bytes_per_row() == (2 * 2 * 3 * 4, 3 * 10 * 4)
    assert c.kv_bytes_per_token() == 2 * 1 * 2 * 2
    # t = 6 tokens: chunks of 2 (4 does not divide 6), three of them;
    # per chunk 2*4*3 + 2*4*4 + 4*2*3*4
    assert c.ssd_flops(6) == 3 * (24 + 32 + 96)
    # offset 3: keys seen 6*3 + 21 = 39
    assert c.attention_flops(6, 3) == 4 * 2 * 2 * 39
    assert c.prefill_flops(6, 3, True) == (2 * 6 * (140 + 108) + 3 * 152
                                           + 4 * 2 * 2 * 39 + 2 * 6 * 7 * 4)
    assert c.decode_bytes(2, 10) == 356 * 2 + 2 * 2 * (48 + 120) + 10 * 8


# -- per-layer readers -----------------------------------------------------------

def _span(name, ts, **args):
    return types.SimpleNamespace(name=name, ts=ts * 1e9, args=args)


def test_hybrid_readers_by_hand():
    c = HybridCounts.of(FULL)
    served = types.SimpleNamespace(trace_window=(10.0, 12.0),
                                   prompt_len={1: 5000, 2: 700})
    recs = [_span("serve.decode_step", 10.5, rows=8, kv_tokens=40000),
            _span("serve.decode_step", 11.0, rows=10, kv_tokens=50000),
            _span("serve.decode_step", 13.0, rows=99, kv_tokens=1),
            _span("serve.prefill_chunk", 10.6, uid=1, offset=4096,
                  tokens=904),
            _span("serve.prefill_chunk", 10.7, uid=1, offset=2048,
                  tokens=2048),
            _span("serve.prefill", 11.5, prompt_tokens=700)]
    trace = types.SimpleNamespace(
        module_ns={"jit__decode_fn": 60e6, "jit__lambda": 200e6},
        module_calls={"jit__decode_fn": 2, "jit__lambda": 3})
    pk = peaks.peaks("TPU v5 lite")
    run = types.SimpleNamespace(records={"served": served, "counts": c,
                                         "obs": recs},
                                trace=trace, peaks=pk)
    dec = registry.metric_reader("decode_roofline.hybrid").read(run)
    mean = (c.decode_bytes(8, 40000) + c.decode_bytes(10, 50000)) / 2
    assert dec == pytest.approx(100 * 2 * mean / pk.hbm_bytes_per_s / 0.06)
    mfu = registry.metric_reader("prefill_mfu.hybrid").read(run)
    flops = (c.prefill_flops(904, 4096, False) + c.head_flops(1)
             + c.prefill_flops(2048, 2048, False)
             + c.prefill_flops(700, 0, True))
    assert mfu == pytest.approx(100 * flops / (0.2 * pk.flops_per_s))
    run.trace = None
    assert registry.metric_reader("decode_roofline.hybrid").read(run) is None


# -- the sharded training job's exchange -------------------------------------------

HLO = """\
ENTRY %main {
  %p0 = f32[4]{0} parameter(0)
  %all-gather-start.3 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %x), metadata={op_name="jit(step)/jvp(train)/gspn_sp.exchange/all_gather"}
  %all-reduce.7 = f32[4]{0} all-reduce(f32[4]{0} %p0), metadata={op_name="jit(step)/transpose(jvp)/psum"}
  ROOT %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p0), kind=kLoop
}
"""


def test_exchange_ops_from_hlo_metadata():
    job = registry.job("train_sp")
    assert job.exchange_ops(HLO) == ({"all-gather-start.3"}, "scope")
    bare = HLO.replace("gspn_sp.exchange/", "")
    assert job.exchange_ops(bare) == (
        {"all-gather-start.3", "all-reduce.7"}, "collectives")


def test_exchange_share_on_a_recorded_trace():
    """On the recorded one-chip train_224 trace: the named ops' time over
    the chip's busy time, no more than the busy time itself."""
    from benchlib.trace import reduce
    job = registry.job("train_sp")
    path = BENCH / "tests" / "data" / "gspn2t.train_224.xplane.pb.gz"
    none = job.exchange_share(path, set(), 1)
    assert none["exchange_ns"] == [0.0] and none["fullest"] == 0
    assert none["busy_ns"][0] == pytest.approx(reduce(path, 1).busy_ns)
    summary = reduce(path, 1)
    ops = {k: v for k, v in summary.op_ns.items()
           if not k.endswith("gspn_scan_kernel")}
    top = max(ops, key=ops.get).split("/", 1)[1]
    got = job.exchange_share(path, {top}, 1)
    assert 0 < got["exchange_ns"][0] <= got["busy_ns"][0]
    run = types.SimpleNamespace(records={"exchange": got})
    share = registry.metric_reader("sp_exchange_share").read(run)
    assert share == pytest.approx(100 * got["exchange_ns"][0]
                                  / got["busy_ns"][0])
