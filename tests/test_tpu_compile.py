"""Ahead-of-time compiles of the scan kernels for a described TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (unaligned slices,
scans over values, VMEM overruns), so the CPU suite alone cannot say the
kernels run on the chip.  These tests compile — without a chip — every
(row tile, pipeline depth) that the tuner or its heuristic can emit for
the shapes the shipped models scan, forward and adjoint, single and
fused pair, f32 and bf16 streams, and assert a Mosaic kernel
(``tpu_custom_call``) is in the result:

* the LM fold of qwen2-1.5b-gspn at batch 4 (G = 32 planes, 4 weight
  planes): W = 1024 with H ∈ {1, 2, 3, 64} (a prompt's grid rows; H = 2
  is one chunk plus the resumed boundary row), and the within-row pass
  W ∈ {1, 3} with H = 1024;
* the same fold at the prefill_32k shape (batch 32: G = 256, H = 32),
  where depth 2 cannot hold every plane in VMEM and the tuner must emit
  depth 1;
* the gspn2-t stages at 224², batch 8 (G = 16, 8 weight planes):
  W = H ∈ {56, 7};
* the gspn2-t stages the benchmark's vision cells run, fused pair
  forward and adjoint in f32: train_224 (batch 32, G = 64) at W = H ∈
  {56, 28, 14, 7} and infer_1024 (batch 8, G = 16) at W = H ∈ {256, 128,
  64, 32} — the plan the heuristic picks, which is the plan they run
  (every enumerated plan there would add minutes of compiles);
* the fused chunked-prefill attention kernel at the hybrid cell's shape
  (a 2048-token chunk over a 17,408-row bf16 cache, 32 query heads on 8
  KV heads of dim 64), as the model's dispatch selects it for a TPU;
* granite-4.0-h-micro's serving programs at the hybrid cell's pool (a
  memory check that the decode step and a prompt chunk, with the chunk
  attention kernel in it, fit one chip beside 32 slots of SSM state and
  KV).

The topology is described inside a module fixture, never while a module
is imported: only the worker that runs this file loads the TPU library.
The persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gspn as G
from repro.kernels import autotune as A
from repro.kernels import gspn_multidir as MK
from repro.kernels import gspn_scan as GS
from repro.kernels import ops
from repro.kernels.spec import ScanSpec

pytestmark = pytest.mark.kernels

# (G, H, W, channels_per_weight)
LM_SHAPES = [(32, 1, 1024, 8), (32, 2, 1024, 8), (32, 3, 1024, 8),
             (32, 64, 1024, 8), (32, 1024, 1, 8), (32, 1024, 3, 8),
             (256, 32, 1024, 8)]
VISION_SHAPES = [(16, 56, 56, 2), (16, 7, 7, 2)]
CELL_STAGE_SHAPES = ([(64, s, s, 2) for s in (56, 28, 14, 7)]
                     + [(16, s, s, 2) for s in (256, 128, 64, 32)])
KINDS = {"fwd": ("fwd", "pallas"), "bwd": ("bwd", "pallas"),
         "pair": ("pair_fwd", "multidir"), "pair_grad": ("pair_bwd",
                                                         "multidir")}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # No skip: an installation without the TPU compiler (libtpu, pulled
    # in by ``jax[tpu]``) fails here rather than reading as covered.
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _emitted_plans(key):
    """Every (row_tile, depth) the tuner may time or the heuristic pick."""
    plans = {(c.row_tile, c.pipeline_depth)
             for c in A.enumerate_candidates(key)}
    plans.add((A.heuristic_row_tile(key), A.heuristic_pipeline_depth(key)))
    return sorted(plans)


def _compile_plans(one_chip, shape, dtype, kind, plans):
    g, h, w, cpw = shape
    gw = g // cpw
    _, impl = KINDS[kind]

    def sds(*s):
        return jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=one_chip)

    if kind in ("fwd", "bwd"):
        data, taps = sds(g, h, w), [sds(gw, h, w)] * 3
    else:
        data, taps = sds(2, g, h, w), [sds(2, gw, h, w)] * 3
    for t, d in plans:
        sp = ScanSpec(impl=impl, channels_per_weight=cpw, row_tile=t,
                      pipeline_depth=d, interpret=False)
        if kind == "fwd":
            fn = lambda x, a, b, c, lam: GS.gspn_scan_fwd_pallas(
                x, a, b, c, lam, spec=sp)
            args = [sds(g, h, w), *taps, sds(g, h, w)]
        elif kind == "bwd":
            fn = lambda dy, a, b, c: GS.gspn_scan_bwd_pallas(
                dy, a, b, c, spec=sp)
            args = [data, *taps]
        elif kind == "pair":
            fn = lambda x, a, b, c, lam: MK.gspn_scan_bidir_pallas(
                x, {"wl": a, "wc": b, "wr": c}, lam, spec=sp)
            args = [sds(g, h, w), *taps, data]
        else:
            fn = lambda dy, a, b, c: MK.gspn_scan_bidir_bwd_pallas(
                dy, a, b, c, spec=sp)
            args = [data, *taps]
        assert "tpu_custom_call" in _compiled_text(fn, args), (t, d)


def _key(shape, dtype, kind):
    g, h, w, cpw = shape
    direction, impl = KINDS[kind]
    return A.ScanKey("tpu-v5-lite", h, w, g, direction, impl, dtype,
                     "float32", cpw > 1)


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", LM_SHAPES + VISION_SHAPES,
                         ids=lambda s: "g{}h{}w{}cpw{}".format(*s))
def test_every_emitted_plan_compiles(one_chip, shape, dtype, kind):
    plans = _emitted_plans(_key(shape, dtype, kind))
    assert plans
    _compile_plans(one_chip, shape, dtype, kind, plans)


@pytest.mark.parametrize("kind", ["pair", "pair_grad"])
@pytest.mark.parametrize("shape", CELL_STAGE_SHAPES,
                         ids=lambda s: "g{}h{}w{}cpw{}".format(*s))
def test_cell_stage_plan_compiles(one_chip, shape, kind):
    """The vision cells' f32 stages take the staged (depth-2) plan on a
    v5e, and it compiles."""
    key = _key(shape, "float32", kind)
    plan = (A.heuristic_row_tile(key), A.heuristic_pipeline_depth(key))
    assert plan[1] == 2
    _compile_plans(one_chip, shape, "float32", kind, [plan])


@pytest.mark.parametrize("pair", [False, True], ids=["single", "pair"])
def test_platform_default_spec_lowers_to_mosaic(one_chip, pair):
    """A spec that leaves ``interpret`` unset — what every model path
    builds — gets the Mosaic kernel when lowered for a TPU, forward and
    gradient, with the tuner's own plan."""
    g, h, w = 16, 56, 56
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    if pair:
        sp = ScanSpec(impl="multidir", channels_per_weight=2)
        args = [sds(g, h, w)] + [sds(2, g // 2, h, w)] * 3 + [sds(2, g, h, w)]
        scan = lambda *a: ops.gspn_scan_pair(*a, spec=sp)
    else:
        sp = ScanSpec(impl="pallas", channels_per_weight=2)
        args = [sds(g, h, w)] + [sds(g // 2, h, w)] * 3 + [sds(g, h, w)]
        scan = lambda *a: ops.gspn_scan(*a, spec=sp)
    assert sp.interpret is None

    def loss(*a):
        return jnp.sum(scan(*a).astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 4)), args)
    assert text.count("tpu_custom_call") >= 2       # forward + adjoint


def test_lm_mixer_at_full_width_compiles(one_chip):
    """The qwen2-1.5b-gspn sequence mixer (d_model 1536, row width 1024,
    bf16 streams) over a 3-row prompt: both folded passes lower to Mosaic
    kernels inside the layer."""
    cfg = G.GSPNSeqConfig(dim=1536, proxy_dim=8, row_width=1024,
                          impl="pallas", param_dtype=jnp.bfloat16,
                          compute_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda k: G.init_gspn_seq_mixer(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((4, 3 * 1024, 1536), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(
        lambda p, x: G.apply_gspn_seq_mixer(p, x, cfg), [params, x])
    assert text.count("tpu_custom_call") >= 2       # T→B + within-row


# granite4h.serve_doc's engine on one chip: 32 slots of 16384 + 1024 rows.
HYBRID_SLOTS, HYBRID_ROWS, HYBRID_CHUNK = 32, 17408, 2048
HBM_BYTES = 15.75e9                       # what the v5e compiler allots


def test_chunk_attention_kernel_compiles_at_the_hybrid_cell_shape(one_chip):
    """``chunk_prefill_attention`` lowered for a TPU at serve_doc's chunk
    shape takes the fused kernel: a Mosaic kernel and no key-block loop in
    the compiled program."""
    from repro.models.attention import chunk_prefill_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(chunk_prefill_attention, [
        arg((1, HYBRID_CHUNK, 32, 64), jnp.bfloat16),
        arg((1, HYBRID_ROWS, 8, 64), jnp.bfloat16),
        arg((1, HYBRID_ROWS, 8, 64), jnp.bfloat16), arg((), jnp.int32)])
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text


def test_hybrid_serving_programs_fit_one_chip(one_chip):
    """granite-4.0-h-micro's decode step over the donated 32-slot pool (as
    the engine serves it, and with its logits as an output, as
    ``ServeEngine.score`` replays it), and a padded prompt chunk beside
    that pool, fit one v5e: the decodes' compiled peaks, and the chunk's
    peak plus the resident pool, stay under the chip's HBM (the in-place
    stage walk and the chunk attention that never holds T x S logits are
    what make them fit; a fresh-buffer walk peaked at 15.9 GB).  The
    chunk program carries the fused attention kernel, once per attention
    layer."""
    from repro.configs.base import with_precision
    from repro.configs.granite_4_0_h_micro import full
    from repro.models import lm
    from repro.serve.engine import sample_tokens
    cfg = with_precision(full(), "bf16")

    def shapes(fn):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), jax.eval_shape(fn))

    params = shapes(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    pool = shapes(lambda: lm.init_lm_cache(cfg, HYBRID_SLOTS, HYBRID_ROWS))
    one = shapes(lambda: lm.init_lm_cache(cfg, 1, HYBRID_ROWS))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(p, token, caches, rng, with_logits):
        logits, new = lm.lm_decode_step(p, cfg, token, caches)
        nxt = sample_tokens(logits[:, 0], rng, 0.0, 0)
        return (nxt, new) + ((logits[:, 0],) if with_logits else ())

    decs = [jax.jit(decode, donate_argnums=2, static_argnums=4).lower(
        params, arg((HYBRID_SLOTS, 1), jnp.int32), pool,
        arg((2,), jnp.uint32), flag).compile().memory_analysis()
        for flag in (False, True)]
    chunk_prog = jax.jit(lambda p, t, c, o, n: lm.lm_prefill_chunk(
        p, cfg, t, c, o, with_logits=True, n_valid=n)).lower(
        params, arg((1, HYBRID_CHUNK), jnp.int32), one,
        arg((), jnp.int32), arg((), jnp.int32)).compile()
    assert chunk_prog.as_text().count("tpu_custom_call") == 4
    chunk = chunk_prog.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    for dec in decs:
        assert dec.peak_memory_in_bytes <= HBM_BYTES
    assert chunk.peak_memory_in_bytes + pool_bytes <= HBM_BYTES
