#!/usr/bin/env python3
"""Smoke run of the GSPN-2 main paths on a TPU, through the entry points a
user calls, with the compiled (Mosaic) scan kernels.

    python chip_smoke.py              # one chip: serve + train
    python chip_smoke.py --four-chip  # four chips: the sharded scan only

One chip (the default):

(a) serve — qwen2-1.5b-gspn at full width (28 layers, d_model 1536,
    vocab 151936, row width 1024) under the bf16 policy, seeded random
    weights, served greedily by ``ServeEngine`` as ``repro.launch.serve``
    builds it: a prompt that folds into 3 grid rows goes through chunked
    prefill (chunk 1024), a short prompt is decoded for 32 tokens.
    Checks, on the chip: the long prompt's prefill logits under
    ``gspn_impl="auto"`` (which must contain the compiled kernel) agree
    with ``gspn_impl="xla"`` to float rounding (relative L2 <= 1e-5:
    the reference rounds where the kernel does, so they agree exactly),
    and the greedy tokens of both agree for the first steps.
(b) train — gspn2-t at 224², batch 8: jitted steps of ``vision_loss`` +
    ``adamw_update`` as ``examples/train_vision.py`` runs them.  Checks:
    every loss is finite; the step-0 loss and gradient norm agree with
    ``impl="xla"``.

Four chips (``--four-chip``): a gspn2-t forward and backward at 512² with
``impl="sp"`` on a 4-device ``seq`` mesh, against the same step unsharded
on one chip; the inputs and outputs must hold a shard on every device.
The pair is compared twice.  At the default matmul precision, which users
run, a TPU's f32 matmul rounds its operands to bf16, so the two steps are
held to the DESIGN.md §10 bf16 bound (relative 1e-2).  Under
``default_matmul_precision("highest")`` every matmul is f32, and loss and
gradient norm must agree to f32 accuracy (1e-4 / 1e-3).

Every earlier line is information: compile seconds (set-up time), run
seconds, the resolved kernel plans.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every check passed.
The script exits non-zero, printing no result, when JAX finds no TPU or a
check fails.  It runs in one process, which holds the chip throughout.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

LONG_PROMPT = 2 * 1024 + 500      # folds into 3 grid rows of 1024
SHORT_PROMPT = 16
SHORT_NEW = 32
LONG_NEW = 8
TOKENS_CHECKED = 8                 # greedy steps that must agree
LOGITS_BOUND = 1e-5                # kernel vs reference, relative L2
TRAIN_BATCH = 8
TRAIN_STEPS = 3
TRAIN_LOSS_BOUND = 1e-4            # f32 step-0 loss, relative
TRAIN_GNORM_BOUND = 1e-3           # f32 step-0 gradient norm, relative
SP_BF16_BOUND = 1e-2               # DESIGN.md §10 bf16 bound, relative


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compile_timed(fn, *args):
    """Ahead-of-time compile ``jax.jit(fn)`` for ``args``; returns the
    executable, its compile seconds, and whether a Mosaic kernel is in
    it."""
    import jax
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    return exe, time.perf_counter() - t0, "tpu_custom_call" in exe.as_text()


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# (a) serve
# ---------------------------------------------------------------------------

def prefill_logits(params, cfg, prompt, chunk, max_len):
    """The long prompt's last-chunk logits through the same chunked
    prefill the engine runs (``lm_prefill_chunk``), each chunk program
    compiled ahead of time so its kernels can be inspected."""
    import jax.numpy as jnp

    from repro.models import lm as lm_mod
    cache = lm_mod.init_lm_cache(cfg, 1, max_len)
    compile_s, run_s, kernels = 0.0, 0.0, True
    exes = {}
    for off in range(0, len(prompt), chunk):
        toks = jnp.asarray(prompt[off:off + chunk], jnp.int32)[None]
        last = off + chunk >= len(prompt)
        key = (toks.shape[1], last)
        args = (params, toks, cache, jnp.asarray(off, jnp.int32))
        if key not in exes:
            def fn(p, t, c, o, _last=last):
                return lm_mod.lm_prefill_chunk(p, cfg, t, c, o,
                                               with_logits=_last)
            exe, s, has_kernel = compile_timed(fn, *args)
            exes[key] = exe
            compile_s += s
            kernels = kernels and has_kernel
        (logits, cache), s = timed(exes[key], *args)
        run_s += s
    return logits, compile_s, run_s, kernels


def serve_requests(params, cfg, prompts, chunk, max_len):
    """Serve ``prompts`` [(tokens, max_new)] greedily through ONE engine,
    twice: the first pass compiles, the second reuses the programs.
    Returns the tokens per request and both passes' seconds."""
    from repro.serve.engine import Request, ServeEngine
    engine = ServeEngine(params, cfg, batch_size=len(prompts),
                         max_len=max_len, temperature=0.0,
                         prefill_chunk=chunk, scheduler="fcfs", seed=0)
    passes = []
    for _ in range(2):
        engine.reset()
        handles = [engine.submit(Request(uid=i, prompt=p, max_new_tokens=n))
                   for i, (p, n) in enumerate(prompts)]
        t0 = time.perf_counter()
        engine.run()
        passes.append(time.perf_counter() - t0)
        results = [h.result() for h in handles]
    chunks = [r.prefill_chunks for r in results]
    return [r.tokens for r in results], passes, chunks


def serve_phase(seed: int) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import qwen2_1_5b_gspn
    from repro.configs.base import with_precision
    from repro.kernels import autotune
    from repro.models.lm import count_params, init_lm

    cfg = with_precision(qwen2_1_5b_gspn.full(), "bf16")
    check(cfg.gspn_impl == "auto", f"LMConfig.gspn_impl={cfg.gspn_impl!r}")
    cfg_xla = dataclasses.replace(cfg, gspn_impl="xla")
    chunk, max_len = cfg.gspn_row_width, 4096
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: init_lm(k, cfg))(jax.random.PRNGKey(seed)))
    log(f"serve: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
        f"vocab {cfg.vocab} row width {cfg.gspn_row_width}, "
        f"{count_params(params) / 1e9:.3f}B params "
        f"({jax.tree.leaves(params)[0].dtype}), "
        f"init {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed)
    long_p = rng.integers(0, cfg.vocab, LONG_PROMPT).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab, SHORT_PROMPT).astype(np.int32)

    out = {}
    for name, c in (("auto", cfg), ("xla", cfg_xla)):
        logits, comp_s, run_s, kernels = prefill_logits(
            params, c, long_p, chunk, max_len)
        log(f"serve[{name}]: chunked prefill of {LONG_PROMPT} tokens: "
            f"compile {comp_s:.1f} s (set-up), run {run_s:.3f} s, "
            f"Mosaic kernel in program: {kernels}")
        if name == "auto":
            check(kernels, "gspn_impl=auto prefill has no compiled kernel")
            log(f"serve[auto]: kernel plans: {autotune.plans_summary()}")
        else:
            check(not kernels, "gspn_impl=xla prefill ran a Pallas kernel")
        toks, passes, chunks = serve_requests(
            params, c, [(long_p, LONG_NEW), (short_p, SHORT_NEW)],
            chunk, max_len)
        check(chunks[0] == 3, f"long prompt took {chunks[0]} prefill chunks")
        log(f"serve[{name}]: engine pass 1 (compiles) {passes[0]:.1f} s, "
            f"pass 2 {passes[1]:.3f} s; prefill chunks {chunks}; "
            f"tokens {[len(t) for t in toks]}")
        out[name] = (np.asarray(logits, np.float32), toks)

    err = rel_l2(out["auto"][0], out["xla"][0])
    log(f"serve: prefill logits auto vs xla: relative L2 {err:.3e} "
        f"(bound {LOGITS_BOUND:g})")
    check(err <= LOGITS_BOUND, f"prefill logits disagree: {err:.3e}")
    for i, (a, x) in enumerate(zip(out["auto"][1], out["xla"][1])):
        n = min(TOKENS_CHECKED, len(a), len(x))
        log(f"serve: request {i} greedy tokens auto {a[:n]} xla {x[:n]}")
        check(list(a[:n]) == list(x[:n]),
              f"request {i}: greedy tokens differ in the first {n} steps")
    return {"logits_rel_l2": err}


# ---------------------------------------------------------------------------
# (b) train
# ---------------------------------------------------------------------------

def train_phase(seed: int) -> dict:
    import dataclasses
    import math

    import jax
    import jax.numpy as jnp

    from repro.configs.gspn2_vision import GSPN2_T
    from repro.data.pipeline import DataConfig, synth_images
    from repro.kernels import autotune
    from repro.models.lm import count_params
    from repro.models.vision import init_vision, vision_loss
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

    cfg, batch, steps = GSPN2_T, TRAIN_BATCH, TRAIN_STEPS
    cfg_xla = dataclasses.replace(cfg, impl="xla")
    params = init_vision(jax.random.PRNGKey(seed), cfg)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=steps,
                       weight_decay=0.01)
    opt = adamw_init(ocfg, params)
    dcfg = DataConfig(vocab=1, seq_len=1, global_batch=batch, seed=seed)
    log(f"train: {cfg.name} at {cfg.img_size}², batch {batch}, "
        f"{count_params(params) / 1e6:.1f}M params")

    def batch_at(s):
        return {k: jnp.asarray(v) for k, v in
                synth_images(dcfg, s, cfg.img_size, cfg.n_classes).items()}

    def step(params, opt, b):
        (loss, _), g = jax.value_and_grad(
            lambda p: vision_loss(p, cfg, b), has_aux=True)(params)
        params, opt, stats = adamw_update(ocfg, g, opt, params)
        return params, opt, loss, stats["grad_norm"]

    def loss_and_gnorm(params, b):
        (loss, _), g = jax.value_and_grad(
            lambda p: vision_loss(p, cfg_xla, b), has_aux=True)(params)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(g)))
        return loss, gn

    b0 = batch_at(0)
    ref_exe, ref_c, ref_kernels = compile_timed(loss_and_gnorm, params, b0)
    check(not ref_kernels, "impl=xla train step ran a Pallas kernel")
    (ref_loss, ref_gn), ref_s = timed(ref_exe, params, b0)
    step_exe, step_c, kernels = compile_timed(step, params, opt, b0)
    log(f"train: step compile {step_c:.1f} s, xla reference compile "
        f"{ref_c:.1f} s (set-up); Mosaic kernel in step: {kernels}")
    check(kernels, "impl=auto train step has no compiled kernel")
    log(f"train: kernel plans: {autotune.plans_summary()}")
    losses, secs = [], []
    gn0 = None
    for s in range(steps):
        (params, opt, loss, gn), sec = timed(step_exe, params, opt,
                                             batch_at(s))
        loss = float(loss)
        losses.append(loss)
        secs.append(sec)
        gn0 = float(gn) if gn0 is None else gn0
        check(math.isfinite(loss), f"step {s}: loss {loss}")
    log(f"train: losses {losses}; step seconds {[round(x, 4) for x in secs]}"
        f" (first includes transfer); xla step-0 {ref_s:.3f} s")
    loss_err = abs(losses[0] - float(ref_loss)) / abs(float(ref_loss))
    gn_err = abs(gn0 - float(ref_gn)) / abs(float(ref_gn))
    log(f"train: step-0 loss auto {losses[0]:.6f} xla {float(ref_loss):.6f}"
        f" (rel {loss_err:.2e}); grad norm auto {gn0:.6f} xla "
        f"{float(ref_gn):.6f} (rel {gn_err:.2e})")
    check(loss_err <= TRAIN_LOSS_BOUND, f"step-0 loss rel err {loss_err}")
    check(gn_err <= TRAIN_GNORM_BOUND, f"step-0 grad norm rel err {gn_err}")
    return {"loss_rel": loss_err, "gnorm_rel": gn_err}


# ---------------------------------------------------------------------------
# --four-chip: the sharded scan
# ---------------------------------------------------------------------------

def four_chip_phase(seed: int) -> dict:
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.gspn2_vision import GSPN2_T
    from repro.data.pipeline import DataConfig, synth_images
    from repro.launch.mesh import make_sp_mesh
    from repro.models.lm import Ctx
    from repro.models.vision import init_vision, vision_loss

    n, batch = 4, 2
    check(len(jax.devices()) >= n, f"{len(jax.devices())} devices, need {n}")
    cfg = dataclasses.replace(GSPN2_T, img_size=512)
    cfg_sp = dataclasses.replace(cfg, impl="sp")
    mesh = make_sp_mesh(n)
    devices = set(mesh.devices.flat)
    params = init_vision(jax.random.PRNGKey(seed), cfg)
    b = synth_images(DataConfig(vocab=1, seq_len=1, global_batch=batch,
                                seed=seed), 0, cfg.img_size, cfg.n_classes)
    log(f"four-chip: {cfg.name} at {cfg.img_size}², batch {batch}, "
        f"seq mesh {dict(mesh.shape)}")

    def grads(c, ctx):
        def f(p, bb):
            (loss, _), g = jax.value_and_grad(
                lambda q: vision_loss(q, c, bb, ctx=ctx), has_aux=True)(p)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(g)))
            return loss, gn, g
        return f

    # Unsharded reference on one chip.
    one = jax.devices()[0]
    p1 = jax.device_put(params, one)
    b1 = jax.device_put({k: jnp.asarray(v) for k, v in b.items()}, one)
    # Sharded: images split over the seq axis (rows), params replicated.
    rep = NamedSharding(mesh, P())
    pn = jax.device_put(params, rep)
    bn = {"images": jax.device_put(
              jnp.asarray(b["images"]),
              NamedSharding(mesh, P(None, "seq", None, None))),
          "labels": jax.device_put(jnp.asarray(b["labels"]), rep)}
    check({s.device for s in bn["images"].addressable_shards} == devices
          and len({s.index for s in bn["images"].addressable_shards}) == n,
          "images are not split over the four devices")

    out = {}
    for prec, loss_bound, gn_bound in (
            ("default", SP_BF16_BOUND, SP_BF16_BOUND),
            ("highest", TRAIN_LOSS_BOUND, TRAIN_GNORM_BOUND)):
        ctx = (contextlib.nullcontext() if prec == "default"
               else jax.default_matmul_precision(prec))
        with ctx:
            exe1, c1, k1 = compile_timed(grads(cfg, None), p1, b1)
            exen, cn, kn = compile_timed(grads(cfg_sp, Ctx(mesh=mesh)),
                                         pn, bn)
        (loss1, gn1, _), s1 = timed(exe1, p1, b1)
        (lossn, gnn, gn_tree), sn = timed(exen, pn, bn)
        log(f"four-chip[{prec}]: one-chip step compile {c1:.1f} s, run "
            f"{s1:.3f} s, Mosaic kernel: {k1}; sharded step compile "
            f"{cn:.1f} s, run {sn:.3f} s, Mosaic kernel: {kn}")
        check(kn, "sharded step has no compiled kernel")
        for name, arr in [("loss", lossn), ("grad_norm", gnn)] + [
                (f"grad[{i}]", g)
                for i, g in enumerate(jax.tree.leaves(gn_tree))]:
            check({s.device for s in arr.addressable_shards} == devices,
                  f"{name} lives on {len(arr.addressable_shards)} device(s)")
        loss_err = abs(float(lossn) - float(loss1)) / abs(float(loss1))
        gn_err = abs(float(gnn) - float(gn1)) / abs(float(gn1))
        log(f"four-chip[{prec}]: inputs split over {n} devices, outputs on "
            f"all {n}; loss sharded {float(lossn):.6f} one-chip "
            f"{float(loss1):.6f} (rel {loss_err:.2e}, bound {loss_bound:g});"
            f" grad norm sharded {float(gnn):.6f} one-chip {float(gn1):.6f} "
            f"(rel {gn_err:.2e}, bound {gn_bound:g})")
        check(loss_err <= loss_bound,
              f"{prec} precision: sharded loss rel err {loss_err}")
        check(gn_err <= gn_bound,
              f"{prec} precision: sharded grad norm rel err {gn_err}")
        out[prec] = {"loss_rel": loss_err, "gnorm_rel": gn_err}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded-scan phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    log(f"device: platform {platform}, kind {kind}, count {len(devs)}; "
        f"compile cache {cache_dir}")
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 3

    try:
        if args.four_chip:
            four_chip_phase(args.seed)
        else:
            serve_phase(args.seed)
            train_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
