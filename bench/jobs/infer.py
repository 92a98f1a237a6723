"""Vision inference job: the compiled forward pass (``apply_vision``)
driven for the window on seeded images.

Every step's answer (its logits) is kept on the device.  After the window
a sample of the steps, drawn from the seed and with the last step in it,
is compared image by image with the plain reference on the same images:
the reading is the widest relative L2 gap of an image's logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib import counts, quant, registry, seeding, vision
from benchlib.harness import Check, JobOutput, memory_peak_bytes, scratch_dir


def make_step(pcfg):
    from repro.models.vision import apply_vision

    def infer_step(params, batch):
        return params, apply_vision(params, batch["images"], pcfg)

    return jax.jit(infer_step)


def sample_steps(seed: int, steps: int, k: int) -> list[int]:
    """``k`` step indices drawn from the seed, the last step always in."""
    rng = seeding.rng(seed, "sample")
    pick = set(rng.choice(steps, size=min(k, steps), replace=False).tolist())
    pick.add(steps - 1)
    return sorted(pick)


def reference_inputs(seed: int, cfg: dict, mix: dict):
    side = mix["img_size"]
    params = vision.make_weights(vision.program_config(cfg, side), seed)
    batches = vision.make_batches(seed, mix["batches"], mix["batch"], side,
                                  cfg["n_classes"])
    return params, batches


def reference_logits(cfg, mix, params, images, num=quant.REFERENCE):
    ref = registry.reference(cfg["model"])
    return ref.logits(params, cfg, images, mix["reference_rows"], num)


def widest_gap(answers: dict, want: dict) -> float:
    """Widest relative L2 gap of one image's logits, over the answers
    ``{step: logits}`` against ``want`` ``{step: reference logits}``."""
    worst = 0.0
    for i, p in answers.items():
        r = want[i]
        gap = jnp.linalg.norm(p - r, axis=-1) / jnp.linalg.norm(r, axis=-1)
        worst = max(worst, float(jnp.max(gap)))
    return worst


def checked(seed: int, cfg: dict, mix: dict, answers: dict) -> float:
    """The program's answers at the sampled steps against the reference
    on the same images (step i ran batch i mod the number of batches)."""
    params, batches = reference_inputs(seed, cfg, mix)
    per_batch = {}
    want = {}
    for i in answers:
        bi = i % len(batches)
        if bi not in per_batch:
            per_batch[bi] = reference_logits(cfg, mix, params,
                                             batches[bi]["images"])
        want[i] = per_batch[bi]
    return widest_gap(answers, want)


def controls(seed: int, cfg: dict, mix: dict, records: dict) -> dict:
    """The controls, each put in the program's place on every batch the
    window cycles: the reference at ``high`` matmul precision (one step
    below the float32 at ``highest`` the configuration states), and the
    reference with the scan's streams and carry in bfloat16."""
    params, batches = reference_inputs(seed, cfg, mix)
    want = {i: reference_logits(cfg, mix, params, bt["images"])
            for i, bt in enumerate(batches)}
    out = {}
    for name, num in (("high", quant.HIGH), ("bf16_scan", quant.BF16_SCAN)):
        answers = {i: reference_logits(cfg, mix, params, bt["images"], num)
                   for i, bt in enumerate(batches)}
        out[name] = {"logits": widest_gap(answers, want)}
    return out


def run(ctx) -> JobOutput:
    from repro.kernels import autotune
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    side, b = mix["img_size"], mix["batch"]
    pcfg = vision.program_config(cfg, side)
    params = vision.make_weights(pcfg, ctx.seed)
    batches = vision.make_batches(ctx.seed, mix["batches"], b, side,
                                  cfg["n_classes"])
    jitted = make_step(pcfg)
    step = vision.at_precision(ctx.hook("infer_step", jitted), cfg)
    for bt in batches:                       # compile and warm
        jax.block_until_ready(step(params, bt))
    setup_s = ctx.setup_done()
    ctx.log(f"kernel plans: {autotune.plans_summary()}")
    c0 = ctx.compiles.snapshot()
    params, win = vision.run_window(step, params, batches, 0, ctx.seconds,
                                    keep=lambda out: out)
    c1 = ctx.compiles.snapshot()
    ctx.log(f"window: {win.steps} steps of batch {b} in {win.seconds:.3f} s; "
            f"compiles inside the window: {c1[0] - c0[0]}")
    records = {"images_per_s": win.steps * b / win.seconds,
               "flops_per_image": counts.vision_flops_per_image(
                   dict(cfg, img_size=side), train=False),
               "scan_calls": counts.vision_scan_calls(
                   dict(cfg, img_size=side), b, train=False),
               "compiles_in_window": c1[0] - c0[0]}
    trace = None
    if ctx.trace:
        n = mix["trace_steps"]
        params, trace = vision.traced_steps(
            step, params, batches, win.steps, n,
            scratch_dir("trace") / ctx.cell.name)
        records["trace_steps"] = n
    peak = memory_peak_bytes(vision.planned_bytes(jitted, cfg, params,
                                                  batches[0]))
    picked = sample_steps(ctx.seed, win.steps, mix["check_steps"])
    answers = {i: win.outputs[i] for i in picked}
    steps = win.steps
    del params, batches, step, jitted, win
    gap = checked(ctx.seed, cfg, mix, answers)
    return JobOutput(
        attempted=steps * b, failed=0,
        end_to_end={"setup_s": setup_s,
                    "images_per_s": records["images_per_s"]},
        checks={"logits": Check(gap, cfg["checks"]["infer"]["logits"])},
        memory_peak_bytes=peak, records=records, trace=trace)
