"""Vision training job: the compiled step (``vision_loss`` and
``adamw_update``, as ``examples/train_vision.py`` composes them) driven
for the window on seeded images.

Set-up builds the step and its state once, drives them through the first
``check_steps`` steps on batches whose rows all differ, and hands the
same objects to the window.  After the window the plain reference takes
the same first steps from the same seeded weights and images, and
``correct`` compares each step's loss, the first gradient (as the
optimizer got it, read from its first moment) and the parameters' change
after those steps, each leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import counts, quant, registry, vision
from benchlib.harness import Check, JobOutput, memory_peak_bytes, scratch_dir


def _opt_config(opt: dict):
    from repro.optim.adamw import AdamWConfig
    return AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"],
                       grad_clip=opt["grad_clip"],
                       warmup_steps=opt["warmup_steps"],
                       total_steps=opt["total_steps"], schedule="cosine",
                       min_lr_ratio=opt["min_lr_ratio"])


def make_step(pcfg, opt: dict):
    from repro.models.vision import vision_loss
    from repro.optim.adamw import adamw_update
    ocfg = _opt_config(opt)

    def train_step(state, batch):
        params, opt_state = state
        (loss, _), g = jax.value_and_grad(
            lambda p: vision_loss(p, pcfg, batch), has_aux=True)(params)
        params, opt_state, _ = adamw_update(ocfg, g, opt_state, params)
        return (params, opt_state), loss

    return jax.jit(train_step)


def reference_steps(seed: int, cfg: dict, mix: dict,
                    num=quant.REFERENCE) -> dict:
    """The plain reference's first steps from the seed's weights and
    images, computed as ``num`` says (the reference, or a control): the
    losses, the first clipped gradient's leaf norms and the leaf norms of
    the parameters' change after the steps."""
    ref = registry.reference(cfg["model"])
    side, b = mix["img_size"], mix["batch"]
    params0 = vision.make_weights(vision.program_config(cfg, side), seed)
    batches = vision.make_batches(seed, mix["batches"], b, side,
                                  cfg["n_classes"])
    state, params = ref.adamw_state(params0), params0
    losses, grad = [], None
    for k in range(mix["check_steps"]):
        bt = batches[k % len(batches)]
        loss, grads = ref.loss_and_grad(params, cfg, bt["images"],
                                        bt["labels"], mix["reference_rows"],
                                        num)
        params, state, clipped = ref.adamw_step(cfg["optimizer"], params,
                                                grads, state, k + 1)
        losses.append(float(loss))
        if grad is None:
            grad = vision.leaf_norms(clipped)
    change = vision.leaf_norms(params, params0)
    return {"losses": np.array(losses), "grad": grad, "change": change}


def compare(got: dict, ref: dict, limits: dict) -> dict:
    """Each step's loss (relative), the first gradient and the change
    (worst leaf).  Leaves whose reference gradient is under a thousandth
    of the median leaf's move under Adam by round-off alone: they are left
    out of the change."""
    moving = ref["grad"] >= 1e-3 * np.median(ref["grad"])
    return {
        "loss": Check(float(np.max(np.abs(got["losses"] - ref["losses"])
                                   / np.abs(ref["losses"]))), limits["loss"]),
        "grad": Check(vision.worst_leaf_gap(got["grad"], ref["grad"]),
                      limits["grad"]),
        "change": Check(vision.worst_leaf_gap(got["change"], ref["change"],
                                              moving), limits["change"]),
    }


def controls(seed: int, cfg: dict, mix: dict, records: dict) -> dict:
    """The controls, each put in the program's place: the reference at
    ``high`` matmul precision (one step below the float32 at ``highest``
    the configuration states), and the reference with the scan's streams
    and carry in bfloat16."""
    ref = reference_steps(seed, cfg, mix)
    return {name: {k: c.value for k, c in compare(
                reference_steps(seed, cfg, mix, num), ref,
                cfg["checks"]["train"]).items()}
            for name, num in (("high", quant.HIGH),
                              ("bf16_scan", quant.BF16_SCAN))}


def run(ctx) -> JobOutput:
    from repro.kernels import autotune
    from repro.optim.adamw import adamw_init
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    side, b = mix["img_size"], mix["batch"]
    pcfg = vision.program_config(cfg, side)
    params = vision.make_weights(pcfg, ctx.seed)
    params0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
    batches = vision.make_batches(ctx.seed, mix["batches"], b, side,
                                  cfg["n_classes"])
    jitted = make_step(pcfg, cfg["optimizer"])
    step = vision.at_precision(ctx.hook("train_step", jitted), cfg)
    ocfg = _opt_config(cfg["optimizer"])
    state = (params, jax.jit(lambda p: adamw_init(ocfg, p))(params))

    losses, grad = [], None
    b1 = cfg["optimizer"]["b1"]
    for k in range(mix["check_steps"]):
        state, loss = step(state, batches[k % len(batches)])
        losses.append(loss)
        if grad is None:
            grad = vision.leaf_norms(state[1]["m"], scale=1 / (1 - b1))
    change = vision.leaf_norms(state[0], params0)
    prog = {"losses": np.array([float(x) for x in losses]), "grad": grad,
            "change": change}
    del params0
    setup_s = ctx.setup_done()
    ctx.log(f"kernel plans: {autotune.plans_summary()}")
    c0 = ctx.compiles.snapshot()

    first = mix["check_steps"]
    state, win = vision.run_window(step, state, batches, first, ctx.seconds)
    c1 = ctx.compiles.snapshot()
    ctx.log(f"window: {win.steps} steps of batch {b} in {win.seconds:.3f} s; "
            f"compiles inside the window: {c1[0] - c0[0]}")
    records = {"images_per_s": win.steps * b / win.seconds,
               "flops_per_image": counts.vision_flops_per_image(
                   dict(cfg, img_size=side), train=True),
               "scan_calls": counts.vision_scan_calls(
                   dict(cfg, img_size=side), b, train=True),
               "compiles_in_window": c1[0] - c0[0]}
    trace = None
    if ctx.trace:
        n = mix["trace_steps"]
        state, path = vision.traced_steps(step, state, batches,
                                          first + win.steps, n,
                                          scratch_dir("trace") / ctx.cell.name)
        records["trace_steps"] = n
        trace = path
    peak = memory_peak_bytes(vision.planned_bytes(jitted, cfg, state,
                                                  batches[0]))
    del state, batches, step, jitted
    checks = compare(prog, reference_steps(ctx.seed, cfg, mix),
                     cfg["checks"]["train"])
    return JobOutput(
        attempted=win.steps * b, failed=0,
        end_to_end={"setup_s": setup_s,
                    "images_per_s": records["images_per_s"]},
        checks=checks, memory_peak_bytes=peak, records=records, trace=trace)
