"""Sharded vision training job: the training job's step (``vision_loss``
and ``adamw_update``) with every GSPN scan sharded over the rows of a
``seq`` mesh of the cell's chips (``impl="sp"``, DESIGN.md §8), on one
high-resolution image at a time.

The image is split by rows over the devices and the weights and
optimizer state are replicated; each block's scans exchange only their
compact boundary states (``gspn_sp.exchange``).  Set-up and the window are
the training job's (``bench/jobs/train.py``, imported by path): the first
``check_steps`` steps are checked, then the window runs with one step in
flight.

``correct`` compares the checked steps' losses, the first gradient and
the parameters' change with the plain reference (``bench/refs/
gspn_vision.py``) on one chip, as the training job does; at this
resolution the reference keeps only each block's input for its backward
pass and recomputes the rest (``jax.checkpoint`` per block), so it fits.

The traced steps give ``sp_exchange_share``: the device time of the ops
the compiler emitted under ``gspn_sp.exchange`` (read from the compiled
step's HLO metadata; where no op carries the scope, every collective op)
over the busy time of the busiest chip.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import counts, quant, registry, vision
from benchlib.harness import JobOutput, memory_peak_bytes, scratch_dir
from benchlib.trace import WINDOW, load

train = registry.job("train")

SCOPE = "gspn_sp.exchange"
COLLECTIVES = ("all-gather", "collective-permute", "all-reduce",
               "reduce-scatter", "all-to-all")


def mesh_for(chips: int):
    from repro.launch.mesh import make_sp_mesh
    return make_sp_mesh(chips)


def shardings(pcfg, mesh, image_shape):
    """Replicated weights and labels; the images laid out as the program
    lays out its activations under ``impl="sp"`` (rows over the seq
    axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.lm import Ctx
    from repro.models.vision import activation_spec
    rep = NamedSharding(mesh, P())
    img = NamedSharding(mesh, activation_spec(pcfg, Ctx(mesh=mesh),
                                              image_shape))
    return rep, {"images": img, "labels": rep}


def make_step(pcfg, opt: dict, mesh, image_shape):
    """The training step with the scans and activations sharded over
    ``mesh``'s seq axis (``pcfg`` with ``impl="sp"``); weights and
    optimizer state replicated."""
    from repro.models.lm import Ctx
    from repro.models.vision import vision_loss
    from repro.optim.adamw import adamw_update
    ocfg = train._opt_config(opt)
    ctx = Ctx(mesh=mesh)

    def train_step(state, batch):
        params, opt_state = state
        (loss, _), g = jax.value_and_grad(
            lambda p: vision_loss(p, pcfg, batch, ctx=ctx),
            has_aux=True)(params)
        params, opt_state, _ = adamw_update(ocfg, g, opt_state, params)
        return (params, opt_state), loss

    rep, batch = shardings(pcfg, mesh, image_shape)
    return jax.jit(train_step, in_shardings=((rep, rep), batch),
                   out_shardings=((rep, rep), rep))


# ---------------------------------------------------------------------------
# The reference, in blocks.
# ---------------------------------------------------------------------------

def _forward(ref, params, cfg, images, num):
    """The reference's forward pass with each block rematerialised."""
    cp, shared = cfg["proxy_dim"], cfg["channel_shared"]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = ref._conv(images.astype(jnp.float32), params["stem"]["w"],
                  params["stem"]["b"], 4, num)
    blk = jax.checkpoint(lambda h, p: (ref.block(p, h, cp, shared, num),
                                       None))
    for stage in params["stages"]:
        x, _ = jax.lax.scan(blk, x, stage["blocks"])
        if "down" in stage:
            x = ref._conv(x, stage["down"]["w"], stage["down"]["b"], 2, num)
    x = jnp.mean(ref._ln(x, params["ln_f"]), axis=(1, 2))
    return ref._mm(x, params["head"], num)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _loss_grad(ref, cfg_items, params, batch, num):
    cfg = dict(cfg_items)

    def loss(p):
        logp = jax.nn.log_softmax(_forward(ref, p, cfg, batch["images"], num))
        return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None],
                                             axis=-1))
    return jax.value_and_grad(loss)(params)


def reference_steps(seed: int, cfg: dict, mix: dict,
                    num=quant.REFERENCE) -> dict:
    """The reference's first steps from the seed's weights and image, as
    the training job reads them, computed as ``num`` says."""
    ref = registry.reference(cfg["model"])
    side, b = mix["img_size"], mix["batch"]
    params0 = vision.make_weights(vision.program_config(cfg, side), seed)
    batches = vision.make_batches(seed, mix["batches"], b, side,
                                  cfg["n_classes"])
    items = tuple(sorted((k, cfg[k]) for k in ("proxy_dim",
                                                "channel_shared")))
    state, params = ref.adamw_state(params0), params0
    losses, grad = [], None
    for k in range(mix["check_steps"]):
        loss, grads = _loss_grad(ref, items, params,
                                 batches[k % len(batches)], num)
        params, state, clipped = ref.adamw_step(cfg["optimizer"], params,
                                                grads, state, k + 1)
        losses.append(float(loss))
        if grad is None:
            grad = vision.leaf_norms(clipped)
    change = vision.leaf_norms(params, params0)
    return {"losses": np.array(losses), "grad": grad, "change": change}


def controls(seed: int, cfg: dict, mix: dict, records: dict) -> dict:
    """The controls, each put in the program's place: the reference at
    ``high`` matmul precision and with the scan's streams and carry in
    bfloat16."""
    ref = reference_steps(seed, cfg, mix)
    return {name: {k: c.value for k, c in train.compare(
                reference_steps(seed, cfg, mix, num), ref,
                mix["checks"]).items()}
            for name, num in (("high", quant.HIGH),
                              ("bf16_scan", quant.BF16_SCAN))}


# ---------------------------------------------------------------------------
# The exchange's share of the traced steps.
# ---------------------------------------------------------------------------

def exchange_ops(hlo_text: str) -> tuple[set, str]:
    """Names of the compiled step's instructions under ``SCOPE``; where
    none carries it, every collective instruction.  Returns (names, what
    was found: "scope" or "collectives")."""
    scoped, coll = set(), set()
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not m:
            continue
        name = m.group(1)
        if SCOPE in line:
            scoped.add(name)
        if name.startswith(COLLECTIVES):
            coll.add(name)
    return (scoped, "scope") if scoped else (coll, "collectives")


def exchange_share(path, names: set, chips: int) -> dict:
    """Per device in the trace's window: busy ns (union of its ops) and
    the ns of the ops in ``names``; the share is read on the busiest."""
    pd = load(path)
    win = None
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW and win is None:
                        win = (e.start_ns, e.start_ns + e.duration_ns)
    if win is None:
        return {}
    devs = sorted((p for p in pd.planes
                   if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    busy, exch = [], []
    for plane in devs:
        ivs, ex = [], 0.0
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = max(e.start_ns, win[0])
                t = min(e.start_ns + e.duration_ns, win[1])
                if t <= s:
                    continue
                ivs.append((s, t))
                if e.name.partition(" = ")[0].lstrip("%") in names:
                    ex += t - s
        merged, total = [], 0.0
        for s, t in sorted(ivs):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        total = sum(t - s for s, t in merged)
        busy.append(total)
        exch.append(ex)
    if not busy:
        return {}
    i = int(np.argmax(busy))
    return {"busy_ns": busy, "exchange_ns": exch, "fullest": i}


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------

def run(ctx) -> JobOutput:
    from repro.kernels import autotune
    from repro.optim.adamw import adamw_init
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    side, b = mix["img_size"], mix["batch"]
    mesh = mesh_for(ctx.cell.chips)
    pcfg = dataclasses.replace(vision.program_config(cfg, side), impl="sp")
    image_shape = (b, side, side, cfg["in_chans"])
    rep, shard = shardings(pcfg, mesh, image_shape)
    params = jax.device_put(vision.make_weights(pcfg, ctx.seed), rep)
    params0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
    batches = [jax.device_put(bt, shard) for bt in vision.make_batches(
        ctx.seed, mix["batches"], b, side, cfg["n_classes"])]
    jitted = make_step(pcfg, cfg["optimizer"], mesh, image_shape)
    step = vision.at_precision(ctx.hook("train_step", jitted), cfg)
    ocfg = train._opt_config(cfg["optimizer"])
    state = (params, jax.jit(lambda p: adamw_init(ocfg, p),
                             out_shardings=rep)(params))

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
    ctx.log(f"seq mesh {dict(mesh.shape)}; kernel plans: "
            f"{autotune.plans_summary()}")
    c0 = ctx.compiles.snapshot()

    first = mix["check_steps"]
    state, win = vision.run_window(step, state, batches, first, ctx.seconds)
    c1 = ctx.compiles.snapshot()
    ctx.log(f"window: {win.steps} steps of batch {b} in {win.seconds:.3f} s; "
            f"compiles inside the window: {c1[0] - c0[0]}")
    records = {"images_per_s": win.steps * b / win.seconds,
               "flops_per_image": counts.vision_flops_per_image(
                   dict(cfg, img_size=side), train=True),
               "compiles_in_window": c1[0] - c0[0]}
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        compiled = jitted.lower(state, batches[0]).compile()
    m = compiled.memory_analysis()
    planned = 0 if m is None else int(
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes)
    trace = None
    if ctx.trace:
        names, found = exchange_ops(compiled.as_text())
        n = mix["trace_steps"]
        state, path = vision.traced_steps(step, state, batches,
                                          first + win.steps, n,
                                          scratch_dir("trace") / ctx.cell.name)
        records.update(trace_steps=n, exchange_found=found,
                       exchange_ops=len(names),
                       exchange=exchange_share(path, names, ctx.cell.chips))
        ctx.log(f"exchange: {len(names)} ops by {found}; per device "
                f"{records['exchange']}")
        trace = path
    del compiled
    peak = memory_peak_bytes(planned)
    del state, batches, step, jitted
    checks = train.compare(prog, reference_steps(ctx.seed, cfg, mix),
                           mix["checks"])
    return JobOutput(
        attempted=win.steps * b, failed=0,
        end_to_end={"setup_s": setup_s,
                    "images_per_s": records["images_per_s"]},
        checks=checks, memory_peak_bytes=peak, records=records, trace=trace)

