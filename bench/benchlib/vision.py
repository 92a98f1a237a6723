"""What the vision jobs share: the program's configuration from a
configuration file, seeded weights and images on the device, the windowed
step loop and the comparison of readings."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import seeding, weights
from benchlib.trace import Recorder

ARCH_KEYS = ("in_chans", "n_classes", "dims", "depths", "proxy_dim",
             "mlp_ratio", "channel_shared")


def program_config(cfg: dict, img_size: int):
    from repro.models.vision import GSPNVisionConfig
    if cfg.get("precision", "f32") != "f32":
        raise ValueError("the vision jobs run the f32 policy only")
    kw = {k: cfg[k] for k in ARCH_KEYS}
    kw["dims"], kw["depths"] = tuple(kw["dims"]), tuple(kw["depths"])
    return GSPNVisionConfig(name=cfg["name"], img_size=img_size, **kw)


def at_precision(step, cfg: dict):
    """``step`` traced and run at the configuration's matmul precision
    (float32 at ``highest``: without it the TPU rounds every float32
    matmul operand to bfloat16)."""
    prec = cfg["matmul_precision"]

    def run(*args):
        with jax.default_matmul_precision(prec):
            return step(*args)
    return run


def planned_bytes(jitted, cfg: dict, *args) -> int:
    """The device memory the compiler plans for one call of ``jitted`` at
    the configuration's precision: arguments, outputs and temporaries,
    less what outputs alias (0 where the backend does not say)."""
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        m = jitted.lower(*args).compile().memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def make_weights(pcfg, seed: int):
    from repro.models.vision import init_vision
    shapes = jax.eval_shape(lambda k: init_vision(k, pcfg),
                            jax.random.PRNGKey(0))
    return weights.make(seeding.key(seed, "weights"), shapes)


def make_batches(seed: int, n: int, batch: int, side: int, n_classes: int):
    """``n`` batches of images (standard normal) and labels, made on the
    device from the seed; every row differs."""
    def make(k):
        imgs = jax.random.normal(jax.random.fold_in(k, 0),
                                 (n, batch, side, side, 3), jnp.float32)
        labels = jax.random.randint(jax.random.fold_in(k, 1), (n, batch), 0,
                                    n_classes, jnp.int32)
        return [{"images": imgs[i], "labels": labels[i]} for i in range(n)]

    return jax.jit(make)(seeding.key(seed, "inputs"))


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    outputs: list           # what each step returned last (its "answer")


def run_window(step, state, batches, first: int, seconds: float,
               keep=None) -> tuple:
    """Drive ``state = step(state, batch)`` for ``seconds`` with one step
    in flight: dispatch step i, then wait for step i-1.  Returns the final
    state and a Window; the rate is taken over all steps and all the time
    from the first dispatch to the last step's end."""
    nb = len(batches)
    kept = []
    t0 = time.perf_counter()
    prev = None
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("step.dispatch"):
            state, out = step(state, batches[(first + i) % nb])
        if keep is not None:
            kept.append(keep(out))
        if prev is not None:
            with jax.profiler.TraceAnnotation("host.sync"):
                jax.block_until_ready(prev)
        prev = out
        i += 1
        if time.perf_counter() - t0 >= seconds:
            jax.block_until_ready(out)
            break
    return state, Window(steps=i, seconds=time.perf_counter() - t0,
                         outputs=kept)


def traced_steps(step, state, batches, first: int, n: int, out_dir):
    """Trace ``n`` whole steps, each waited for before the window closes,
    so the trace holds every kernel of exactly ``n`` steps."""
    nb = len(batches)
    with Recorder(out_dir) as rec:
        prev = None
        for i in range(n):
            with jax.profiler.TraceAnnotation("step.dispatch"):
                state, out = step(state, batches[(first + i) % nb])
            if prev is not None:
                with jax.profiler.TraceAnnotation("host.sync"):
                    jax.block_until_ready(prev)
            prev = out
        with jax.profiler.TraceAnnotation("host.sync"):
            jax.block_until_ready(state)
    return state, rec.file()


@jax.jit
def _norms(tree, minus, scale):
    return jnp.stack([jnp.linalg.norm((a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).ravel())
                      for a, b in zip(jax.tree.leaves(tree),
                                      jax.tree.leaves(minus))]) * scale


def leaf_norms(tree, minus=None, scale=1.0) -> np.ndarray:
    """Per leaf, the norm of ``scale * (tree - minus)``, in one program."""
    if minus is None:
        minus = jax.tree.map(lambda a: jnp.zeros((), a.dtype), tree)
    return np.asarray(_norms(tree, minus, jnp.float32(scale)))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """max over leaves of |prog - ref| / max(ref, median(ref)): the gap
    between the two norms of a leaf, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    med = float(np.median(ref))
    den = np.maximum(ref, med)
    gap = np.abs(prog - ref) / np.where(den > 0, den, 1.0)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()) if gap.size else 0.0
