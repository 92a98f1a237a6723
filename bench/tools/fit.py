"""Whether a vision cell's timed step fits the chip at other batch sizes:
an ahead-of-time compile on the chip, which the compiler refuses where
the step's memory exceeds the chip's, and the bytes ``memory_analysis()``
gives for it (arguments, outputs, temporaries).  Nothing runs.

    python3 bench/tools/fit.py --workload gspn2t.train_224 --batches 32,64,128

Prints one JSON line per batch: ``{"batch", "planned_bytes",
"device_bytes", "fits"}``.  A step fits where the compiler accepts it (the
analysis's bytes can exceed the chip's for a step that compiles and runs);
one it refuses for want of memory reads ``"planned_bytes": null, "fits":
false`` with the compiler's line.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def planned(cell, batch: int) -> int:
    import jax
    import jax.numpy as jnp
    from benchlib import registry, vision
    cfg, mix = cell.config, cell.traffic
    side = mix["img_size"]
    pcfg = vision.program_config(cfg, side)
    job = registry.job(mix["job"])
    params = jax.eval_shape(lambda: vision.make_weights(pcfg, 0))
    batch_shape = {
        "images": jax.ShapeDtypeStruct((batch, side, side, 3), jnp.float32),
        "labels": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    if mix["job"] == "train":
        from repro.optim.adamw import adamw_init
        step = job.make_step(pcfg, cfg["optimizer"])
        ocfg = job._opt_config(cfg["optimizer"])
        args = ((params, jax.eval_shape(lambda p: adamw_init(ocfg, p),
                                        params)), batch_shape)
    else:
        step = job.make_step(pcfg)
        args = (params, batch_shape)
    return vision.planned_bytes(step, cfg, *args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", required=True)
    args = ap.parse_args(argv)
    from benchlib import registry
    from benchlib.harness import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("fit: no TPU", file=sys.stderr)
        return 3
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    cell = registry.find_cell(args.workload)
    for b in (int(x) for x in args.batches.split(",")):
        try:
            n = planned(cell, b)
            row = {"batch": b, "planned_bytes": n, "fits": True}
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            row = {"batch": b, "planned_bytes": None, "fits": False,
                   "compiler": str(e).splitlines()[0]}
        print(json.dumps(dict(row, device_bytes=limit)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
