"""Read the control, and the faults, against which each cell's limits are
set: the plain reference computed one precision below what the
configuration states, put in the program's place.

    python3 bench/tools/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 20 [--fault half_batch]

For each seed: one run of the cell (a short window at the cell's own
load, every check as a run makes it), then each of the job's controls
(``controls()``) on the same prompts and tokens or the same images.
Prints one JSON line per seed:
``{"seed", "program": {check: value}, "control": {name: {check: value}}}``.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def half_batch(name, step):
    """Fault: the training step sees half its batch, the mean taken over
    the rest."""
    if name != "train_step":
        return step

    def half(state, batch):
        n = batch["images"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return half


FAULTS = {"half_batch": half_batch}


def readings(cell, seed: int, seconds: float, tamper=None) -> dict:
    """The program's readings from one run, and the control's."""
    from benchlib import registry
    from benchlib.harness import CompileCounter, RunContext, log
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=False,
                     t_process=time.perf_counter(),
                     compiles=CompileCounter(), tamper=tamper)
    job = registry.job(cell.traffic["job"])
    out = job.run(ctx)
    program = {k: c.value for k, c in out.checks.items()}
    control = job.controls(seed, cell.config, cell.traffic, out.records)
    log(f"seed {seed}: program {program} control {control}")
    return {"seed": seed, "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    from benchlib import registry
    from benchlib.harness import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    cell = registry.find_cell(args.workload)
    tamper = FAULTS[args.fault] if args.fault else None
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), args.seconds, tamper)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
