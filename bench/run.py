#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration and
traffic mix; everything else is found by name (``bench/benchlib/
registry.py``).  Set-up (weights and inputs from the seed, the cell's own
programs compiled or loaded from the persistent cache and warmed) counts
as ``setup_s``; then the job measures for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from the job's records and a profiler trace of a steady part of the run.

Earlier lines are information.  The last lines of standard error, and
the last key of the result, give each number that decides ``correct``
beside its limit.  The last line of standard output is the result, a JSON
object.  The run exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or when the checkout lacks the
program.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# The TPU runtime's logs stay inside the checkout, with the run's traces.
os.environ.setdefault("TPU_LOG_DIR",
                      str(BENCH.parent / ".bench_cache" / "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from benchlib import registry  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, out, dev_kind: str) -> dict:
    """Each per-layer metric's reader, given the job's records and the
    reduced trace; a reader that finds nothing returns None and the metric
    is left out."""
    from benchlib import peaks
    from benchlib.trace import reduce
    summary = reduce(out.trace, cell.chips) if out.trace else None
    run = Reading(cell=cell, records=out.records, trace=summary,
                  peaks=peaks.peaks(dev_kind))
    metrics = {}
    for m in cell.per_layer:
        value = registry.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, summary


class Reading:
    """What a per-layer reader gets."""

    def __init__(self, cell, records, trace, peaks):
        self.cell, self.records, self.trace, self.peaks = \
            cell, records, trace, peaks


def execute(cell, seed: int, seconds: float, trace: bool, tamper=None,
            dev_kind: str | None = None, t_process: float = T_PROCESS):
    """Set up, measure and check one run; returns the result object.  The
    caller has already established which device runs it."""
    from benchlib.harness import (CompileCounter, RunContext, device_info,
                                  enable_compile_cache, log)
    cache = enable_compile_cache()
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     t_process=t_process, compiles=CompileCounter(),
                     tamper=tamper)
    dev = device_info()
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {seed}, {seconds} s, trace {int(trace)};"
        f" device {dev}; compile cache {cache}")
    out = registry.job(cell.traffic["job"]).run(ctx)
    comp = ctx.compiles.snapshot()
    log(f"programs compiled or loaded: {comp[0]} ({comp[2]} cache hits, "
        f"{comp[1]} misses, {comp[3]:.1f} s); slowest: "
        + ", ".join(f"{n} {s:.2f} s" for s, n in ctx.compiles.slowest[::-1]))
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        metrics, summary = per_layer(cell, out, dev_kind or dev["kind"])
    else:
        names = [m["name"] for m in cell.end_to_end]
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in out.end_to_end}
        missing = set(names) - set(metrics)
        if missing:
            raise RuntimeError(f"job gave no {sorted(missing)}")
    result["metrics"] = metrics
    result["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                        "count": dev["count"],
                        "memory_peak_bytes": out.memory_peak_bytes}
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": c.value, "limit": c.limit}
                        for k, c in out.checks.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    root = BENCH.parent
    if not (root / "src" / "repro").is_dir():
        print(f"bench: no program under {root / 'src'}; run from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    try:
        cell = registry.find_cell(args.workload, root)
    except registry.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: JAX found {len(devs)} {devs[0].platform} device(s); "
              f"the cell needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    result = execute(cell, args.seed, args.seconds, bool(args.trace))
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Leave without interpreter finalization: a finished run has been
    # seen to hang there, after its result, until it was killed.
    os._exit(code)
