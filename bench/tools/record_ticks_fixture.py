"""Record the serving trace the tick tests read.

    python3 bench/tools/record_ticks_fixture.py <out_dir>

Runs the tests' tiny serving cell with ``--trace 1`` on the chip, as
``record_fixture.py`` does, but traced from 0.12 s to 0.72 s of its 2-s
window: there the seed's arrivals hold one-shot prompts (one chunk) as
well as chunked ones, so every phase of the engine's tick lies in the
trace.  Writes ``<out_dir>/qwen2gspn.serve_chat.ticks.xplane.pb.gz``
(commit it as ``bench/tests/data/``) and prints the reduction.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

SEED, SECONDS = 11, 2.0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out = pathlib.Path(argv[0])
    out.mkdir(parents=True, exist_ok=True)
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    import tiny
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_ticks_fixture: no TPU", file=sys.stderr)
        return 3
    from benchlib import ticks, trace
    from benchlib.harness import scratch_dir
    mix = dict(tiny.serve_traffic(), trace_start=0.06, trace_seconds=0.6)
    cell = tiny.cell("qwen2gspn.serve_chat", tiny.lm_config(), mix)
    result = run.execute(cell, SEED, SECONDS, True,
                         t_process=time.perf_counter())
    src = trace.Recorder(scratch_dir("trace") / cell.name).file()
    dst = out / f"{cell.name}.ticks.xplane.pb.gz"
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        g.write(f.read())
    print(cell.name, dst.stat().st_size, json.dumps(result["metrics"]),
          json.dumps(ticks.reduce(dst).breakdown(top=40)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
