"""Record the small device traces the trace-reduction test reads.

    python3 bench/tools/record_fixture.py <out_dir>

Runs the tests' tiny training and serving cells with ``--trace 1`` on the
chip and copies each ``.xplane.pb`` to ``<out_dir>/<cell>.xplane.pb``
(commit them as ``bench/tests/data/``).  Prints each reduction.
"""

from __future__ import annotations

import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]


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
        print("record_fixture: no TPU", file=sys.stderr)
        return 3
    from benchlib import trace
    from benchlib.harness import scratch_dir
    cells = [tiny.cell("gspn2t.train_224", tiny.vision_config(),
                       tiny.vision_traffic("train_224")),
             tiny.cell("qwen2gspn.serve_chat", tiny.lm_config(),
                       tiny.serve_traffic())]
    for cell in cells:
        result = run.execute(cell, 11, 2.0, True, t_process=time.perf_counter())
        src = trace.Recorder(scratch_dir("trace") / cell.name).file()
        dst = out / f"{cell.name}.xplane.pb"
        shutil.copy(src, dst)
        print(cell.name, dst.stat().st_size, result["metrics"],
              result.get("breakdown"), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
