"""The sharded gspn2-t training job (``bench/jobs/train_sp.py``) on four
of the ``run_sub`` fixture's CPU devices at 64 x 64: ``correct`` holds on
the sound program and fails when the scans' boundary exchange is left
out, the fault a sharded scan can have that no one-chip cell can show."""

import pytest

BODY = """
import pathlib, sys, time, importlib.util
bench = pathlib.Path({root!r}) / "bench"
sys.path[:0] = [str(bench), str(bench / "tests")]
import tiny_hybrid
spec = importlib.util.spec_from_file_location("bench_run_sp",
                                              bench / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
{fault}
r = run.execute(tiny_hybrid.sp_cell(4), 2**31 + 5, 0.5, False,
                t_process=time.perf_counter())
print("CORRECT", r["correct"], r["checks"])
assert r["attempted"] > 0 and r["failed"] == 0
"""

SKIP_EXCHANGE = """
from repro.parallel import gspn_sp
gspn_sp._issue_pair_exchange = lambda cfg, *a: None
"""


@pytest.mark.distributed
@pytest.mark.parametrize("fault,correct", [("", True),
                                           (SKIP_EXCHANGE, False)],
                         ids=["sound", "exchange-left-out"])
def test_sharded_training_job_is_correct_only_with_the_exchange(
        run_sub, fault, correct):
    import pathlib
    root = str(pathlib.Path(__file__).resolve().parents[1])
    out = run_sub(BODY.format(root=root, fault=fault), timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("CORRECT")][-1]
    assert line.startswith(f"CORRECT {correct}"), line
