"""Share of the chip's peak that the prefill programs reach while they
run: 2 N operations per prefilled token (N the weights a token meets, the
vocabulary head where logits are computed) over their device time."""

from benchlib import readings


def read(run):
    work = readings.prefill_work(run)
    if run.trace is None or not work or not work[0]:
        return None
    ns, calls = readings.program_ns(run, readings.PREFILL_PROGRAMS)
    if not calls or ns <= 0:
        return None
    return 100.0 * work[1] / (ns / 1e9 * run.peaks.flops_per_s)
