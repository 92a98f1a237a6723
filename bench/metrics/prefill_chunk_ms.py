"""Device milliseconds of the engine's prefill programs per 1024 prompt
tokens they prefilled inside the traced part of the window (one full
chunk's worth)."""

from benchlib import readings


def read(run):
    work = readings.prefill_work(run)
    if run.trace is None or not work or not work[0]:
        return None
    ns, calls = readings.program_ns(run, readings.PREFILL_PROGRAMS)
    return None if not calls else ns / 1e6 / (work[0] / 1024)
