"""Device milliseconds per batched decode program in the traced part of
the window."""

from benchlib import readings


def read(run):
    if run.trace is None:
        return None
    ns, calls = readings.program_ns(run, readings.DECODE_PROGRAMS)
    return None if not calls else ns / 1e6 / calls
