"""Device programs (``XLA Modules`` runs, engine and eager ones alike)
that start inside the program's ``serve.tick`` spans lying wholly in the
traced window, over the number of those spans (``benchlib.ticks``)."""

from benchlib import ticks


def read(run):
    t = ticks.of_run(run)
    return None if t is None else sum(t.launches.values()) / t.ticks
