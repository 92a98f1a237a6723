"""Device milliseconds idle per engine tick: device 0's idle time inside
the program's ``serve.tick`` spans that lie wholly in the traced window,
over the number of those spans (``benchlib.ticks``)."""

from benchlib import ticks


def read(run):
    t = ticks.of_run(run)
    return None if t is None else t.idle_ns / 1e6 / t.ticks
