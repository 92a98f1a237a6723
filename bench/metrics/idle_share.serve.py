"""Share of the traced window in which no operation ran on the device
(1 - busy / window), averaged over the chips the cell uses."""

from benchlib import readings


def read(run):
    return readings.idle_share(run)
