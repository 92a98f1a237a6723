"""95th percentile of the wait from a request's due time to its admission
into a slot (host clock, the program's ``request.admitted`` events), over
every request due in the window before the trace started."""

from benchlib import readings


def read(run):
    waits = readings.queue_waits(run)
    return None if not waits else 1e3 * readings.p95(waits)
