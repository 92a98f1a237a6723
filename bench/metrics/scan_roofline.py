"""Share of the scan kernels' roofline: the least time the chip could
take for the scans of the traced steps (per launch the larger of bytes
over HBM bandwidth and operations over peak, from logical shapes,
``benchlib.counts``) over the device time of the Pallas kernels that ran
them."""

from benchlib import peaks


def read(run):
    t, rec = run.trace, run.records
    if t is None or not t.kernel_calls or "trace_steps" not in rec:
        return None
    least = sum(peaks.least_seconds(c.flops(), c.bytes(), run.peaks)
                for c in rec["scan_calls"])
    return 100.0 * rec["trace_steps"] * least / (t.kernel_ns / 1e9)
