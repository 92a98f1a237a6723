"""Share of the device's busy time in the traced steps spent in the scan
kernels."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_calls or t.busy_ns <= 0:
        return None
    return 100.0 * t.kernel_ns / t.busy_ns
