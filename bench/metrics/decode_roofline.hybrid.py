"""Share of the HBM roofline that the hybrid's batched decode program
reaches: the least bytes a step must move (every weight once, the SSM
state and conv tail of its occupied rows read and written, the KV of the
positions they attend to; ``benchlib/hybrid_counts.py``), at the chip's
HBM bandwidth, over the program's device time.  Rows and KV positions
come from the program's ``serve.decode_step`` spans (``rows``,
``kv_tokens``) in the traced part of the window."""

from benchlib import readings


def read(run):
    served, counts = run.records.get("served"), run.records.get("counts")
    if run.trace is None or served is None or counts is None \
            or len(served.trace_window) != 2:
        return None
    steps = [r for r in readings.obs_spans(run, "serve.decode_step",
                                           served.trace_window)
             if "kv_tokens" in r.args]
    ns, calls = readings.program_ns(run, readings.DECODE_PROGRAMS)
    if not steps or not calls or ns <= 0:
        return None
    mean_bytes = sum(counts.decode_bytes(r.args["rows"], r.args["kv_tokens"])
                     for r in steps) / len(steps)
    least_s = calls * mean_bytes / run.peaks.hbm_bytes_per_s
    return 100.0 * least_s / (ns / 1e9)
