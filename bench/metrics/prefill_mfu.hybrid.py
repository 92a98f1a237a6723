"""Share of the chip's peak that the hybrid's prefill programs reach while
they run: the operations of the tokens they prefilled inside the traced
part of the window (matmuls, the SSD chunk work and attention at each
chunk's offset; ``benchlib/hybrid_counts.py``) over their device time.
One-shot prefill computes logits at every position; the engine pads the
hybrid's chunks and computes the logits of the prompt's last token alone,
in its last chunk.  Padding is not counted."""

from benchlib import readings


def read(run):
    served, counts = run.records.get("served"), run.records.get("counts")
    if run.trace is None or served is None or counts is None \
            or len(served.trace_window) != 2:
        return None
    flops = 0.0
    for r in readings.obs_spans(run, "serve.prefill", served.trace_window):
        flops += counts.prefill_flops(r.args["prompt_tokens"], 0, True)
    for r in readings.obs_spans(run, "serve.prefill_chunk",
                                served.trace_window):
        t, off = r.args["tokens"], r.args["offset"]
        last = off + t >= served.prompt_len[r.args["uid"]]
        flops += counts.prefill_flops(t, off, False) + (
            counts.head_flops(1) if last else 0.0)
    ns, calls = readings.program_ns(run, readings.PREFILL_PROGRAMS)
    if not flops or not calls or ns <= 0:
        return None
    return 100.0 * flops / (ns / 1e9 * run.peaks.flops_per_s)
