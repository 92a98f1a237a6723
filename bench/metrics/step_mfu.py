"""Share of the chip's peak the whole step reaches: images per second in
the measured window times the operations per image (2 per
multiply-accumulate of the published architecture, three passes for a
training step) over the peak."""


def read(run):
    rec = run.records
    if "images_per_s" not in rec:
        return None
    return 100.0 * rec["images_per_s"] * rec["flops_per_image"] \
        / run.peaks.flops_per_s
