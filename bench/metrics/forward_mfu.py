"""The forward's share of the chip's peak while the device is busy:
operations of every batch dispatched in the window (the configuration's
multiply-accumulates per image, times 2, times the bucket, padding
rows included since the device computes them) over the device's busy
time, over the peak of the configuration's arithmetic (bf16 for fp32,
whose products cannot beat it; int8 for int8), in %.  Layer: program."""


def read(run):
    d = run.device
    if d is None or not d.busy_s or not run.peak:
        return None
    images = sum(attrs["bucket"] for *_x, attrs, _s, _p
                 in run.spans_in_window("dispatch"))
    if not images:
        return None
    return 100.0 * images * run.flops_per_image / d.busy_s / run.peak
