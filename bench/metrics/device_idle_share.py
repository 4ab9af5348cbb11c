"""Share of the window in which no operation ran on the device: 1 -
(union of the device's op intervals / window), in %.  Layer: device."""


def read(run):
    d = run.device
    if d is None:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
