"""device: share of the traced window in which no operation ran on the
device, %."""


def read(w):
    return 100.0 * (1.0 - w.device.busy_s / w.device.window_s)
