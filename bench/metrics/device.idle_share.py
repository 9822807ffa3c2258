"""device.idle_share: the part of the traced window in which no operation
ran on the device, in %, averaged over the cell's chips."""


def read(run):
    t = run.data.get("trace")
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
