"""Share of the traced window in which no op ran on the device,
averaged over the cell's chips."""


def read(r):
    return 100.0 * (1.0 - r.red["busy_s"] / r.red["window_s"])
