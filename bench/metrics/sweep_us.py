"""Device busy time per sweep of the compiled while_loop."""


def read(r):
    return r.device_ms * 1e3 / r.sweeps if r.sweeps else None
