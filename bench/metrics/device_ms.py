"""Device busy time (the union of op intervals) per invocation."""


def read(r):
    return r.device_ms
