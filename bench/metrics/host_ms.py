"""The traced time per invocation in which the device was idle: the
runtime's host side (elaborate, lower, key, resolve, copies in and out)."""


def read(r):
    return r.run_ms - r.device_ms
