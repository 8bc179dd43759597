"""Sweeps of the compiled while_loop per invocation
(``CompiledEngine.n_sweeps``); it repeats exactly."""


def read(r):
    return r.sweeps
