"""Device time of the ring push and pop kernels per invocation, found
by the names the program gives their Pallas calls (`ring_push`,
`ring_pop`), which the trace shows as the HLO instruction's name."""

RING_KERNELS = r"^%?ring_(push|pop)\b"


def read(r):
    s = r.op_seconds(RING_KERNELS)
    return s / r.n * 1e3 if s > 0 else None
