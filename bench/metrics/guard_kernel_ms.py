"""Device time of the fused guard evaluation per invocation, found by
the name the program gives its Pallas call (`eval_guards`), which the
trace shows as the HLO instruction's name.  A program that leaves the
call unnamed reads nothing."""

GUARD_KERNEL = r"^%?eval_guards\b"


def read(r):
    s = r.op_seconds(GUARD_KERNEL)
    return s / r.n * 1e3 if s > 0 else None
