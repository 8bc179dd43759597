"""Device time of the cut-channel exchange per invocation, averaged over
the cell's chips: the collective-permute ops (start and done) that the
partitioned program's `lax.ppermute`s of cut channels lower to.  It has
no other collective, so the ops are found by opcode."""

COLLECTIVE_OPS = r" collective-permute(-start|-done)?\("


def read(r):
    s = r.op_seconds(COLLECTIVE_OPS)
    return s / r.n * 1e3 if s > 0 else None
