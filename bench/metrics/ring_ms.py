"""Device time of the ring push and pop kernels per invocation.

The program gives its Pallas calls no names, so the trace names each by
its HLO instruction; the ring kernels are the TPU custom calls that take
one scalar-prefetched int32 (the ring offset) first.  The fused guard
kernel is a custom call without one."""

RING_OPS = (r'custom_call_target="tpu_custom_call", '
            r'operand_layout_constraints=\{s32\[1\]\{0\}')


def read(r):
    s = r.op_seconds(RING_OPS)
    return s / r.n * 1e3 if s > 0 else None
