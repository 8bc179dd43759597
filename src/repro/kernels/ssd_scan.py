"""Mamba-2 SSD (state-space duality) chunked-scan kernel for TPU.

The SSD algorithm (arXiv:2405.21060 §6) splits the sequence into chunks:
inside a chunk the recurrence is expanded into a small quadratic
"attention-like" form (MXU-friendly matmuls), and *between* chunks a tiny
[P, N] state is carried recurrently.  The published kernel is a GPU Triton
kernel that parallelizes chunks across SMs and then runs a separate
state-passing pass.

TPU adaptation: the Pallas grid executes **sequentially** on the core, so
the inter-chunk state pass needs no separate kernel — the [P, N] fp32 state
simply lives in VMEM scratch and is carried across grid steps along the
chunk axis (the same trick the flash kernel uses for softmax state).  One
kernel therefore fuses all three SSD stages:

    grid = (B, H, n_chunks)        # chunk axis innermost, sequential
    per step:  y  = (tril(C Bᵀ) ⊙ decay) (dt·x)      intra-chunk (MXU)
               y += (C ⊙ head-decay) @ state          inter-chunk read
            state = total-decay * state + (tail-decay·dt·x)ᵀ B
                                                       inter-chunk write

All state math is fp32; inputs may be bf16.  Chunk length and N=d_state
are 128-lane aligned for the assigned configs (chunk=256, N∈{64,128});
P=64 rides the sublane dimension.

The wrapper (ops.ssd_scan) precomputes dA = dt*A and xdt = dt*x outside the
kernel (cheap elementwise, keeps the kernel's input count small) and adds
the D-skip term outside.  Gradients: ``jax.custom_vjp`` recomputes through
the pure-jnp chunked reference (models/layers.ssd_chunked) — the standard
recompute-in-backward trade, noted in DESIGN.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, s0_ref,   # inputs
                y_ref, sout_ref,                          # outputs
                state_ref,                                # VMEM scratch
                *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    xdt = xdt_ref[0, 0].astype(jnp.float32)       # [Q, P]  dt-weighted input
    dA = dA_ref[0, 0].astype(jnp.float32)         # [1, Q]  dt * A  (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)          # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)          # [Q, N]

    # inclusive prefix sums of dA as a lower-triangular matmul (Mosaic has
    # no cumsum lowering): cum_c[i] = cum_r[i] = sum_{j <= i} dA[j]
    tril = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    trilf = tril.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum_c = jax.lax.dot_general(trilf, dA, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)  # [Q, 1]
    cum_r = jax.lax.dot_general(dA, trilf, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)  # [1, Q]
    cum_end = jnp.sum(dA)
    # Intra-chunk decay factors decay[i,j] = exp(cum_i - cum_j), j <= i.
    # Mask the exponent (not the exp) so masked entries are exactly 0 and
    # no inf/NaN can leak through.
    diff = cum_c - cum_r
    decay = jnp.exp(jnp.where(tril, diff, -jnp.inf))        # [Q, Q]

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    y = jax.lax.dot_general(cb * decay, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk read: y[i] += (C_i * exp(cum_i)) @ state   ([Q,N]@[N,P])
    head = jnp.exp(cum_c)                                    # [Q, 1]
    y += jax.lax.dot_general(Cm * head, state_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # inter-chunk write: state = exp(cum_end)*state + (tail·xdt)ᵀ B
    tail = jnp.exp(cum_end - cum_c)                          # [Q, 1]
    new_state = state_ref[...] * jnp.exp(cum_end) + jax.lax.dot_general(
        xdt * tail, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [P, N]
    state_ref[...] = new_state

    @pl.when(ci == nc - 1)
    def _emit_state():
        sout_ref[0, 0] = new_state


def ssd_scan_fwd(xdt: jax.Array, dA: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, s0: jax.Array, *, chunk: int,
                 interpret: bool) -> tuple:
    """Head-major kernel entry.

    xdt: [B, H, S, P] (dt-weighted inputs); dA: [B, H, 1, S];
    Bm/Cm: [B, G, S, N]; s0: [B, H, P, N] fp32 initial state.
    Returns (y [B,H,S,P] fp32, final_state [B,H,P,N] fp32).
    """
    B, H, S, P = xdt.shape
    G, N = Bm.shape[1], Bm.shape[3]
    group = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (B, H, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, sout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c: (b, h // group, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c: (b, h // group, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, dA, Bm, Cm, s0)
    return y, sout
