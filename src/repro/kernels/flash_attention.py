"""Flash attention forward kernel for TPU (pl.pallas_call + BlockSpec).

TPU adaptation notes (hw-codesign):

* The grid's innermost dimension iterates KV blocks **sequentially** — on
  TPU, grid steps execute in order on the single core, so the online-softmax
  running state (m, l, acc) lives in VMEM scratch and is carried across KV
  iterations instead of needing atomics/shared-memory reductions as a GPU
  port would.
* Block shapes are MXU/VPU aligned: the score matmul is
  [block_q, hd] x [hd, block_k] with block_q = block_k = 128 by default and
  hd in {64, 128}; the softmax statistics are stored as (block_q, 128) f32
  tiles (lane-width aligned) of which only column 0 is meaningful.
* Causal and sliding-window masks are applied per-block, and blocks that are
  *entirely* masked are skipped with ``pl.when`` — the sequential grid makes
  this a genuine compute saving (GPU persistent kernels need explicit work
  scheduling for the same effect).
* GQA is expressed in the BlockSpec index maps: the K/V index map divides
  the query-head index by ``group`` so kv blocks are fetched once per kv
  head, not once per q head.

The backward pass uses the standard flash recomputation formulated in pure
jnp (fp32) via ``jax.custom_vjp`` — on a real TPU it would get its own
kernel; training paths in this repo default to the XLA attention anyway
(``use_kernel=False``), so the kernel's production role is prefill/serving.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
STATS_LANES = 128          # lane-aligned f32 tile for m/l statistics
NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref,          # inputs
                o_ref, lse_ref,               # outputs
                acc_ref, m_ref, l_ref,        # VMEM scratch
                *, scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # Block-level skip: under a causal mask every k in this block is in the
    # future of every q; under a sliding window every k is out of reach.
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window is not None:
        live &= k_start + block_k - 1 > q_start - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)                 # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                # [bq]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # rows that are entirely masked so far must not poison exp()
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)                  # fully-masked row
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        # lane-broadcast (block_q, 128) tile: a (1, block_q) row block
        # would not tile; the wrapper keeps lane 0
        lse_ref[0, 0] = m_ref[...] + jnp.log(safe)[:, None]


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, window: Optional[int],
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool) -> tuple:
    """q: [B, nh, Sq, hd]; k/v: [B, nkv, Sk, hd] (head-major layout).

    Returns (out [B, nh, Sq, hd], lse [B, nh, Sq] fp32).
    """
    B, nh, Sq, hd = q.shape
    nkv, Sk = k.shape[1], k.shape[2]
    group = nh // nkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    grid = (B, nh, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, block_q=block_q, block_k=block_k)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, STATS_LANES),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, nh, Sq, STATS_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]
