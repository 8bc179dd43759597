"""Pallas kernels for the compiled interconnect (channel ring buffers).

``CompiledEngine`` lowers every channel to ``(buf[cap, *elem], head,
size)`` carried through a ``lax.while_loop`` — see ``core/synth.py``.
This module provides the three hot ops of that sweep loop as Pallas
kernels with a bit-exact XLA reference:

* :func:`ring_pop`   — pop ``n`` tokens: burst copy out of the ring
  with the head/size update fused into the same op.  The ring stays in
  HBM; the contiguous case (``head + n <= cap``) is ONE DMA at the
  dynamic head offset, the wraparound case one DMA per row.
* :func:`ring_push`  — push ``n`` tokens at ``(head + size) % cap``,
  same contiguous/wrap structure; the output aliases the ring, so only
  the pushed rows move.
* :func:`eval_guards` — fused firing-predicate evaluation: ONE kernel
  computes every task's fire guard from the channel occupancy vector
  (``need_r <= size`` and ``need_w <= cap - size`` reduced over the
  channel axis), replacing N·C scalar ops per sweep with one tiled
  compare-and-reduce.

Backend dispatch mirrors :func:`repro.kernels.ops.decode_attention`
via :mod:`repro.kernels.dispatch`:

* ``"pallas"``    — Mosaic-lowered kernels (TPU default);
* ``"interpret"`` — the same kernels under the Pallas interpreter
  (bit-exact kernel semantics on any backend; the CI parity path);
* ``"xla"``       — the vectorized gather/scatter reference (non-TPU
  default; identical integer index math to the kernels, so every
  graph keeps a bit-exact reference lowering).

The ring kernels are named ``ring_pop`` and ``ring_push``: the HLO
instruction name that a profile shows for each call.

Select with ``impl=`` or ``$REPRO_RING_IMPL``.  All three impls are
exact integer/copy ops — no arithmetic reassociation — so parity is
bitwise, not approximate.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import resolve_impl

RING_ENV = "REPRO_RING_IMPL"
RING_CHOICES = ("pallas", "interpret", "xla")

_SUB = 8      # sublane multiple for fp32/int32 VMEM tiles
_LANE = 128   # lane multiple


def _resolve(impl: Optional[str]) -> str:
    return resolve_impl("ring", RING_ENV, RING_CHOICES,
                        fallback="xla", impl=impl)


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel_dtype(dtype) -> np.dtype:
    """bools ride the kernels as int32 (TPU vregs have no 1-bit lanes);
    the wrappers cast back, which is exact for {0, 1}."""
    d = np.dtype(dtype)
    return np.dtype(np.int32) if d == np.bool_ else d


def _tiled(x: jax.Array) -> jax.Array:
    """``[rows, *elem] -> [rows, R, L]`` with ``(R, L)`` aligned to the
    dtype's HBM tile, so a DMA at any dynamic row offset moves whole
    tiles.  Elements whose last two dims are already aligned keep them
    (merging leading dims is then free); anything else is flattened and
    zero-padded to ``R = sublanes``-multiple rows of 128 lanes."""
    x = x.astype(_kernel_dtype(x.dtype))
    rows, elem = x.shape[0], x.shape[1:]
    sub = _SUB * max(1, 4 // x.dtype.itemsize)      # 8 f32, 16 bf16, 32 i8
    if len(elem) >= 2 and elem[-1] % _LANE == 0 and elem[-2] % sub == 0:
        return x.reshape(rows, -1, elem[-1])
    e = int(np.prod(elem, dtype=np.int64))
    e_p = _ceil(max(e, 1), sub * _LANE)
    flat = jnp.pad(x.reshape(rows, e), ((0, 0), (0, e_p - e)))
    return flat.reshape(rows, e_p // _LANE, _LANE)


def _untiled(t: jax.Array, like_shape: tuple, dtype) -> jax.Array:
    """Inverse of :func:`_tiled` for ``like_shape = (rows, *elem)``."""
    rows, elem = like_shape[0], like_shape[1:]
    e = int(np.prod(elem, dtype=np.int64))
    flat = t.reshape(rows, -1)[:, :e]
    return flat.reshape(like_shape).astype(dtype)


def _ring_call(name: str, kernel, out_shape, scalar, *arrays, aliases=None,
               interpret: bool):
    """One-step ``pallas_call`` named ``name`` (the name profiles and
    HLO dumps give the kernel) with every array left in HBM
    (``pl.ANY``): the kernel moves rows by DMA at the dynamic offset in
    ``scalar`` (scalar-prefetched), so no VMEM block ever holds the ring."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        input_output_aliases=aliases or {},
        interpret=interpret,
        name=name,
    )(jnp.asarray(scalar, jnp.int32).reshape(1), *arrays)


def _copy_rows(src, dst, src_at, dst_at, n: int, cap: int, wrap_src: bool,
               sem):
    """DMA ``n`` ring rows between ``src`` and ``dst``.  The ring side
    starts at ``src_at`` (pop) or ``dst_at`` (push); the contiguous case
    is one DMA, the wraparound case one DMA per row."""
    ring_at = src_at if wrap_src else dst_at

    @pl.when(ring_at + n <= cap)
    def _contig():
        cp = pltpu.make_async_copy(src.at[pl.ds(src_at, n)],
                                   dst.at[pl.ds(dst_at, n)], sem)
        cp.start()
        cp.wait()

    @pl.when(ring_at + n > cap)
    def _wrap():
        def row(i, carry):
            idx = jax.lax.rem(ring_at + i, jnp.int32(cap))
            s_i, d_i = (idx, dst_at + i) if wrap_src else (src_at + i, idx)
            cp = pltpu.make_async_copy(src.at[pl.ds(s_i, 1)],
                                       dst.at[pl.ds(d_i, 1)], sem)
            cp.start()
            cp.wait()
            return carry
        jax.lax.fori_loop(0, n, row, 0)


# ---------------------------------------------------------------------------
# pop
# ---------------------------------------------------------------------------

def _pop_kernel(n: int, cap: int, s_ref, buf_ref, out_ref, sem):
    _copy_rows(buf_ref, out_ref, s_ref[0], 0, n, cap, True, sem)


def ring_pop(buf: jax.Array, head: jax.Array, size: jax.Array, n: int, *,
             impl: Optional[str] = None):
    """Pop ``n`` tokens from a ring buffer.

    Returns ``(toks[n, *elem], new_head, new_size)`` with the head/size
    update fused: ``new_head = (head + n) % cap``, ``new_size = size - n``.
    ``n`` is static (synthesis enforces static I/O rates).
    """
    impl = _resolve(impl)
    cap = buf.shape[0]
    n = int(n)
    new_head = (head + n) % cap
    new_size = size - n
    if n == 0:
        return buf[0:0], new_head, new_size
    if impl == "xla":
        idx = (head + jnp.arange(n, dtype=jnp.int32)) % cap
        return buf[idx], new_head, new_size
    tbuf = _tiled(buf)
    out = _ring_call(
        "ring_pop", partial(_pop_kernel, n, cap),
        jax.ShapeDtypeStruct((n,) + tbuf.shape[1:], tbuf.dtype),
        head, tbuf, interpret=impl == "interpret")
    toks = _untiled(out, (n,) + buf.shape[1:], buf.dtype)
    return toks, new_head, new_size


# ---------------------------------------------------------------------------
# push
# ---------------------------------------------------------------------------

def _push_kernel(n: int, cap: int, s_ref, buf_ref, arr_ref, out_ref, sem):
    # ``out_ref`` aliases ``buf_ref``: only the pushed rows are written
    _copy_rows(arr_ref, out_ref, 0, s_ref[0], n, cap, False, sem)


def ring_push(buf: jax.Array, head: jax.Array, size: jax.Array,
              arr: jax.Array, *, impl: Optional[str] = None):
    """Push ``arr[n, *elem]`` onto a ring buffer at the tail.

    Returns ``(new_buf, head, new_size)`` — the head is unchanged, the
    size update (``size + n``) is fused with the buffer write.
    """
    impl = _resolve(impl)
    cap = buf.shape[0]
    n = int(arr.shape[0])
    new_size = size + n
    if n == 0:
        return buf, head, new_size
    if impl == "xla":
        idx = (head + size + jnp.arange(n, dtype=jnp.int32)) % cap
        return buf.at[idx].set(arr), head, new_size
    tbuf = _tiled(buf)
    out = _ring_call(
        "ring_push", partial(_push_kernel, n, cap),
        jax.ShapeDtypeStruct(tbuf.shape, tbuf.dtype),
        (head + size) % cap, tbuf, _tiled(arr.astype(buf.dtype)),
        aliases={1: 0}, interpret=impl == "interpret")
    return _untiled(out, buf.shape, buf.dtype), head, new_size


# ---------------------------------------------------------------------------
# fused guard evaluation
# ---------------------------------------------------------------------------

def _guard_kernel(nr_ref, nw_ref, occ_ref, spc_ref, live_ref, out_ref):
    ok = (nr_ref[...] <= occ_ref[...]) & (nw_ref[...] <= spc_ref[...])
    allok = jnp.all(ok, axis=1, keepdims=True)            # [Tp, 1]
    out_ref[...] = jnp.where(allok & (live_ref[...] > 0), 1, 0)


def eval_guards(sizes: jax.Array, caps, need_r: jax.Array,
                need_w: jax.Array, live: jax.Array, *,
                impl: Optional[str] = None) -> jax.Array:
    """Fused firing predicates for every task in one op.

    ``sizes[C]`` is the current channel occupancy vector, ``caps[C]``
    the static capacities, ``need_r/need_w[T, C]`` each task's
    *current-phase* per-firing token needs, ``live[T]`` the
    still-has-firings mask.  Returns ``fire[T]`` bool:

        ``fire[t] = live[t] & all_c(need_r[t,c] <= sizes[c])
                            & all_c(need_w[t,c] <= caps[c] - sizes[c])``

    Pure integer comparisons — bit-identical across all impls.  The
    Pallas call is named ``eval_guards``, the name a profile gives it.
    """
    impl = _resolve(impl)
    caps = jnp.asarray(caps, jnp.int32)
    t, c = need_r.shape
    if impl == "xla" or c == 0:
        if c == 0:
            return live
        space = caps - sizes
        ok_r = jnp.all(need_r <= sizes[None, :], axis=1)
        ok_w = jnp.all(need_w <= space[None, :], axis=1)
        return live & ok_r & ok_w
    t_p, c_p = _ceil(t, _SUB), _ceil(c, _LANE)
    pad2 = lambda a: jnp.pad(a.astype(jnp.int32),
                             ((0, t_p - t), (0, c_p - c)))
    row = lambda v: jnp.broadcast_to(
        jnp.pad(v.astype(jnp.int32), (0, c_p - c))[None, :], (t_p, c_p))
    live_m = jnp.broadcast_to(
        jnp.pad(live.astype(jnp.int32), (0, t_p - t))[:, None],
        (t_p, _LANE))
    out = pl.pallas_call(
        _guard_kernel,
        out_shape=jax.ShapeDtypeStruct((t_p, _LANE), jnp.int32),
        interpret=impl == "interpret",
        name="eval_guards",
    )(pad2(need_r), pad2(need_w), row(sizes), row(caps - sizes), live_m)
    return out[:t, 0] > 0
