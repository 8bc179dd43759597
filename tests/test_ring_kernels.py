"""Ring-buffer kernel parity (repro.kernels.ring).

The compiled interconnect's channel operations — burst push/pop against
VMEM-resident ring state and the fused all-task guard evaluation — must
be bit-identical across every backend implementation: the XLA reference
path, the Pallas kernel under the interpreter (CI), and the Mosaic-
lowered kernel on a real TPU.  A Python deque is the oracle; the op
sequences force wraparound, capacity-1 rings, and full/empty boundaries.
"""

from collections import deque

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels import ring
from repro.kernels.dispatch import is_tpu, resolve_impl

IMPLS = ["xla", "interpret"] + (["pallas"] if is_tpu() else [])


def _mk(counter, n, elem, dtype):
    """n fresh tokens with distinct values (rows counter..counter+n-1)."""
    base = counter + np.arange(n)
    flat = (base[:, None] * 100 +
            np.arange(max(1, int(np.prod(elem, dtype=int))))[None, :])
    arr = flat.reshape((n,) + elem) if elem else flat[:, 0]
    if dtype == np.bool_:
        return (arr % 2).astype(np.bool_)
    return arr.astype(dtype)


def _run_ops(cap, elem, dtype, impl, n_ops=24, seed=0):
    rng = np.random.default_rng(seed)
    buf = jnp.zeros((cap,) + elem, dtype=dtype)
    head = jnp.int32(0)
    size = jnp.int32(0)
    oracle = deque()
    counter = 0
    for _ in range(n_ops):
        free = cap - len(oracle)
        if len(oracle) and (free == 0 or rng.random() < 0.5):
            n = int(rng.integers(1, len(oracle) + 1))
            toks, head, size = ring.ring_pop(buf, head, size, n, impl=impl)
            want = np.stack([oracle.popleft() for _ in range(n)])
            got = np.asarray(toks).reshape(want.shape)
            assert np.array_equal(got, want), (impl, cap, elem)
        else:
            n = int(rng.integers(1, free + 1))
            arr = _mk(counter, n, elem, dtype)
            counter += n
            buf, head, size = ring.ring_push(buf, head, size,
                                             jnp.asarray(arr), impl=impl)
            oracle.extend(arr)
        assert int(size) == len(oracle)


_ORACLE_CASES = [
    (1, (), np.int32),               # capacity-1 ring: every push wraps
    (5, (), np.int32),
    (5, (3,), np.int32),
    (4, (2, 2), np.float32),
    (3, (), np.bool_),               # rides the int32 kernel cast
    (7, (3,), np.float32),
]


def _oracle_params():
    # the sequential interpreter costs ~3s per op sequence, so tier-1
    # keeps two representative interpret combos (capacity-1 wraparound +
    # a 2-D float element) and the CI kernel job (-m "") runs the rest
    out = []
    for impl in IMPLS:
        for i, (cap, elem, dtype) in enumerate(_ORACLE_CASES):
            heavy = impl == "interpret" and i not in (0, 3)
            marks = (pytest.mark.slow,) if heavy else ()
            out.append(pytest.param(cap, elem, dtype, impl, marks=marks))
    return out


@pytest.mark.parametrize("cap,elem,dtype,impl", _oracle_params())
def test_ring_matches_deque_oracle(cap, elem, dtype, impl):
    _run_ops(cap, elem, dtype, impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_ring_preserves_sentinel_bits(impl):
    # EoT/sentinel payloads: NaN, infinities and signed zero must round-
    # trip bit-exactly through the ring (no arithmetic on the payload)
    vals = np.array([np.nan, -np.inf, np.inf, -0.0, 1.5e-38],
                    np.float32)
    buf = jnp.zeros((5,), jnp.float32)
    buf, head, size = ring.ring_push(buf, jnp.int32(3), jnp.int32(0),
                                     jnp.asarray(vals), impl=impl)
    toks, _, size = ring.ring_pop(buf, jnp.int32(3), size, 5, impl=impl)
    assert np.asarray(toks).tobytes() == vals.tobytes()
    assert int(size) == 0


def _guards_ref(sizes, caps, need_r, need_w, live):
    t = need_r.shape[0]
    out = np.zeros(t, bool)
    for ti in range(t):
        out[ti] = bool(live[ti]) and \
            all(need_r[ti, c] <= sizes[c] for c in range(len(caps))) and \
            all(need_w[ti, c] <= caps[c] - sizes[c]
                for c in range(len(caps)))
    return out


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("t,c,seed", [(1, 1, 0), (3, 2, 1), (17, 9, 2),
                                      (8, 130, 3)])
def test_eval_guards_matches_reference(impl, t, c, seed):
    rng = np.random.default_rng(seed)
    caps = rng.integers(1, 6, c).astype(np.int32)
    sizes = np.array([rng.integers(0, k + 1) for k in caps], np.int32)
    need_r = rng.integers(0, 4, (t, c)).astype(np.int32)
    need_w = rng.integers(0, 4, (t, c)).astype(np.int32)
    live = rng.integers(0, 2, t).astype(bool)
    got = np.asarray(ring.eval_guards(jnp.asarray(sizes), jnp.asarray(caps),
                                      jnp.asarray(need_r),
                                      jnp.asarray(need_w),
                                      jnp.asarray(live), impl=impl))
    want = _guards_ref(sizes, caps, need_r, need_w, live)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("impl", IMPLS)
def test_ring_ops_trace_under_jit(impl):
    @jax.jit
    def f(buf, head, size, arr):
        buf, head, size = ring.ring_push(buf, head, size, arr, impl=impl)
        return ring.ring_pop(buf, head, size, 2, impl=impl)

    buf = jnp.zeros((4, 3), jnp.float32)
    arr = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    toks, head, size = f(buf, jnp.int32(2), jnp.int32(0), arr)
    assert np.array_equal(np.asarray(toks), np.asarray(arr))
    assert int(size) == 0


@pytest.mark.parametrize("shape,dtype,tiled", [
    ((1024, 128), jnp.bfloat16,
     (1024, 16, 128)),                      # 16-bit rows: 16 sublanes
    ((2, 256, 256), jnp.float32, (2, 256, 256)),   # aligned: kept as is
    ((64, 16384), jnp.float32, (64, 128, 128)),
    ((8,), jnp.int32, (8, 8, 128)),         # scalars pad to one tile
    ((5, 3), jnp.bool_, (5, 8, 128)),       # bools ride as int32
])
def test_ring_rows_tile_aligned(shape, dtype, tiled):
    """The DMA kernels move whole HBM tiles: every ring is viewed as
    [rows, R, L] with (R, L) a multiple of the dtype's tile, and the
    view round-trips exactly."""
    x = jnp.asarray(np.arange(int(np.prod(shape))).reshape(shape) % 7,
                    dtype)
    t = ring._tiled(x)
    assert t.shape == tiled
    assert np.array_equal(np.asarray(ring._untiled(t, x.shape, x.dtype)),
                          np.asarray(x))


def test_dispatch_precedence(monkeypatch):
    # explicit arg > environment > backend fallback
    monkeypatch.setenv(ring.RING_ENV, "interpret")
    assert resolve_impl("ring", ring.RING_ENV, ring.RING_CHOICES,
                        fallback="xla") == "interpret"
    assert resolve_impl("ring", ring.RING_ENV, ring.RING_CHOICES,
                        fallback="xla", impl="xla") == "xla"
    monkeypatch.delenv(ring.RING_ENV)
    want = "pallas" if is_tpu() else "xla"
    assert resolve_impl("ring", ring.RING_ENV, ring.RING_CHOICES,
                        fallback="xla") == want


def test_dispatch_rejects_unknown_impl(monkeypatch):
    with pytest.raises(ValueError, match="ring"):
        ring.ring_pop(jnp.zeros(4), jnp.int32(0), jnp.int32(2), 1,
                      impl="cuda")
    monkeypatch.setenv(ring.RING_ENV, "nope")
    with pytest.raises(ValueError, match="REPRO_RING_IMPL"):
        ring.ring_pop(jnp.zeros(4), jnp.int32(0), jnp.int32(2), 1)
