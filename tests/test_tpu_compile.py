"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel's semantics on any backend, but it accepts
blocks and slices that Mosaic refuses (unaligned dynamic slices, blocks
that do not tile, ops with no lowering).  These tests hand the kernels
the shapes the main paths use, at published widths, to the TPU compiler
for a chip that is described rather than attached, and check that a
Mosaic custom call is in the compiled program.  Nothing runs: the
results and timings of these kernels come from the chip.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler; it skips
where no v5e topology can be described.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels import ops, ring  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan_fwd  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip,
    with JAX's persistent cache off (an entry written for a described
    chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# channel rings the compiled task graphs carry: (cap, elem, dtype, burst)
RING_CASES = [
    pytest.param(1024, (128,), jnp.bfloat16, 256, id="bf16-128lane-b256"),
    pytest.param(64, (16384,), jnp.float32, 16, id="f32-16384lane-cap64"),
    pytest.param(1024, (16384,), jnp.float32, 16, id="f32-16384lane-cap1024"),
    pytest.param(2, (256, 256), jnp.float32, 1, id="gemm-block-n256"),
    pytest.param(1, (4096,), jnp.float32, 1, id="pagerank-vec-4096"),
    pytest.param(8, (), jnp.int32, 3, id="int32-scalar"),
    pytest.param(5, (3,), jnp.bool_, 2, id="bool-vec"),
]


@pytest.mark.parametrize("cap,elem,dtype,n", RING_CASES)
def test_ring_pop_compiles(spec, cap, elem, dtype, n):
    def pop(buf, head, size):
        return ring.ring_pop(buf, head, size, n, impl="pallas")

    _assert_mosaic(pop, spec((cap,) + elem, dtype), spec((), jnp.int32),
                   spec((), jnp.int32))


@pytest.mark.parametrize("cap,elem,dtype,n", RING_CASES)
def test_ring_push_compiles(spec, cap, elem, dtype, n):
    def push(buf, head, size, arr):
        return ring.ring_push(buf, head, size, arr, impl="pallas")

    _assert_mosaic(push, spec((cap,) + elem, dtype), spec((), jnp.int32),
                   spec((), jnp.int32), spec((n,) + elem, dtype))


def test_eval_guards_compiles(spec):
    t, c = 64, 300

    def guards(sizes, nr, nw, live):
        return ring.eval_guards(sizes, np.full((c,), 4, np.int32), nr, nw,
                                live, impl="pallas")

    _assert_mosaic(guards, spec((c,), jnp.int32), spec((t, c), jnp.int32),
                   spec((t, c), jnp.int32), spec((t,), jnp.bool_))


def test_eval_guards_named_at_paper_array(spec):
    """The guard kernel of the paper's 13x13 systolic GEMM (208 tasks,
    507 channels) is an instruction named ``eval_guards``, the name a
    chip trace gives its device time."""
    t, c = 208, 507

    def guards(sizes, nr, nw, live):
        return ring.eval_guards(sizes, np.full((c,), 2, np.int32), nr, nw,
                                live, impl="pallas")

    text = jax.jit(guards).lower(
        spec((c,), jnp.int32), spec((t, c), jnp.int32),
        spec((t, c), jnp.int32), spec((t,), jnp.bool_)).compile().as_text()
    assert re.search(r"^\s*%eval_guards(\.\d+)? = .*custom-call\(", text,
                     re.M)


def test_decode_attention_compiles(spec):
    """qwen3-0.6b serving widths: 8 slots, 16 q / 8 kv heads, hd 128."""
    B, nh, nkv, hd, smax = 8, 16, 8, 128, 2048

    def attend(q, k, v, lens):
        return ops.decode_attention(q, k, v, lens, impl="pallas")

    _assert_mosaic(attend, spec((B, nh, hd), jnp.bfloat16),
                   spec((B, smax, nkv, hd), jnp.bfloat16),
                   spec((B, smax, nkv, hd), jnp.bfloat16),
                   spec((B,), jnp.int32))


def test_flash_attention_fwd_compiles(spec):
    """qwen3-0.6b prefill widths, head-major, causal."""
    B, nh, nkv, S, hd = 1, 16, 8, 2048, 128

    def fwd(q, k, v):
        return flash_attention_fwd(q, k, v, causal=True, window=None,
                                   interpret=False)

    _assert_mosaic(fwd, spec((B, nh, S, hd), jnp.bfloat16),
                   spec((B, nkv, S, hd), jnp.bfloat16),
                   spec((B, nkv, S, hd), jnp.bfloat16))


def test_ssd_scan_fwd_compiles(spec):
    """mamba2-130m widths: 24 heads of 64, d_state 128, chunk 256."""
    B, H, S, P, G, N, chunk = 1, 24, 2048, 64, 1, 128, 256

    def scan(xdt, dA, bm, cm, s0):
        return ssd_scan_fwd(xdt, dA, bm, cm, s0, chunk=chunk,
                            interpret=False)

    _assert_mosaic(scan, spec((B, H, S, P), jnp.float32),
                   spec((B, H, 1, S), jnp.float32),
                   spec((B, G, S, N), jnp.float32),
                   spec((B, G, S, N), jnp.float32),
                   spec((B, H, P, N), jnp.float32))
