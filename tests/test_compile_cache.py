"""Compile cache: structural hashing (fast) + store behaviour (slow).

Hash-only tests run in tier-1; anything that triggers an XLA compile or
spawns a subprocess is marked slow.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.compile_cache import (CompileCache, instance_key,
                                      structural_digest)

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# structural hash (no JAX compiles — tier-1)
# ---------------------------------------------------------------------------

def _make_stage(coef, shift):
    def stage(x):
        return x * coef + shift
    return stage


def test_recreated_closures_hash_equal():
    """The failure mode of id(fn): re-created identical closures must
    dedup to one definition."""
    assert structural_digest(_make_stage(2.0, 1)) == \
        structural_digest(_make_stage(2.0, 1))


def test_edited_constant_dirties_hash():
    base = structural_digest(_make_stage(2.0, 1))
    assert structural_digest(_make_stage(2.5, 1)) != base
    assert structural_digest(_make_stage(2.0, 2)) != base


def test_closure_array_content_hashed():
    """Closure-captured weights are part of the compiled program."""
    w1, w2 = np.ones(4), np.ones(4) * 2

    def make(w):
        def stage(x):
            return x + w
        return stage

    assert structural_digest(make(w1)) == structural_digest(make(w1.copy()))
    assert structural_digest(make(w1)) != structural_digest(make(w2))


def test_referenced_global_data_hashed():
    import types
    ns1 = {"K": np.eye(2), "np": np}
    ns2 = {"K": np.eye(2) * 3, "np": np}
    src = "def f(x):\n    return np.dot(K, x)\n"
    f1, f2, f3 = [], [], []
    exec(src, ns1); f1 = ns1["f"]           # noqa: E702
    exec(src, ns2); f2 = ns2["f"]           # noqa: E702
    ns3 = {"K": np.eye(2), "np": np}
    exec(src, ns3); f3 = ns3["f"]           # noqa: E702
    assert structural_digest(f1) == structural_digest(f3)
    assert structural_digest(f1) != structural_digest(f2)


def test_instance_key_includes_aval_signature():
    f = _make_stage(2.0, 1)
    a = np.zeros((4, 4), np.float32)
    b = np.zeros((8, 8), np.float32)
    c = np.zeros((4, 4), np.float64)
    assert instance_key(f, (a,)) == instance_key(f, (a.copy(),))
    assert instance_key(f, (a,)) != instance_key(f, (b,))
    assert instance_key(f, (a,)) != instance_key(f, (c,))
    assert instance_key(f, (a,)) != instance_key(f, (a,), extra="x")


def test_jit_wrapped_closures_unwrap_to_content():
    """jax.jit wrappers have no __code__; the digest must reach through
    __wrapped__ or different-weight models would share cache keys."""
    import jax

    def make(w):
        def f(x):
            return x * w
        return f

    assert structural_digest(jax.jit(make(2.0))) == \
        structural_digest(jax.jit(make(2.0)))
    assert structural_digest(jax.jit(make(2.0))) != \
        structural_digest(jax.jit(make(99.0)))


def test_bound_method_receiver_state_hashed():
    class Stepper:
        def __init__(self, w):
            self.w = w

        def step(self, x):
            return x * self.w

    assert structural_digest(Stepper(1.0).step) == \
        structural_digest(Stepper(1.0).step)
    assert structural_digest(Stepper(1.0).step) != \
        structural_digest(Stepper(2.0).step)


def test_global_read_from_nested_lambda_hashed():
    src = "def f(x):\n    g = lambda y: y * W\n    return g(x)\n"
    ns1, ns2, ns3 = {"W": 2.0}, {"W": 99.0}, {"W": 2.0}
    for ns in (ns1, ns2, ns3):
        exec(src, ns)
    assert structural_digest(ns1["f"]) == structural_digest(ns3["f"])
    assert structural_digest(ns1["f"]) != structural_digest(ns2["f"])


def test_inplace_mutation_of_captured_array_dirties_digest():
    """The QoR loop edits weights in place on a live function object; the
    digest must not be memoized past the edit."""
    w = np.ones(4)

    def f(x):
        return x * w

    before = structural_digest(f)
    w[:] = 5.0
    assert structural_digest(f) != before


def test_callable_object_instance_state_hashed():
    """A callable object's behaviour lives in its attributes; Scale(2.0)
    and Scale(3.0) captured in closures must not share a digest."""
    class Scale:
        def __init__(self, c):
            self.c = c

        def __call__(self, x):
            return x * self.c

    def make(op):
        def stage(x):
            return op(x)
        return stage

    assert structural_digest(make(Scale(2.0))) == \
        structural_digest(make(Scale(2.0)))
    assert structural_digest(make(Scale(2.0))) != \
        structural_digest(make(Scale(3.0)))
    # and as the top-level callable itself
    assert structural_digest(Scale(2.0)) != structural_digest(Scale(3.0))


def test_opaque_callables_never_share_keys():
    """C-implemented callables can't be content-hashed; they must get
    unique keys (recompile) rather than colliding (wrong executable)."""
    assert structural_digest(np.add) != structural_digest(np.multiply)


def test_module_and_nonjittable_values_hash_safely():
    """Channels/engines/modules in closures must never crash the hasher
    (graph dedup hashes simulation task bodies too)."""
    import repro.core as core

    def make(obj):
        def stage():
            return obj
        return stage

    for obj in (core, object(), {"nested": [core, (1, {2})]},
                lambda x: x + 1):
        assert isinstance(structural_digest(make(obj)), str)


def test_legacy_key_warns():
    from repro.core.hier_compile import StageInstance
    inst = StageInstance(fn=_make_stage(1.0, 0), args=())
    with pytest.warns(DeprecationWarning):
        inst.legacy_key


# ---------------------------------------------------------------------------
# memo store (file I/O only — tier-1)
# ---------------------------------------------------------------------------

def test_cache_root_follows_jax_compilation_cache_dir(monkeypatch,
                                                     tmp_path):
    """One variable places both caches: JAX's at its root, this repo's
    executable store under ``repro/``; unset, both sit at a fixed path
    in the checkout (never a temp name)."""
    from repro.core import compile_cache as ccm
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ccm.cache_root() == tmp_path
    assert CompileCache().root == tmp_path / "repro"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = Path(__file__).resolve().parent.parent
    assert ccm.cache_root() == checkout / ".cache" / "jax"
    assert CompileCache().root == checkout / ".cache" / "jax" / "repro"


def test_instance_key_folds_device_kind(monkeypatch):
    """An executable built for one TPU generation is a miss on another."""
    from repro.core import compile_cache as ccm

    def f(x):
        return x + 1

    arg = (np.zeros((4,), np.float32),)
    keys = set()
    for kind in ("tpu/TPU v5 lite", "tpu/TPU v6 lite"):
        monkeypatch.setattr(ccm, "toolchain_tag", lambda k=kind: k)
        keys.add(instance_key(f, arg))
    assert len(keys) == 2


def test_disk_hit_keeps_the_executables_devices(tmp_path):
    """A one-device executable loaded from disk on a host with several
    devices runs on its own device, not spread over all of them."""
    body = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.core.compile_cache import CompileCache
        assert len(jax.devices()) == 4
        x = jnp.arange(8.0)
        cc = CompileCache(root={str(tmp_path)!r})
        cc.compile_cached(lambda v: v * 2, (x,), key="k")
        cc.clear_memory()
        exe, src = cc.get_with_source("k")
        assert src == "disk", src
        print("OUT", exe(x).tolist())
    """)
    r = subprocess.run(
        [sys.executable, "-c", body], capture_output=True, text=True,
        timeout=300,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OUT [0.0, 2.0, 4.0" in r.stdout


def test_memo_roundtrip_and_corrupt_recovery(tmp_path):
    cc = CompileCache(root=tmp_path)
    key = "ab" + "0" * 62
    assert cc.memo_get(key) is None
    cc.memo_put(key, {"flops": 1.5, "bytes": 2})
    assert cc.memo_get(key) == {"flops": 1.5, "bytes": 2}
    assert cc.stats.memo_hits == 1
    # corrupt the entry: recovery deletes it and reports a miss
    p = cc._path(key, "memo")
    p.write_text("{not json")
    assert cc.memo_get(key) is None
    assert cc.stats.corrupt == 1
    assert not p.exists()


def test_lru_eviction_bound(tmp_path):
    import os
    import time
    cc = CompileCache(root=tmp_path, max_bytes=1 << 20)
    keys = [f"{i:02x}" + "0" * 62 for i in range(4)]
    for i, k in enumerate(keys):
        cc.memo_put(k, {"pad": "x" * 100})
        # strictly order mtimes (coarse filesystem timestamps)
        os.utime(cc._path(k, "memo"), (time.time() + i, time.time() + i))
    cc.max_bytes = 256           # shrink the bound: next op must evict
    cc.evict_to_fit()
    assert cc.disk_bytes() <= 256
    assert cc.stats.evictions >= 1
    # the newest entry survives, the oldest went first
    assert cc._path(keys[-1], "memo").exists()
    assert not cc._path(keys[0], "memo").exists()


# ---------------------------------------------------------------------------
# executable store + incremental compile (XLA compiles — slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_hit_miss_and_warm_restart(tmp_path):
    import jax.numpy as jnp

    def make(c):
        def f(x):
            return jnp.tanh(x) * c
        return f

    cc = CompileCache(root=tmp_path)
    x = jnp.ones((8, 8))
    exe, src = cc.compile_cached(make(1.5), (x,))
    assert src == "compiled" and cc.stats.misses == 1
    exe2, src2 = cc.compile_cached(make(1.5), (x,))
    assert src2 == "memory" and exe2 is exe
    cc.clear_memory()                       # simulate process restart
    exe3, src3 = cc.compile_cached(make(1.5), (x,))
    assert src3 == "disk"
    np.testing.assert_allclose(np.asarray(exe3(x)), np.asarray(exe(x)))


@pytest.mark.slow
def test_corrupt_executable_recovers(tmp_path):
    import jax.numpy as jnp

    def f(x):
        return x * 3.0

    cc = CompileCache(root=tmp_path)
    x = jnp.ones((4,))
    _, src = cc.compile_cached(f, (x,))
    assert src == "compiled"
    key = instance_key(f, (x,))
    cc._path(key).write_bytes(b"garbage not a pickle")
    cc.clear_memory()
    exe, src2 = cc.compile_cached(f, (x,))   # recovery: delete + recompile
    assert src2 == "compiled" and cc.stats.corrupt == 1
    np.testing.assert_allclose(np.asarray(exe(x)), 3.0)


@pytest.mark.slow
def test_incremental_recompile_one_dirty_definition(tmp_path):
    import jax.numpy as jnp

    from repro.core.hier_compile import (StageInstance, compile_stages,
                                         diff_definitions)

    def make(c):
        def f(x):
            return jnp.tanh(x @ x.T) * c
        return f

    x = jnp.ones((16, 16))

    def instances(coefs):
        return [StageInstance(fn=make(c), args=(x,), name=f"s{i}")
                for i, c in enumerate(coefs)]

    cc = CompileCache(root=tmp_path)
    prev = compile_stages(instances([1.0, 2.0, 3.0] * 4), cache=cc)
    assert prev.n_unique == 3 and prev.n_compiled == 3
    # edit one definition (2.0 -> 2.5): only it recompiles
    edited = instances([1.0, 2.5, 3.0] * 4)
    clean, dirty = diff_definitions(prev, edited)
    assert len(clean) == 2 and len(dirty) == 1
    rep = compile_stages(edited, cache=CompileCache(root=tmp_path / "i"),
                         prev=prev)
    assert rep.n_reused == 2 and rep.n_compiled == 1
    assert all(i.executable is not None for i in edited)


@pytest.mark.slow
def test_cross_process_reuse_and_gaussian_zero_compiles(tmp_path):
    """The acceptance bar: a second elaborate+compile_stages run of the
    gaussian app — in a *fresh process* pointed at the same cache root —
    performs zero XLA compilations."""
    body = textwrap.dedent("""
        import json, numpy as np
        from repro.apps import gaussian
        g, rep, prog = gaussian.compile_app(iters=4)
        img = np.random.default_rng(0).standard_normal((12, 12)) \\
            .astype(np.float32)
        out = np.asarray(prog(img))
        ref = img
        for _ in range(4):
            ref = gaussian._stencil_ref(ref)
        assert float(np.abs(out - ref).max()) < 1e-4
        print("REPORT", json.dumps({
            "n_compiled": rep.n_compiled,
            "n_cache_hits": rep.n_cache_hits,
            "sources": sorted(set(rep.sources.values()))}))
    """)
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", body], capture_output=True, text=True,
            timeout=600,
            env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                 "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
        assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
        line = [l for l in r.stdout.splitlines() if l.startswith("REPORT")]
        outs.append(json.loads(line[0][len("REPORT "):]))
    assert outs[0]["n_compiled"] == 3          # cold: 3 unique definitions
    assert outs[1]["n_compiled"] == 0          # warm process: all from disk
    assert outs[1]["sources"] == ["disk"]


@pytest.mark.slow
def test_serve_warmup_through_cache(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import (Request, ServeConfig, ServingEngine,
                                    serve_requests)

    V = 16

    def prefill(toks):
        cache = jnp.sum(toks.astype(jnp.float32), axis=1)
        return jax.nn.one_hot((toks[:, -1] + 1) % V, V), cache

    def decode(tok, cache):
        return jax.nn.one_hot((tok + 1) % V, V), cache + 1.0

    cc = CompileCache(root=tmp_path)
    eng = ServingEngine(ServeConfig(batch_slots=2), prefill, decode)
    info = eng.warmup(prompt_len=3, cache=cc)
    assert info["ok"] and info["prefill"] == "compiled"
    res = serve_requests(eng, [Request(0, [1, 2, 3], max_new=3)])
    assert res[0] == [4, 5, 6]
    # a second engine (same shapes) resolves warmup from the cache
    eng2 = ServingEngine(ServeConfig(batch_slots=2), prefill, decode)
    info2 = eng2.warmup(prompt_len=3, cache=cc)
    assert info2["ok"] and info2["prefill"] in ("memory", "disk")
    # non-jittable toy engines degrade gracefully (np.asarray on a tracer
    # raises at trace time -> warmup falls back to eager)
    eng3 = ServingEngine(
        ServeConfig(),
        lambda t: (np.ones((1, V)) * float(np.asarray(t).sum()),
                   np.zeros(1)),
        lambda t, c: (np.ones((1, V)), c))
    assert eng3.warmup(cache=cc)["ok"] is False


@pytest.mark.slow
def test_cnn_gcn_compiled_apps_match_reference(tmp_path):
    from repro.apps import cnn, gcn

    cc = CompileCache(root=tmp_path)
    rep, prog, ref = cnn.compile_app(cache=cc)
    assert rep.n_unique == 2                  # P*P PEs share one definition
    np.testing.assert_allclose(np.asarray(prog()), ref, atol=1e-3)
    rep2, prog2, ref2 = gcn.compile_app(cache=cc)
    np.testing.assert_allclose(np.asarray(prog2()), ref2, atol=1e-3)
    # re-created closures: zero compiles on a rerun
    rep3, _, _ = cnn.compile_app(cache=cc)
    assert rep3.n_compiled == 0
