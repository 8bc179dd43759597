"""Serving-engine semantics: admission, batched decode, eos, slot churn.

The fast section drives both decode paths with toy step functions (the
batched toy adapter is a pure-jnp counter model so its compiles are
trivial); the slow section checks batched-vs-per-slot greedy parity on a
real reduced model and cross-process compile-cache reuse.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compile_cache import CompileCache
from repro.models.lm import ServingAdapter
from repro.serve import Request, ServeConfig, ServingEngine, serve_requests

SRC = str(Path(__file__).resolve().parent.parent / "src")

V = 16   # toy vocab


# ---------------------------------------------------------------------------
# toy engines for both paths: next token = (prev + 1) % V
# ---------------------------------------------------------------------------

def toy_per_slot_engine(scfg: ServeConfig) -> ServingEngine:
    def prefill(toks):
        last = int(toks[0, -1]) % V
        return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}

    def decode(tok, cache):
        return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

    return ServingEngine(scfg, prefill, decode)


def toy_batched_adapter(max_seq: int) -> ServingAdapter:
    """Minimal ServingAdapter: the 'model' is a mod-V counter.  The packed
    cache is {"len": [slots], "last": [1, slots]} — every non-"len" leaf
    carries its batch on axis 1, exactly like the real KV pytree."""

    def prefill_fn(params, tokens, true_len, step):
        idx = jnp.clip(true_len - 1, 0, tokens.shape[1] - 1)
        last = jnp.take_along_axis(tokens, idx[:, None], axis=1)[:, 0]
        first = (last + 1) % V
        cache = {"len": jnp.asarray(true_len, jnp.int32),
                 "last": first[None].astype(jnp.int32)}
        return first.astype(jnp.int32), cache

    def step_fn(params, tokens, packed, step):
        live = packed["len"] > 0
        nxt = jnp.where(live, (tokens + 1) % V, 0).astype(jnp.int32)
        return nxt, {"len": jnp.where(live, packed["len"] + 1, 0),
                     "last": nxt[None]}

    from repro.models.lm import retire_slot, write_slot

    class ToyAdapter(ServingAdapter):
        def init_slots(self, slots, abstract=False):
            mk = (jax.ShapeDtypeStruct if abstract
                  else lambda s, d: jnp.zeros(s, d))
            return {"len": mk((slots,), jnp.int32),
                    "last": mk((1, slots), jnp.int32)}

    return ToyAdapter(cfg=None, max_seq=max_seq,
                      prefill_fn=prefill_fn, step_fn=step_fn,
                      write_slot_fn=write_slot, retire_fn=retire_slot)


def toy_batched_engine(scfg: ServeConfig) -> ServingEngine:
    eng = ServingEngine(scfg, batched=toy_batched_adapter(scfg.max_seq))
    info = eng.warmup(cache=CompileCache(disk=False))
    assert info["ok"], info
    return eng


ENGINES = {"per_slot": toy_per_slot_engine, "batched": toy_batched_engine}


def expected(prompt, max_new, eos=-1):
    last = (prompt[-1] if prompt else 0) % V
    out = []
    for _ in range(max_new):
        last = (last + 1) % V
        out.append(last)
        if eos >= 0 and last == eos:
            break
    return out


# ---------------------------------------------------------------------------
# semantics both decode paths must preserve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["per_slot", "batched"])
def test_eos_token_early_stop(variant):
    scfg = ServeConfig(batch_slots=2, max_seq=32, eos_token=5,
                       prefill_buckets=(8,))
    eng = ENGINES[variant](scfg)
    # prompt ends at 3 -> generates 4, 5(eos): stops after 2 of 8 tokens;
    # prompt ends at 5 -> generates 6..: runs to max_new
    reqs = [Request(0, [1, 2, 3], max_new=8),
            Request(1, [5], max_new=4)]
    res = serve_requests(eng, reqs)
    assert res[0] == [4, 5]
    assert res[1] == [6, 7, 8, 9]


@pytest.mark.parametrize("variant", ["per_slot", "batched"])
def test_more_requests_than_slots_churn(variant):
    scfg = ServeConfig(batch_slots=2, max_seq=32, prefill_buckets=(8,))
    eng = ENGINES[variant](scfg)
    reqs = [Request(i, [(3 * i) % V], max_new=2 + i % 3)
            for i in range(9)]
    res = serve_requests(eng, reqs)
    assert set(res) == set(range(9))
    for r in reqs:
        assert res[r.rid] == expected(r.prompt, r.max_new), r.rid


@pytest.mark.parametrize("variant", ["per_slot", "batched"])
def test_empty_prompt_and_zero_max_new(variant):
    scfg = ServeConfig(batch_slots=2, max_seq=32, prefill_buckets=(8,))
    eng = ENGINES[variant](scfg)
    res = serve_requests(eng, [Request(0, [], max_new=3),
                               Request(1, [4, 5], max_new=0),
                               Request(2, [7], max_new=2)])
    # empty prompt decodes from a single pad token (token 0)
    assert res[0] == [1, 2, 3]
    assert res[1] == []
    assert res[2] == [8, 9]


@pytest.mark.parametrize("variant", ["per_slot", "batched"])
def test_max_seq_capacity_stop(variant):
    """A request whose prompt + generation would overflow the cache is
    retired at the capacity bound instead of scattering out of range."""
    scfg = ServeConfig(batch_slots=1, max_seq=8, prefill_buckets=(8,))
    eng = ENGINES[variant](scfg)
    res = serve_requests(eng, [Request(0, [1, 2, 3, 4], max_new=32)])
    assert res[0] == expected([1, 2, 3, 4], 4)   # 4 + 4 = max_seq


@pytest.mark.parametrize("variant", ["per_slot", "batched"])
def test_prompt_longer_than_largest_bucket(variant):
    """A prompt that fits no configured bucket pads straight to max_seq
    (and an over-long prompt keeps its most recent max_seq-1 tokens)."""
    scfg = ServeConfig(batch_slots=1, max_seq=16, prefill_buckets=(4,))
    eng = ENGINES[variant](scfg)
    res = serve_requests(eng, [Request(0, [1] * 9 + [7], max_new=2),
                               Request(1, list(range(40)), max_new=2)])
    assert res[0] == [8, 9]
    # 40-token prompt keeps its last 15 tokens (last = 39 = 7 mod V) and
    # the capacity stop retires it after one token (15 + 1 == max_seq)
    assert res[1] == [8]


def test_batched_single_step_call_per_iteration():
    """The tentpole invariant: one jitted decode call per iteration,
    independent of how many slots are live."""
    scfg = ServeConfig(batch_slots=4, max_seq=32, prefill_buckets=(8,))
    eng = toy_batched_engine(scfg)
    calls = {"n": 0}
    step_exe = eng._exe[("step",)]

    def counting(*args):
        calls["n"] += 1
        return step_exe(*args)

    eng._exe[("step",)] = counting
    # one admission wave, staggered finishes: slots stay ragged throughout
    reqs = [Request(i, [i], max_new=mn)
            for i, mn in enumerate((3, 5, 7, 9))]
    res = serve_requests(eng, reqs)
    for r in reqs:
        assert res[r.rid] == expected(r.prompt, r.max_new)
    # the longest request needs 8 decode steps after its prefill token;
    # a per-slot loop would have paid 3+5+7+9-4 = 20 decode calls
    assert calls["n"] == 8, calls["n"]


def test_admission_consumes_peeked_header_once():
    """Regression for the double-peek bug: the scheduler must base
    admission on the peeked header and consume it exactly once (prompt
    token counts must never shift by a stale header read)."""
    scfg = ServeConfig(batch_slots=1, max_seq=32, prefill_buckets=(8,))
    eng = toy_batched_engine(scfg)
    reqs = [Request(i, [(i + 1) % V, (i + 2) % V], max_new=2)
            for i in range(6)]
    res = serve_requests(eng, reqs)
    for r in reqs:
        assert res[r.rid] == expected(r.prompt, r.max_new), r.rid


def test_warmup_reports_bucket_sources():
    scfg = ServeConfig(batch_slots=2, max_seq=32)
    eng = ServingEngine(scfg, batched=toy_batched_adapter(32))
    cc = CompileCache(disk=False)
    info = eng.warmup(cache=cc)
    assert info["ok"]
    assert set(info["buckets"]) == {"1x8", "1x16", "1x32"}
    assert all(v == "compiled" for v in info["buckets"].values())
    assert info["decode"] == "compiled"
    # same process, fresh engine: everything resolves from memory
    eng2 = ServingEngine(scfg, batched=toy_batched_adapter(32))
    info2 = eng2.warmup(cache=cc)
    assert all(v == "memory" for v in info2["buckets"].values())
    assert info2["decode"] == "memory"


# ---------------------------------------------------------------------------
# real model: batched fast path == per-slot seed path (greedy)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_batched_matches_per_slot_on_real_model():
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("qwen3-0.6b").with_reduced()
    params = lm.init_params(cfg, jax.random.key(0))
    max_seq = 32
    scfg = ServeConfig(batch_slots=3, max_seq=max_seq)

    @jax.jit
    def prefill_fn(tokens):
        return lm.prefill(params, cfg, tokens, max_seq=max_seq)

    @jax.jit
    def decode_fn(token, cache):
        return lm.decode_step(params, cfg, token, cache)

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab,
                                    1 + int(rng.integers(0, 13))).tolist(),
                    max_new=4)
            for i in range(7)]
    reqs.append(Request(7, [], max_new=3))            # empty prompt

    want = serve_requests(ServingEngine(scfg, prefill_fn, decode_fn), reqs)

    adapter = lm.serving_adapter(params, cfg, max_seq=max_seq)
    eng = ServingEngine(scfg, batched=adapter)
    assert eng.warmup(cache=CompileCache(disk=False))["ok"]
    got = serve_requests(eng, reqs)
    for r in reqs:
        assert got[r.rid] == want[r.rid], r.rid


@pytest.mark.slow
def test_serving_adapter_rejects_recurrent_families():
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("mamba2-130m").with_reduced()
    params = lm.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="per-slot"):
        lm.serving_adapter(params, cfg, max_seq=32)


@pytest.mark.slow
def test_on_device_sampling_temperature_topk():
    """temperature>0 sampling stays inside the model's support and top_k=1
    degenerates to greedy."""
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("qwen3-0.6b").with_reduced()
    params = lm.init_params(cfg, jax.random.key(0))
    max_seq = 32
    scfg = ServeConfig(batch_slots=2, max_seq=max_seq)
    reqs = [Request(0, [1, 2, 3], max_new=4), Request(1, [9], max_new=4)]

    greedy_ad = lm.serving_adapter(params, cfg, max_seq=max_seq)
    eng_g = ServingEngine(scfg, batched=greedy_ad)
    assert eng_g.warmup(cache=CompileCache(disk=False))["ok"]
    want = serve_requests(eng_g, reqs)

    topk1 = lm.serving_adapter(params, cfg, max_seq=max_seq,
                               temperature=0.7, top_k=1)
    eng_k = ServingEngine(scfg, batched=topk1)
    assert eng_k.warmup(cache=CompileCache(disk=False))["ok"]
    assert serve_requests(eng_k, reqs) == want

    hot = lm.serving_adapter(params, cfg, max_seq=max_seq,
                             temperature=1.5, top_k=8, seed=3)
    eng_h = ServingEngine(scfg, batched=hot)
    assert eng_h.warmup(cache=CompileCache(disk=False))["ok"]
    res = serve_requests(eng_h, reqs)
    assert all(0 <= t < cfg.vocab for seq in res.values() for t in seq)
    assert [len(v) for v in res.values()] == [4, 4]


# ---------------------------------------------------------------------------
# cross-process: a warm serving process pays zero XLA compiles
# ---------------------------------------------------------------------------

_SERVE_PROC = r"""
import json
import jax
import numpy as np
from repro.configs import get_config
from repro.models import lm
from repro.serve import Request, ServeConfig, ServingEngine, serve_requests

cfg = get_config("qwen3-0.6b").with_reduced()
params = lm.init_params(cfg, jax.random.key(0))
max_seq = 32
adapter = lm.serving_adapter(params, cfg, max_seq=max_seq)
eng = ServingEngine(ServeConfig(batch_slots=2, max_seq=max_seq),
                    batched=adapter)
info = eng.warmup()
assert info["ok"], info
rng = np.random.default_rng(0)
reqs = [Request(i, rng.integers(0, cfg.vocab, 4 + 3 * (i % 3)).tolist(), 3)
        for i in range(5)]
res = serve_requests(eng, reqs)
assert len(res) == 5 and all(len(v) == 3 for v in res.values())
report = {"warmup": info,
          "log": [[k, list(map(int, np.ravel(s))), src]
                  for k, s, src in eng.compile_log]}
print("REPORT " + json.dumps(report))
"""


@pytest.mark.slow
def test_second_serving_process_compiles_nothing(tmp_path):
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", _SERVE_PROC], capture_output=True,
            text=True, timeout=600,
            env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                 "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
        assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("REPORT")]
        outs.append(json.loads(line[0][len("REPORT "):]))
    cold, warm = outs
    # first process compiled every warmup shape ...
    assert all(v == "compiled" for v in cold["warmup"]["buckets"].values())
    assert cold["warmup"]["decode"] == "compiled"
    # ... the second resolves every one of them (and every lazily-resolved
    # serving shape: larger prefill batches, write_slot, retire) from disk
    assert all(v == "disk" for v in warm["warmup"]["buckets"].values())
    assert warm["warmup"]["decode"] == "disk"
    lazy = [(k, tuple(s)) for k, s, src in warm["log"] if src == "compiled"]
    assert lazy == [], lazy


def test_serve_launcher_batched_warmup_failure_is_an_error(monkeypatch):
    """A batched warmup the device refuses stops the launcher with the
    reason; it never reruns per-slot and exits 0."""
    from repro.launch import serve as launcher

    monkeypatch.setattr(ServingEngine, "warmup",
                        lambda self, **kw: {"ok": False,
                                            "reason": "kernel refused"})
    with pytest.raises(RuntimeError, match="kernel refused"):
        launcher.serve(["--arch", "qwen3-0.6b", "--requests", "1",
                        "--max-new", "1", "--max-seq", "16"])
