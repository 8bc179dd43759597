"""Smoke run of the repo's main paths on one TPU chip.

    python chip_smoke.py               # one chip: graphs, serving, training
    python chip_smoke.py --four-chips  # partitioned gemm on four chips only

Everything runs in this one process, through the entry points a user
calls: ``CompiledEngine.run`` on the gemm and page_rank step graphs,
``repro.launch.serve`` on qwen3-0.6b at its published widths, and
``repro.launch.train`` for three full-width steps.  Each phase checks its
result against a reference computed here (numpy, the XLA ring ops, a
direct prefill + decode loop teacher-forced on the served tokens).  Any failed check, or a platform other
than a TPU, exits non-zero before the last line, which is the JSON
object ``{"ok": true, "device": {...}}``.

The compile caches live under ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, else under ``.cache/jax`` in this checkout; a second run with the
same directory loads the executables from disk.  Numbers printed here
are smoke figures, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
SMOKE_DIR = ROOT / ".cache" / "chip_smoke"

# gemm: 16 PEs on (256, 256) f32 blocks; A and B are 16.8 MB each
GEMM = dict(P=4, n=256, K=16, seed=0)
# page_rank: 4096 vertices, 65536 edges over 4 scatter PEs, 10 iterations
PAGE_RANK = dict(n_vertices=4096, n_edges=65536, n_pe=4, n_iters=10, seed=0)
SERVE_ARGV = ["--arch", "qwen3-0.6b", "--full", "--slots", "8",
              "--max-seq", "2048", "--requests", "16", "--max-new", "32",
              "--prefill-buckets", "16", "--seed", "0"]
TRAIN_STEPS = 3
TRAIN_ARGV = ["--arch", "qwen3-0.6b", "--steps", str(TRAIN_STEPS),
              "--batch", "4", "--seq", "1024", "--log-every", "1",
              "--seed", "0"]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# compiled task graphs
# ---------------------------------------------------------------------------

def run_graph(build, kw: dict, **engine_kw):
    """Build a fresh step graph, run it under ``CompiledEngine`` and
    return ``(engine, report, check, args)``."""
    from repro.core.synth import CompiledEngine
    top, args, chk = build(**kw)
    eng = CompiledEngine(**engine_kw)
    rep = eng.run(top, *args)
    check(rep.ok, f"{build.__module__} compiled run failed: {rep.error}")
    return eng, rep, chk, args


def _gemm_out(args) -> "np.ndarray":
    import numpy as np
    return np.concatenate([np.asarray(m.data) for m in args[2]], axis=0)


def _rank_out(args) -> "np.ndarray":
    import numpy as np
    return np.asarray(args[1].data).copy()


def phase_graphs(gemm_kw: dict = GEMM, rank_kw: dict = PAGE_RANK) -> None:
    """gemm and page_rank through ``CompiledEngine`` with the ring impl
    left to the dispatcher (Pallas on a TPU), each checked against numpy
    by the app's own tolerance and bit for bit against the same graph
    with the XLA ring ops."""
    import numpy as np
    from repro.apps import gemm, page_rank
    from repro.kernels.dispatch import is_tpu

    for name, build, kw, out in (
            ("gemm", gemm.build_step, gemm_kw, _gemm_out),
            ("page_rank", page_rank.build_step, rank_kw, _rank_out)):
        eng, rep, chk, args = run_graph(build, kw)
        if is_tpu():
            check(eng.ring_impl_used == "pallas",
                  f"{name}: ring impl resolved to {eng.ring_impl_used!r}")
        good, err = chk()
        check(good, f"{name}: max |err| vs numpy {err} over tolerance")
        got = out(args)
        ref_eng, _, _, ref_args = run_graph(build, kw, ring_impl="xla")
        same = np.array_equal(got, out(ref_args))
        check(same, f"{name}: pallas-ring output differs from xla-ring")
        log(f"{name} {kw}: ring={eng.ring_impl_used} sweeps={eng.n_sweeps} "
            f"tasks={len(rep.instances)} max_err={err:.3e} "
            f"bit_identical_to_xla_ring={same} "
            f"compile_source={eng.compile_source} "
            f"compile_s={eng.compile_s:.2f} "
            f"(xla-ring variant {ref_eng.compile_source} "
            f"{ref_eng.compile_s:.2f}s) wall_s={rep.wall_s:.2f}")


def phase_four_chips(gemm_kw: dict = GEMM, n_dev: int = 4) -> None:
    """The partitioned gemm on an ``n_dev``-chip mesh, bit for bit
    against the single-chip program."""
    import jax
    import numpy as np
    from repro.apps import gemm
    from repro.distributed.sharding import device_mesh

    check(len(jax.devices()) >= n_dev,
          f"--four-chips needs {n_dev} devices, found {len(jax.devices())}")
    one, rep1, chk1, args1 = run_graph(gemm.build_step, gemm_kw)
    good, err = chk1()
    check(good, f"gemm single-chip: max |err| {err} over tolerance")
    part, rep, chk, args = run_graph(gemm.build_step, gemm_kw, mesh=n_dev)
    good, err = chk()
    check(good, f"gemm mesh={n_dev}: max |err| {err} over tolerance")
    same = np.array_equal(_gemm_out(args), _gemm_out(args1))
    check(same, f"gemm mesh={n_dev} output differs from the single chip")
    mesh_ids = sorted(d.id for d in device_mesh(n_dev).devices.flat)
    check(len(set(mesh_ids)) == n_dev,
          f"the {n_dev}-device mesh holds devices {mesh_ids}")
    pl = part.placement_used
    log(f"gemm {gemm_kw} mesh={n_dev} over devices {mesh_ids}: "
        f"owners={list(pl.owners)} "
        f"cut_channels={len(pl.objective['cut_channels'])} "
        f"cut_bytes={pl.objective['cut_bytes']} "
        f"sweeps={part.n_sweeps} (single chip {one.n_sweeps}) "
        f"bit_identical={same} max_err={err:.3e} "
        f"compile_s={part.compile_s:.2f} ({part.compile_source}) "
        f"wall_s={rep.wall_s:.2f} (single chip {rep1.wall_s:.2f})")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _reference_logits(cfg, params, prompt: list, tokens: list,
                      max_seq: int) -> "np.ndarray":
    """Logits of a plain ``lm.prefill`` + ``lm.decode_step`` loop for one
    request (no padding, no slots), teacher-forced on ``tokens``: row
    ``i`` scores the choice of ``tokens[i]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    prefill = jax.jit(lambda p, t: lm.prefill(p, cfg, t, max_seq=max_seq))
    decode = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))
    logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32))
    rows = [np.asarray(logits[0], np.float32)]
    for t in tokens[:-1]:
        logits, cache = decode(params, jnp.asarray([t], jnp.int32), cache)
        rows.append(np.asarray(logits[0], np.float32))
    return np.stack(rows)


# A greedy token may differ from the reference's argmax only where the
# two are a near-tie: bf16 programs of different shapes (bucket-padded
# prefill, 8-slot decode vs one unpadded request) round differently.
NEAR_TIE = 0.02       # in units of the reference logits' std at that step


def _check_greedy(run, req) -> tuple:
    """Every token the engine chose for ``req`` is the reference's argmax
    or within ``NEAR_TIE`` of it; returns (exact matches, worst gap)."""
    import numpy as np
    toks = run.results[req.rid]
    lg = _reference_logits(run.cfg, run.params, req.prompt, toks,
                           run.engine.scfg.max_seq)
    gaps = (lg.max(axis=1) - lg[np.arange(len(toks)), toks]) \
        / lg.std(axis=1)
    exact = int(np.sum(lg.argmax(axis=1) == np.asarray(toks)))
    check(bool(np.all(gaps <= NEAR_TIE)),
          f"request {req.rid}: batched tokens {toks} leave the reference "
          f"argmax by {np.round(gaps, 4).tolist()} logit stds")
    return exact, float(gaps.max())


def phase_serving(argv: list = SERVE_ARGV) -> None:
    from repro.core.compile_cache import default_cache
    from repro.launch.serve import run_serve
    from repro.serve import RequestError

    stats = default_cache().stats
    fails0, writes0 = stats.serialize_failures, stats.disk_writes
    t0 = time.perf_counter()
    run = run_serve(argv)
    total = time.perf_counter() - t0
    eng = run.engine
    check(run.rc == 0, f"serve exited {run.rc}")
    check(eng.batched is not None, "serving did not use the batched path")
    check(eng.degraded is None, f"serving degraded: {eng.degraded}")
    check(run.lazy == [], f"lazy compiles while serving: {run.lazy}")
    errs = {r: v for r, v in run.results.items()
            if isinstance(v, RequestError)}
    check(not errs and len(run.results) == len(run.requests),
          f"unanswered or failed requests: {errs}")
    check(all(len(run.results[r.rid]) == r.max_new for r in run.requests),
          "a request stopped short of max_new")
    compiled = [(k, s) for k, s, src in eng.compile_log if src == "compiled"]
    fails = stats.serialize_failures - fails0
    writes = stats.disk_writes - writes0
    check(fails == 0 and writes >= len(compiled),
          f"serving executables did not reach disk: {len(compiled)} "
          f"compiled, {writes} written, {fails} serialize failures")
    parity = [_check_greedy(run, req) for req in run.requests[:2]]
    sources = sorted({src for _, _, src in eng.compile_log})
    log(f"serving {run.cfg.name}: {len(run.requests)} requests x "
        f"{run.requests[0].max_new} tokens, slots={eng.scfg.batch_slots} "
        f"max_seq={eng.scfg.max_seq}; executables {len(eng.compile_log)} "
        f"from {sources}, serialize_failures={stats.serialize_failures}; "
        f"vs a direct decode loop, (argmax matches, worst gap in logit "
        f"stds) per request: {parity}; smoke figure (not a "
        f"benchmark result): {run.n_tokens / run.wall_s:.1f} tok/s, "
        f"serve+warmup {total:.1f}s")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def phase_training(argv: list = TRAIN_ARGV, steps: int = TRAIN_STEPS) -> None:
    from repro.launch.train import train

    work = SMOKE_DIR / "train"
    shutil.rmtree(work, ignore_errors=True)   # a fresh run, never a resume
    work.mkdir(parents=True)
    metrics = work / "metrics.jsonl"
    t0 = time.perf_counter()
    rc = train(argv + ["--ckpt-dir", str(work / "ckpt"),
                       "--metrics", str(metrics)])
    total = time.perf_counter() - t0
    check(rc == 0, f"train exited {rc}")
    rows = [json.loads(ln) for ln in metrics.read_text().splitlines()]
    check(len(rows) == steps, f"{len(rows)} training steps logged")
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    log(f"training: {steps} steps, losses {losses}, first step "
        f"(with compile) {rows[0]['dt']:.2f}s, later steps "
        f"{[round(r['dt'], 3) for r in rows[1:]]}s, total {total:.1f}s")
    shutil.rmtree(work, ignore_errors=True)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned gemm on four chips "
                         "against the single-chip program")
    args = ap.parse_args(argv)
    try:
        check((SRC / "repro").is_dir(),
              f"no repro sources under {SRC}: run from a checkout")
        for var in ("REPRO_RING_IMPL", "REPRO_DECODE_ATTN"):
            check(var not in os.environ,
                  f"${var} is set: the smoke checks the default dispatch")
        sys.path.insert(0, str(SRC))
        import jax
        from repro.core.compile_cache import enable_persistent_cache

        dev = jax.devices()[0]
        check(dev.platform == "tpu",
              f"JAX found no TPU (platform {dev.platform!r})")
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        log(f"device: {device}; compile cache: {enable_persistent_cache()}")
        phases = ([("four_chips", phase_four_chips)] if args.four_chips
                  else [("graphs", phase_graphs), ("serving", phase_serving),
                        ("training", phase_training)])
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            log(f"phase {name} passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
