"""Serving driver: continuous batching over TAPA channels + jit'd decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 12

The request stream, the admission scheduler (peek) and the per-request
transactions (EoT) run as a task graph under the coroutine engine; the
compute inside is the batched packed-slot decode of the selected model:
one jitted step per iteration for every slot, on-device sampling, and
length-bucketed prefill AOT-resolved through the persistent compile cache
(``--per-slot`` selects the seed per-slot path instead; recurrent
families, which the batched adapter refuses, use it automatically).
A batched warmup that fails is an error, never a silent per-slot run.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any

import jax
import numpy as np

from ..configs import get_config
from ..core.compile_cache import enable_persistent_cache
from ..ft import PreemptionGuard
from ..models import lm
from ..serve import (AdmissionConfig, AdmissionController, Request,
                     RequestError, ServeConfig, ServeMetrics, ServingEngine,
                     TenantSpec, make_trace, serve_requests)


def _build_engine(cfg, params, scfg: ServeConfig, args) -> ServingEngine:
    if not args.per_slot:
        try:
            adapter = lm.serving_adapter(
                params, cfg, max_seq=scfg.max_seq,
                temperature=args.temperature, top_k=args.top_k,
                seed=args.seed)
            return ServingEngine(scfg, batched=adapter)
        except ValueError as e:       # recurrent family etc.
            print(f"[serve] batched path unavailable ({e}); "
                  f"falling back to per-slot")

    if args.temperature > 0 or args.top_k:
        print("[serve] WARNING: the per-slot path is greedy-only; "
              "--temperature/--top-k are ignored")
    max_seq = scfg.max_seq

    @jax.jit
    def prefill_fn(tokens):
        return lm.prefill(params, cfg, tokens, max_seq=max_seq)

    @jax.jit
    def decode_fn(token, cache):
        return lm.decode_step(params, cfg, token, cache)

    return ServingEngine(scfg, prefill_fn, decode_fn)


def _print_warmup(engine: ServingEngine, info: dict) -> None:
    if not info.get("ok"):
        print(f"[serve] warmup: eager fallback ({info.get('reason')})")
        return
    if "buckets" in info:
        hits = [k for k, v in info["buckets"].items() if v != "compiled"]
        fresh = [k for k, v in info["buckets"].items() if v == "compiled"]
        print(f"[serve] warmup: prefill buckets cached={hits or '-'} "
              f"fresh-compile={fresh or '-'}; "
              f"decode step: {info['decode']}")
    else:
        print(f"[serve] warmup: prefill={info['prefill']} "
              f"decode={info['decode']}")


@dataclasses.dataclass
class ServeRun:
    """What one serving invocation did: its exit code and what a caller
    checks (``chip_smoke.py`` compares the tokens against a direct
    decode loop with the same ``params``)."""
    rc: int
    cfg: Any
    params: Any
    engine: ServingEngine
    requests: list
    results: dict
    lazy: list          # (kind, shape) compiled after warmup
    wall_s: float
    n_tokens: int


def serve(argv=None) -> int:
    return run_serve(argv).rc


def run_serve(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-buckets", default="",
                    help="comma-separated prompt buckets to warm and pad "
                         "to (default: powers of two from 8 to --max-seq)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--per-slot", action="store_true",
                    help="seed path: one decode call per slot per token")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples on device")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget from admission")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal (JSONL).  A restarted "
                         "process given the same flags replays it: retired "
                         "requests answer from the journal, in-flight ones "
                         "resume at their last journaled token — "
                         "exactly-once results across SIGKILL")
    ap.add_argument("--traffic", choices=("poisson", "burst"), default=None,
                    help="open-loop traffic mode: seeded Poisson or bursty "
                         "on/off (MMPP) arrivals paced in wall time under "
                         "the thread engine, instead of a back-to-back "
                         "request list")
    ap.add_argument("--tenants", type=int, default=2,
                    help="number of traffic tenants (fair-queued)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate per tenant (requests/s)")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="traffic trace duration (seconds)")
    ap.add_argument("--shed-policy",
                    choices=("none", "reject-new", "drop-oldest"),
                    default="reject-new",
                    help="admission-control shed policy under --traffic; "
                         "'none' disables the admission controller (the "
                         "frontend blocks on a full queue)")
    ap.add_argument("--queue-limit", type=int, default=32,
                    help="admission-controller backlog bound (requests)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.with_reduced()
    print(f"[serve] arch={cfg.name} family={cfg.family} "
          f"params={cfg.param_count()/1e6:.1f}M slots={args.slots}")

    params = lm.init_params(cfg, jax.random.key(args.seed))
    buckets = tuple(int(b) for b in args.prefill_buckets.split(",") if b)
    scfg = ServeConfig(batch_slots=args.slots, max_seq=args.max_seq,
                       prefill_buckets=buckets)
    engine = _build_engine(cfg, params, scfg, args)

    t0 = time.perf_counter()
    if engine.batched is not None:
        # warm every admission shape a serving process can meet: all
        # power-of-two prefill batch dims up to the slot count, plus the
        # slot count itself (a full wave pads to it when it is not pow2)
        sizes = tuple(sorted({min(2 ** k, args.slots)
                              for k in range(args.slots.bit_length())}
                             | {args.slots}))
        info = engine.warmup(batch_sizes=sizes)
        if not info.get("ok"):
            # a batched adapter has no eager path, and a per-slot rerun
            # would hide why the device refused the batched program
            raise RuntimeError(
                f"batched warmup failed: {info.get('reason')}")
    else:
        info = engine.warmup()
    warm = time.perf_counter() - t0
    mode = "batched" if engine.batched is not None else "per-slot"
    _print_warmup(engine, info)
    print(f"[serve] warmup took {warm:.2f}s mode={mode}")
    n_warm_log = len(engine.compile_log)

    sim_engine = "coroutine"
    metrics = None
    if args.traffic:
        # seeded open-loop traffic: the trace is a pure function of
        # (--seed, tenant mix, duration) — see repro/serve/traffic.py
        phases = {"on_s": 0.4, "off_s": 0.4, "on_scale": 3.0} \
            if args.traffic == "burst" else None
        tenants = [TenantSpec(name=f"t{i}", rate=args.rate,
                              max_new=(args.max_new, args.max_new),
                              deadline_s=args.deadline_s, phases=phases)
                   for i in range(args.tenants)]
        reqs = make_trace(tenants, args.duration, seed=args.seed,
                          vocab=cfg.vocab)
        metrics = engine.metrics = ServeMetrics()
        if args.shed_policy != "none":
            ctrl = AdmissionController(
                AdmissionConfig(shed_policy=args.shed_policy,
                                queue_limit=args.queue_limit),
                metrics=metrics)
            ctrl.register_tenants(tenants)
            engine.admission = ctrl
            ctrl.journal = engine.journal
        engine.pace = "wall"
        sim_engine = "thread"     # wall pacing needs preemptive tasks
        print(f"[serve] traffic={args.traffic} tenants={args.tenants} "
              f"rate={args.rate}/s x {args.duration}s -> "
              f"{len(reqs)} requests, shed-policy={args.shed_policy}")
    else:
        rng = np.random.default_rng(args.seed)
        reqs = [Request(rid=i,
                        prompt=rng.integers(
                            0, cfg.vocab, rng.integers(4, 17)).tolist(),
                        max_new=args.max_new,
                        deadline_s=args.deadline_s)
                for i in range(args.requests)]

    # preemption-safe serving: SIGTERM/SIGINT flips the guard; the
    # scheduler then rejects queued admissions with "preempted" errors,
    # finishes the in-flight slots, flushes results and exits clean
    guard = PreemptionGuard()
    engine.stop_flag = lambda: guard.requested
    if args.journal:
        from ..serve import ServeJournal
        engine.journal = ServeJournal(args.journal)
        if engine.journal.completed or engine.journal.inflight:
            print(f"[serve] journal replay: "
                  f"{len(engine.journal.completed)} retired, "
                  f"{len(engine.journal.inflight)} in-flight")
    try:
        t0 = time.perf_counter()
        results = serve_requests(engine, reqs, sim_engine=sim_engine)
        wall = time.perf_counter() - t0
    finally:
        guard.uninstall()
    ok = {r: v for r, v in results.items() if not isinstance(v, RequestError)}
    failed = {r: v for r, v in results.items() if isinstance(v, RequestError)}
    n_new = sum(len(v) for v in ok.values())
    if not args.traffic:               # traffic mode prints a summary instead
        for rid in sorted(results):
            v = results[rid]
            if isinstance(v, RequestError):
                print(f"[serve] req {rid}: {v.status} ({v.detail})")
            else:
                print(f"[serve] req {rid}: prompt "
                      f"{len(reqs[rid].prompt):2d} tok -> {v}")
    lazy = [(k, s, src) for k, s, src in engine.compile_log[n_warm_log:]
            if src == "compiled"]
    if lazy:
        print(f"[serve] lazy compiles during serving: "
              f"{[(k, s) for k, s, _ in lazy]}")
    if engine.degraded is not None:
        print(f"[serve] degraded to {engine.degraded[0]}: "
              f"{engine.degraded[1]}")
    if guard.requested:
        print(f"[serve] preempted: {len(ok)} completed, "
              f"{len(failed)} rejected")
    print(f"[serve] {len(ok)} requests, {n_new} tokens in {wall:.2f}s "
          f"({n_new/max(wall,1e-9):.1f} tok/s, {mode} decode)")
    if metrics is not None:
        metrics.check_accounting()
        summ = metrics.summary(wall_s=wall)

        def _ms(v):
            return "-" if v is None else f"{v * 1e3:.0f}ms"

        print(f"[serve] overload: offered={summ['offered']} "
              f"admitted={summ['admitted']} shed={summ['shed']} "
              f"completed={summ['completed']} "
              f"goodput={summ['goodput_tok_s'] or 0:.1f} tok/s "
              f"ttft p50={_ms(summ['ttft_p50_s'])} "
              f"p99={_ms(summ['ttft_p99_s'])}")
        for name, row in summ["tenants"].items():
            print(f"[serve]   tenant {name}: offered={row['offered']} "
                  f"admitted={row['admitted']} shed={row['shed']} "
                  f"ttft p50={_ms(row['ttft_p50_s'])} "
                  f"p99={_ms(row['ttft_p99_s'])}")
        # open-loop contract: every offered request gets an answer —
        # tokens or a structured error — never a silent absence
        ok_run = len(results) == len(reqs)
    elif guard.requested:
        # a preempted run that answered every request (some with
        # structured rejections) still exits clean — that is the
        # graceful-drain contract
        ok_run = len(results) == args.requests
    else:
        ok_run = len(ok) == args.requests
    return ServeRun(rc=0 if ok_run else 1, cfg=cfg, params=params,
                    engine=engine, requests=reqs, results=results,
                    lazy=[(k, sh) for k, sh, _ in lazy], wall_s=wall,
                    n_tokens=n_new)


if __name__ == "__main__":
    enable_persistent_cache()
    sys.exit(serve())
