"""Chip benchmark of the compiled task graph: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``, whose ``program`` names
the module ``bench/configs/<program>.py``, by default the configuration's
own name) under a traffic file (``bench/traffic/<traffic>.json``).  One
run, in one process that holds the cell's chips:

1. set-up: build the graph and its inputs from the seed, load the
   executable through the compile caches and invoke the graph twice;
2. the window: ``CompiledEngine.run(top, *mmaps)`` back to back for
   ``--seconds``, each invocation after a seeded in-place refresh of a
   small part of its inputs; a compilation inside the window fails the
   run;
3. where the run reports ``compile_s`` or ``xla_compile_s``:
   invocations of a freshly built graph with both compile caches off,
   of which the last is read;
4. the check: sampled invocations' outputs against the configuration's
   float64 reference of their own inputs, each number beside its limit
   from the traffic file.

The last line of standard output is one JSON object: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
which the readers in ``bench/metrics/<metric>.py`` take from a profiler
trace of the window and from the engine's counters.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".cache" / "jax"
TRACE_DIR = ROOT / ".cache" / "bench_trace"
WARMUP = 2              # invocations in set-up: load, then steady state
TRACE_SECONDS = 8.0     # a traced run traces the window's first seconds
COLD_TIMES = 2          # cold invocations per compile reading; the last counts
CHECK_DRAWS = 2         # seeded window invocations checked besides the
                        # first and the last


class BenchError(Exception):
    """A run that cannot give a result."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def cell_spec(bench: dict, name: str) -> SimpleNamespace:
    """Everything one cell's run needs, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = read_json(ROOT / conf["file"])
    # a configuration may share another's module (the four-chip gemm)
    program = cfg.get("program", cfg["name"])

    def mine(metrics: list) -> list:
        return [m for m in metrics if name in m.get("workloads", [name])]

    e2e = mine(bench["end_to_end"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in mine(bench["per_layer"])
                 if m["moves"] in e2e_names]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), cfg=cfg,
        traffic=read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        module=BENCH / "configs" / f"{program}.py",
        e2e=e2e, per_layer=per_layer)


class CompileWatch:
    """Counts XLA backend compilations while armed."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if self.armed and event == self.event:
            self.count += 1


def use_checkout() -> None:
    """Import the program from this checkout and keep both compile caches
    (JAX's and the program's store, which lives in its ``repro/``
    subdirectory) at a fixed path inside it, whatever the environment
    says, so that two checkouts share nothing."""
    if not (SRC / "repro").is_dir():
        raise BenchError(f"no program under {SRC}: run from a checkout")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def device_info(chips: int, require_tpu: bool) -> tuple:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    return info, devs[:chips]


def peak_table(kind: str, require_tpu: bool) -> dict:
    kinds = read_json(BENCH / "peaks.json")["kinds"]
    if kind not in kinds:
        if require_tpu:
            raise BenchError(f"no peaks for device kind {kind!r} in "
                             f"bench/peaks.json")
        return {}
    return kinds[kind]


def invoke(g, top=None, **engine_kw):
    """One invocation as a user makes it; returns the engine."""
    from repro.core.synth import CompiledEngine
    eng = CompiledEngine(**{**g.engine_kw, **engine_kw})
    rep = eng.run(top or g.top, *g.args)
    eng.report_ok = rep.ok
    return eng


def window(g, seconds: float, seed: int, first_s: float, watch,
           annotate, stop_trace=None) -> SimpleNamespace:
    """Invoke back to back until ``seconds`` of refresh + invocation have
    passed; keep copies of the sampled invocations' outputs.
    ``stop_trace`` is called once the first ``TRACE_SECONDS`` have."""
    import numpy as np
    n_est = max(2, int(seconds / max(first_s, 1e-3)))
    draws = np.random.default_rng([seed, 1]).integers(1, n_est, CHECK_DRAWS)
    sample = {0, *map(int, draws)}
    outs, failed, spent, i = {}, 0, 0.0, 0
    watch.armed = True
    while spent < seconds:
        t0 = time.perf_counter()
        with annotate("refresh"):
            g.refresh(i)
        with annotate("invoke"):
            eng = invoke(g)
        spent += time.perf_counter() - t0
        if eng.compile_source != "memory":
            raise BenchError(f"invocation {i} resolved its executable from "
                             f"{eng.compile_source!r} inside the window")
        failed += not eng.report_ok
        if i in sample:
            outs[i] = g.output()
        i += 1
        if stop_trace is not None and spent >= TRACE_SECONDS:
            stop_trace()
            stop_trace = None
    watch.armed = False
    if watch.count:
        raise BenchError(f"{watch.count} compilation(s) inside the window")
    outs[i - 1] = g.output()
    return SimpleNamespace(n=i, seconds=spent, failed=failed, outs=outs,
                           engine=eng)


def check(mod, g, outs: dict, limits: dict) -> tuple:
    """Worst reading of each compared number over the sampled
    invocations, against the float64 reference of their own inputs."""
    worst: dict = {}
    for i in sorted(outs):
        got = mod.compare(outs[i], mod.reference(g.inputs_at(i)))
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    missing = set(limits) - set(worst)
    if missing:
        raise BenchError(f"limits for numbers the comparison does not "
                         f"give: {sorted(missing)}")
    rows = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok, rows


def cold_compile(g, run_ms: float, watch) -> tuple:
    """``compile_s``: one invocation of a freshly built graph with the
    repo store and JAX's persistent cache off, less ``run_ms``; and the
    engine's own resolve seconds of it.  Of ``COLD_TIMES`` such
    invocations the last is read: a first cold compile has read 2-3x the
    next by what ran on the machine before it, not by the graph."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # JAX keeps its first decision
    for k in range(COLD_TIMES):
        watch.count, watch.armed = 0, True
        t0 = time.perf_counter()
        eng = invoke(g, top=g.make_top(), cache=False)
        wall = time.perf_counter() - t0
        watch.armed = False
        if eng.compile_source != "compiled" or not watch.count or \
                not eng.report_ok:
            raise BenchError(f"cold invocation {k} came from "
                             f"{eng.compile_source!r} with {watch.count} "
                             f"compilation(s) (ok={eng.report_ok})")
        print(f"cold invocation {k}: {wall:.2f} s, resolve "
              f"{eng.compile_s:.2f} s", file=sys.stderr, flush=True)
    return wall - run_ms / 1e3, eng.compile_s


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             sizes: dict = None) -> dict:
    """One run of cell ``name``.  ``require_tpu=False`` and ``sizes``
    (``{"cfg": {...}, "traffic": {...}}`` merged over the files) are for
    the tests, which drive a run on the CPU at a small size."""
    cs = cell_spec(bench, name)
    for part, over in (sizes or {}).items():
        getattr(cs, part).update(over)
    seed %= 1 << 64
    use_checkout()
    import jax
    from repro.core.compile_cache import enable_persistent_cache
    device, devs = device_info(cs.chips, require_tpu)
    peaks = peak_table(device["kind"], require_tpu)
    enable_persistent_cache()
    watch = CompileWatch()
    mod = load_module(cs.module)

    t_jax = time.perf_counter() - T_START
    g = mod.build(cs.cfg, cs.traffic, seed)
    t_build = time.perf_counter() - T_START - t_jax
    warm = []
    for _ in range(WARMUP):
        t0 = time.perf_counter()
        eng = invoke(g)
        first_s = time.perf_counter() - t0
        warm.append(f"{first_s:.2f} s ({eng.compile_source}, resolve "
                    f"{eng.compile_s:.2f} s)")
        if not eng.report_ok:
            raise BenchError("a set-up invocation failed")
    setup_s = time.perf_counter() - T_START
    print(f"setup {setup_s:.2f} s: to devices and module {t_jax:.2f} s, "
          f"build {t_build:.2f} s, invocations {'; '.join(warm)}",
          file=sys.stderr, flush=True)

    annotate = jax.profiler.TraceAnnotation
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # annotations, not every call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    stopped = []

    def stop_trace():
        jax.profiler.stop_trace()
        stopped.append(True)

    try:
        w = window(g, seconds, seed, first_s, watch, annotate,
                   stop_trace if trace else None)
    finally:
        if trace and not stopped:
            stop_trace()
    run_ms = w.seconds / w.n * 1e3
    stats = [d.memory_stats() or {} for d in devs]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)
    counters = SimpleNamespace(     # placement: for a cut_bytes reader
        sweeps=w.engine.n_sweeps,
        placement=getattr(w.engine.placement_used, "objective", None))
    del w.engine
    names = {m["name"] for m in (cs.per_layer if trace else cs.e2e)}
    compile_s = xla_compile_s = None
    if names & {"compile_s", "xla_compile_s"}:
        compile_s, xla_compile_s = cold_compile(g, run_ms, watch)
    gc.collect()
    correct, rows = check(mod, g, w.outs, cs.traffic["limits"])

    metrics = {}
    result = {"correct": correct and w.failed == 0, "attempted": w.n,
              "failed": w.failed, "metrics": metrics, "device": device}
    if trace:
        tr = load_module(BENCH / "trace_reduce.py")
        red = tr.reduce(tr.events(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = SimpleNamespace(
            red=red, op_seconds=lambda p: tr.op_seconds(red, p),
            run_ms=red["window_s"] / red["n_invokes"] * 1e3,
            device_ms=red["busy_s"] / red["n_invokes"] * 1e3,
            n=red["n_invokes"], sweeps=counters.sweeps,
            placement=counters.placement, xla_compile_s=xla_compile_s,
            work=g.work(), peaks=peaks, chips=cs.chips)
        for m in cs.per_layer:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(red)
    else:
        got = {"run_ms": run_ms, "setup_s": setup_s, "compile_s": compile_s}
        for m in cs.e2e:
            metrics[m["name"]] = {"value": got[m["name"]], "unit": m["unit"]}
    result["check"] = rows
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = read_json(ROOT / "BENCHMARK.json")
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for k, row in result["check"].items():
        print(f"check {k} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
