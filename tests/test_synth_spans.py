"""Spans and counters that ``CompiledEngine.run`` leaves for a profile.

Each stage of a run is a ``jax.profiler.TraceAnnotation`` named
``compiled.<stage>``, nested in one ``compiled.run``; the benchmark's
per-layer metrics read them from the host plane of a trace.  These tests
trace small graphs on the CPU and read the host plane back with
``ProfileData``, as the benchmark's reduction does.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import repro  # noqa: E402
from repro.apps import gemm  # noqa: E402
from repro.core import synth  # noqa: E402
from repro.core.compile_cache import CompileCache  # noqa: E402
from repro.core.synth import elaborate_step_graph  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")
# the stages of the single-device path, in the order they run
STAGES = ("compiled.elaborate", "compiled.lower", "compiled.copy_in",
          "compiled.key", "compiled.resolve", "compiled.execute",
          "compiled.writeback")


def _traced(fn, trace_dir) -> list:
    """Run ``fn`` under a profiler trace; return its ``compiled.*`` host
    events as ``(name, start_ns, end_ns, thread)`` in start order."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        fn()
    path, = Path(trace_dir).rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns, line.name)
                             for ev in line.events
                             if ev.name.startswith("compiled."))
    return sorted(spans, key=lambda s: s[1])


def _children(spans: list) -> dict:
    """``{run span: [its child spans]}``; every child lies inside one run
    on the run's thread."""
    runs = [s for s in spans if s[0] == "compiled.run"]
    out = {r: [] for r in runs}
    for s in spans:
        if s[0] == "compiled.run":
            continue
        owner = [r for r in runs if r[3] == s[3] and r[1] <= s[1]
                 and s[2] <= r[2]]
        assert len(owner) == 1, f"{s[0]} is not nested in one run"
        out[owner[0]].append(s)
    return out


def _covered(run, kids) -> float:
    """Share of ``run`` that its children cover; they never overlap."""
    kids = sorted(kids, key=lambda s: s[1])
    for a, b in zip(kids, kids[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    return sum(e - s for _, s, e, _ in kids) / (run[2] - run[1])


def test_each_stage_once_per_run_covering_it(tmp_path):
    cc = CompileCache(root=tmp_path / "cc")
    reports = []

    def two_runs():
        for _ in range(2):      # a compile, then a hit in memory
            top, args, check = gemm.build_step(P=2, n=4, K=2)
            rep = repro.ENGINES["compiled"](cache=cc).run(top, *args)
            reports.append((rep.ok, check()[0]))

    spans = _traced(two_runs, tmp_path / "trace")
    assert reports == [(True, True)] * 2
    tree = _children(spans)
    assert len(tree) == 2
    for run, kids in tree.items():
        assert tuple(k[0] for k in kids) == STAGES
        assert _covered(run, kids) >= 0.95


def test_phase_traces_count_the_plans_phases(tmp_path):
    top, args, _ = gemm.build_step(P=2, n=4, K=2)
    plan, _, _ = elaborate_step_graph(top, *args)
    phases = sum(len(tp.phases) for tp in plan.tasks)
    assert phases > len(plan.tasks)         # PEs have a flush phase
    cc = CompileCache(root=tmp_path / "cc")
    for want in (phases, 0):        # traced, then from the lowering memo
        top, args, _ = gemm.build_step(P=2, n=4, K=2)
        eng = repro.ENGINES["compiled"](cache=cc)
        assert eng.n_phase_traces == 0
        eng.run(top, *args)
        assert eng.n_phase_traces == want


# The partitioned path needs four devices, which a CPU backend gives only
# to a process that starts with the flag: the checks run in a child.
_CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {tests!r})
    import jax
    import numpy as np
    import repro
    from repro.apps import gemm
    from repro.core import synth
    from repro.core.compile_cache import CompileCache
    from repro.core.floorplan import plan_placement
    from test_synth_spans import _traced

    top, args, _ = gemm.build_step(P=2, n=4, K=2)
    plan, graph, _ = synth.elaborate_step_graph(top, *args)
    placement = plan_placement(plan, graph, 4, cache=False,
                               cost_fn=lambda plan, tp: 1.0)
    cc = CompileCache(root={cc!r})
    eng = repro.ENGINES["compiled"](mesh=4, placement=placement, cache=cc)
    top, args, check = gemm.build_step(P=2, n=4, K=2)
    spans = _traced(lambda: eng.run(top, *args), {trace!r})
    # a second partitioned run of the same structure lowers from the memo
    again = repro.ENGINES["compiled"](mesh=4, placement=placement, cache=cc)
    top2, args2, check2 = gemm.build_step(P=2, n=4, K=2)
    again.run(top2, *args2)
    memo = {{
        "sources": [eng.lower_source, again.lower_source],
        "traces": [eng.n_phase_traces, again.n_phase_traces],
        "ok": bool(check2()[0]),
        "same": all(np.array_equal(a.data, b.data)
                    for a, b in zip(args[2], args2[2]))}}
    # the ring kernels' names and the cut exchange's collective permute,
    # which the benchmark's readers match in a chip trace
    plan.ring_impl = "interpret"
    program = synth._build_partitioned_program(
        plan, np.asarray(placement.owners, np.int32),
        eng._resolve_mesh(), "dev")
    text = jax.jit(program).lower(
        tuple(tp.state0 for tp in plan.tasks),
        tuple(np.asarray(m.data) for m in plan.mmaps)).as_text(
            debug_info=True)
    print(json.dumps({{
        "ok": bool(check()[0]), "spans": spans, "memo": memo,
        "named": [k for k in ("collective_permute", "ring_push", "ring_pop")
                  if k in text]}}))
""")


def test_partitioned_run_spans_and_named_kernels(tmp_path):
    prog = _CHILD.format(src=SRC, tests=str(Path(__file__).parent),
                         cc=str(tmp_path / "cc"),
                         trace=str(tmp_path / "trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["ok"]
    phases = got["memo"]["traces"][0]
    assert got["memo"] == {"sources": ["traced", "memory"],
                           "traces": [phases, 0], "ok": True, "same": True}
    assert phases > 0
    assert got["named"] == ["collective_permute", "ring_push", "ring_pop"]
    spans = [tuple(s) for s in got["spans"]]
    (run, kids), = _children(spans).items()
    # placement comes between lowering the plan and building the
    # partitioned program, which is lowering again
    assert [k[0] for k in kids] == [
        "compiled.elaborate", "compiled.lower", "compiled.place",
        "compiled.lower", *STAGES[2:]]
    assert _covered(run, kids) >= 0.95


def test_single_device_program_names_guard_kernel():
    """The fused guard evaluation is a Pallas call named ``eval_guards``
    (the benchmark's ``guard_kernel_ms`` matches that name in a chip
    trace); the name opens a scope over the call in the lowered program,
    as the ring kernels' names do."""
    top, args, _ = gemm.build_step(P=2, n=4, K=2)
    plan, _, _ = elaborate_step_graph(top, *args)
    plan.ring_impl = "interpret"
    text = jax.jit(synth._build_program(plan)).lower(
        tuple(tp.state0 for tp in plan.tasks),
        tuple(np.asarray(m.data) for m in plan.mmaps),
        tuple(synth._port_carry0(p) for p in plan.ports)).as_text(
            debug_info=True)
    for kernel in ("eval_guards", "ring_push", "ring_pop"):
        assert f"/{kernel}/pallas_call" in text, kernel
