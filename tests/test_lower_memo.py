"""The lowering memo: ``CompiledEngine._lower`` counts each phase's I/O
rates with a ``jax.eval_shape`` trace once per graph structure, and every
later invocation of that structure on the same ``CompileCache`` fills its
phase plans from the cache's memory level instead.

A hit has to give exactly what a trace gives (counts, compile key,
outputs); anything the trace depends on has to miss; a failure is never
remembered.
"""

from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

import repro  # noqa: E402
from repro import StepTask, SynthesisError, channel, mmap  # noqa: E402
from repro.apps import gemm, page_rank  # noqa: E402
from repro.core import compile_cache, synth  # noqa: E402
from repro.core.compile_cache import CompileCache  # noqa: E402
from repro.core.context import clear_context  # noqa: E402

# each app's step graph at a small size, and its output mmaps
APPS = {
    "gemm": (lambda: gemm.build_step(P=2, n=4, K=2),
             lambda args: list(args[2])),
    "page_rank": (lambda: page_rank.build_step(n_iters=3),
                  lambda args: [args[1]]),
}


def _phase_table(plan) -> list:
    """Every phase's task position, label, firings and per-firing counts,
    with channels and mmaps by name: comparable across invocations."""
    rows = []
    for ti, tp in enumerate(plan.tasks):
        for ph in tp.phases:
            rows.append((ti, ph.label, ph.count, *(
                {(plan.channels if ids == "chan_ids" else plan.mmaps)[i].name:
                 n for i, n in getattr(ph, name).items()}
                for name, ids in synth._COUNTS)))
    return rows


@pytest.fixture(scope="module", params=sorted(APPS))
def twice(request, tmp_path_factory):
    """A cold elaboration of the app's graph, then two invocations of it
    on one cache, with the plan each invocation lowered."""
    build, outputs = APPS[request.param]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_cache, "_default",
                   CompileCache(root=tmp_path_factory.mktemp("default")))
        top, args, _ = build()
        cold, graph, _ = synth.elaborate_step_graph(top, *args)
    plans = []
    real = synth._build_program
    cc = CompileCache(root=tmp_path_factory.mktemp("cc"))
    runs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(synth, "_build_program",
                   lambda plan: plans.append(plan) or real(plan))
        for _ in range(2):
            top, args, check = build()
            eng = repro.ENGINES["compiled"](cache=cc)
            rep = eng.run(top, *args)
            runs.append(SimpleNamespace(
                eng=eng, ok=rep.ok and check()[0],
                outs=[m.data.copy() for m in outputs(args)]))
    return SimpleNamespace(cold=cold, graph=graph, plans=plans, runs=runs)


def test_second_invocation_lowers_from_memory(twice):
    phases = sum(len(tp.phases) for tp in twice.cold.tasks)
    assert twice.cold.n_phase_traces == phases > 0
    assert [r.eng.lower_source for r in twice.runs] == ["traced", "memory"]
    assert [r.eng.n_phase_traces for r in twice.runs] == [phases, 0]


def test_memo_counts_equal_a_cold_elaboration(twice):
    assert _phase_table(twice.plans[1]) == _phase_table(twice.cold)
    assert _phase_table(twice.plans[0]) == _phase_table(twice.cold)


def test_memo_keeps_the_compile_key(twice):
    # hashed before counting, as the executable key was hashed after it
    assert twice.cold.structural_hash == twice.graph.structural_hash()
    first, second = twice.runs
    assert first.eng.compile_key == second.eng.compile_key
    assert second.eng.compile_source == "memory"


def test_memo_outputs_bit_identical(twice):
    first, second = twice.runs
    assert first.ok and second.ok
    for a, b in zip(first.outs, second.outs, strict=True):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# what misses: a two-task pipeline with one knob per input of the trace
# ---------------------------------------------------------------------------

def _pipe(cap=8, off=0, gain=1, buf_len=8, burst=2, n=8):
    """Src -> Sink over one channel of capacity ``cap``; Src adds the
    closure constant ``off``, Sink scales by the plain argument ``gain``
    and stores into an mmap of ``buf_len`` elements."""
    fires = n // burst

    def src(k, out):
        out.write_burst(k * burst + off + jnp.arange(burst, dtype=jnp.int32))
        return k + 1

    def snk(k, inp, res, gain):
        res.write_burst(k * burst, inp.read_burst(burst) * gain)
        return k + 1

    Src = StepTask(src, steps=fires, init=jnp.int32(0), name="Src")
    Snk = StepTask(snk, steps=fires, init=jnp.int32(0), name="Snk")

    def Top(res):
        c = channel(cap, "c", dtype=np.int32, shape=())
        repro.task().invoke(Src, c).invoke(Snk, c, res, gain)

    return Top, (mmap(np.zeros(buf_len, np.int32), "res"),)


def _lower(graph, cache, **engine_kw):
    """Elaborate and lower ``graph`` as ``run`` does, without compiling;
    return the engine and its plan."""
    top, args = graph
    eng = repro.ENGINES["compiled"](cache=cache, **engine_kw)
    try:
        plan, _, _ = eng._elaborate(top, *args)
    finally:
        clear_context()
    return eng, plan


@pytest.mark.parametrize("graph_kw,engine_kw", [
    (dict(cap=4), {}),                      # channel capacity
    (dict(off=5), {}),                      # closure constant of a body
    (dict(gain=3), {}),                     # scalar argument
    (dict(buf_len=16), {}),                 # mmap shape
    ({}, dict(ring_impl="interpret")),      # ring implementation
], ids=["capacity", "closure", "scalar", "mmap_shape", "ring_impl"])
def test_changed_input_of_the_trace_misses(tmp_path, graph_kw, engine_kw):
    cc = CompileCache(root=tmp_path)
    base = dict(ring_impl="xla")
    assert _lower(_pipe(), cc, **base)[0].lower_source == "traced"
    assert _lower(_pipe(), cc, **base)[0].lower_source == "memory"
    eng, plan = _lower(_pipe(**graph_kw), cc, **{**base, **engine_kw})
    assert eng.lower_source == "traced"
    assert eng.n_phase_traces == 2
    assert [(ph.reads, ph.writes, ph.mmap_stores) for tp in plan.tasks
            for ph in tp.phases] == [({}, {0: 2}, {}), ({0: 2}, {}, {0: 2})]
    # both structures stay remembered
    again, _ = _lower(_pipe(**graph_kw), cc, **{**base, **engine_kw})
    assert (again.lower_source, again.n_phase_traces) == ("memory", 0)
    assert _lower(_pipe(), cc, **base)[0].lower_source == "memory"


def test_failed_lowering_is_never_remembered(tmp_path):
    def grow(k, out):
        out.write(jnp.int32(1))
        return jnp.float32(k)           # int32 state becomes float32

    def snk(state, inp):
        inp.read()
        return state

    Grow = StepTask(grow, steps=2, init=jnp.int32(0), name="Grow")
    Snk = StepTask(snk, steps=2, name="Snk")

    def Top():
        c = channel(2, "c", dtype=np.int32, shape=())
        repro.task().invoke(Grow, c).invoke(Snk, c)

    cc = CompileCache(root=tmp_path)
    for _ in range(2):
        eng = repro.ENGINES["compiled"](cache=cc)
        with pytest.raises(SynthesisError, match="changed the state spec"):
            eng.run(Top)
        assert eng.lower_source == "traced"


def test_cache_off_traces_every_time():
    for _ in range(2):
        eng, _ = _lower(_pipe(), False)
        assert (eng.lower_source, eng.n_phase_traces) == ("traced", 2)


@pytest.mark.parametrize("clear", ["clear_memory", "clear"])
def test_clearing_the_cache_empties_the_memo(tmp_path, clear):
    cc = CompileCache(root=tmp_path)
    _lower(_pipe(), cc)
    assert _lower(_pipe(), cc)[0].lower_source == "memory"
    getattr(cc, clear)()
    eng, _ = _lower(_pipe(), cc)
    assert (eng.lower_source, eng.n_phase_traces) == ("traced", 2)
