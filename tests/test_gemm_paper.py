"""The PolySA systolic GEMM at the paper's array (arXiv:2009.11389,
section 4.1): 13x13 PEs, so 13 AFeeders, 13 BFeeders, 169 PEs and 13
Collectors, through ``CompiledEngine.run`` against the benchmark's
float64 reference.

The program, reference and control are the benchmark's own
(``bench/configs/gemm_systolic.py``) at ``P = 13``, with the ``gemm.paper``
cell's limits (``bench/traffic/paper.json``), at a CPU-sized block
(n = 8, K = 2).  The module compiles the 208-task program once: about
24 s of XLA:CPU compile; the whole module took 28-57 s on one CPU host,
by how busy the host was.
"""

import importlib.util
import json
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.core import compile_cache, synth  # noqa: E402
from repro.core.compile_cache import CompileCache  # noqa: E402

BENCH = Path(__file__).resolve().parents[1] / "bench"
P, SMALL = 13, {"n": 8, "K": 2}
SEED = 2**31 + 15


def _gemm_module():
    path = BENCH / "configs" / "gemm_systolic.py"
    spec = importlib.util.spec_from_file_location("bench_gemm_systolic",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def paper(tmp_path_factory):
    """The cell's graph at a small block, elaborated cold, then invoked
    twice on one compile cache, each time by a fresh engine as a host
    program's ``invoke`` makes it."""
    mod = _gemm_module()
    traffic = {**json.loads((BENCH / "traffic" / "paper.json").read_text()),
               **SMALL}
    g = mod.build({"P": P}, traffic, SEED)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_cache, "_default",
                   CompileCache(root=tmp_path_factory.mktemp("default")))
        plan, _, _ = synth.elaborate_step_graph(g.top, *g.args)
    cc = CompileCache(root=tmp_path_factory.mktemp("cc"))
    engines = []
    for _ in range(2):
        eng = synth.CompiledEngine(cache=cc)
        eng.report_ok = eng.run(g.top, *g.args).ok
        engines.append(eng)
    inputs = {"A": g.A.copy(), "B": g.B.copy(), "rounding": g.rounding}
    return dict(mod=mod, g=g, plan=plan, engines=engines, inputs=inputs,
                limits=traffic["limits"])


def test_plan_is_the_papers_array(paper):
    plan = paper["plan"]
    assert len(plan.tasks) == 2 * P + P * P + P == 208
    assert len(plan.channels) == 3 * P * P == 507
    # every PE has a flush phase besides its step phase
    assert sum(len(tp.phases) for tp in plan.tasks) == 208 + P * P == 377


def test_matches_reference_and_control_fails(paper):
    mod, limits = paper["mod"], paper["limits"]
    assert all(e.report_ok for e in paper["engines"])
    ref = mod.reference(paper["inputs"])
    got = mod.compare(paper["g"].output(), ref)
    assert all(got[k] <= lim for k, lim in limits.items()), got
    control = mod.compare(mod.control(paper["inputs"]), ref)
    assert any(control[k] > lim for k, lim in limits.items()), control


def test_second_invocation_lowers_from_memory(paper):
    first, again = paper["engines"]
    assert (first.lower_source, first.n_phase_traces) == ("traced", 377)
    assert first.compile_source == "compiled"
    assert (again.lower_source, again.n_phase_traces) == ("memory", 0)
    assert again.compile_source == "memory"
    assert again.compile_key == first.compile_key
    assert first.n_sweeps == again.n_sweeps == SMALL["K"] + 27
