"""``correct`` separates the program from its control and from broken
programs: at a small size on the CPU, a sound run is correct, the control
fails a limit, and each fault planted under the timed path makes a run
come out not correct."""

import numpy as np
import pytest

import control
import run
from conftest import SMALL

CELLS = ["gemm.wide", "pagerank.uniform"]
BENCH = run.read_json(run.ROOT / "BENCHMARK.json")


def _run(cell: str, seconds: float = 0.5) -> dict:
    return run.run_cell(BENCH, cell, 2**31 + 11, seconds, False,
                        require_tpu=False, sizes=SMALL[cell])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, fresh_caches):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell, fresh_caches):
    limits = run.cell_spec(BENCH, cell).traffic["limits"]
    got = {}
    for r in control.readings(cell, [5], [5, 6, 7], 0.3,
                              require_tpu=False, sizes=SMALL[cell]):
        got.setdefault(r["kind"], []).append(r)
    assert all(r["correct"] for r in got["program"])
    for r in got["control"]:
        assert any(r[k] > lim for k, lim in limits.items()), r


def _broken_writeback(monkeypatch, fault: str):
    from repro.core.synth import CompiledEngine
    orig = CompiledEngine._writeback
    calls = {"n": 0}

    def writeback(self, plan, mm_final):
        calls["n"] += 1
        written = [plan.mmaps[mi] for tp in plan.tasks for ph in tp.phases
                   for mi in ph.mmap_stores]
        before = [np.array(m.data, copy=True) for m in written]
        if fault == "state_unchanged" and calls["n"] > run.WARMUP:
            return                      # the invocation changes nothing
        orig(self, plan, mm_final)
        if fault == "answer_altered":
            flat = written[0].data.reshape(-1)
            flat[0] = 2 * flat[0] + 1
        elif fault == "half_left_out":
            for m, old in zip(written, before):
                flat, prev = m.data.reshape(-1), old.reshape(-1)
                flat[len(flat) // 2:] = prev[len(prev) // 2:]

    monkeypatch.setattr(CompiledEngine, "_writeback", writeback)


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_run_incorrect(cell, fault, fresh_caches, monkeypatch):
    _broken_writeback(monkeypatch, fault)
    assert not _run(cell)["correct"]



@pytest.mark.parametrize("cell", CELLS)
def test_cold_compile_compiles_each_time(cell, fresh_caches):
    """``xla_compile_s`` reads a compilation, never a cache hit."""
    cs = run.cell_spec(BENCH, cell)
    for part, over in SMALL[cell].items():
        getattr(cs, part).update(over)
    run.use_checkout()
    mod = run.load_module(cs.module)
    g = mod.build(cs.cfg, cs.traffic, 3)
    run.invoke(g)
    wall_s, resolve_s = run.cold_compile(g, 0.0, run.CompileWatch())
    assert wall_s >= resolve_s > 0
