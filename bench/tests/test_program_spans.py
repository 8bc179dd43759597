"""What this benchmark reads of the program's own names and spans, and the
``gemm.tiles`` cell at a small size on the CPU.

The ring kernels are found by their Pallas names (``ring_kernel_ms``)
and the cut-channel exchange by its opcode (``collective_ms``).  The
``compiled.*`` spans of ``CompiledEngine.run`` are checked on a chip
trace of one ``gemm.wide`` invocation recorded with them."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import control
import run
from test_correctness import _broken_writeback

tr = run.load_module(run.BENCH / "trace_reduce.py")
RING_MS = run.load_module(run.BENCH / "metrics" / "ring_ms.py")
RING_KERNEL_MS = run.load_module(run.BENCH / "metrics" / "ring_kernel_ms.py")
COLLECTIVE_MS = run.load_module(run.BENCH / "metrics" / "collective_ms.py")
DATA = Path(__file__).resolve().parent / "data"
BENCH = run.read_json(run.ROOT / "BENCHMARK.json")
STAGES = {"compiled.elaborate", "compiled.lower", "compiled.copy_in",
          "compiled.key", "compiled.resolve", "compiled.execute",
          "compiled.writeback"}
TILES = {"traffic": {"n": 8, "K": 16}}   # gemm.tiles' shape, CPU-sized


def _ctx(red: dict) -> SimpleNamespace:
    """The part of a traced run's reader context these readers use."""
    return SimpleNamespace(red=red, n=red["n_invokes"],
                           op_seconds=lambda p: tr.op_seconds(red, p))


def test_named_kernels_and_exchange_by_hand():
    ring = ("%ring_push.3 = f32[1,8,128]{2,1,0} custom-call(s32[1]{0} %p), "
            'custom_call_target="tpu_custom_call"')
    guard = ("%ring_guards = (s32[4]{0}) custom-call(s32[4]{0} %g), "
             'custom_call_target="tpu_custom_call"')
    perm = ("%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) "
            "collective-permute-start(f32[8]{0} %x), "
            "source_target_pairs={{0,1},{1,0}}")
    done = ("%collective-permute-done.1 = f32[8]{0} "
            "collective-permute-done((f32[8]{0}, f32[8]{0}) %s)")
    ev = {"devices": {"/device:TPU:0": [[ring, 0, 4e6], [guard, 4e6, 1e6],
                                        [perm, 5e6, 2e6], [done, 7e6, 1e6]],
                      "/device:TPU:1": [[ring, 0, 2e6], [perm, 5e6, 4e6]]},
          "host": [["invoke", 0, 10e6, "main"], ["invoke", 10e6, 10e6, "main"]]}
    ctx = _ctx(tr.reduce(ev))
    # milliseconds per invocation, averaged over the two devices
    assert RING_KERNEL_MS.read(ctx) == pytest.approx(1.5)
    assert COLLECTIVE_MS.read(ctx) == pytest.approx(1.75)
    quiet = _ctx(tr.reduce({"devices": {"/device:TPU:0": [[guard, 0, 1]]},
                            "host": [["invoke", 0, 2, "main"]]}))
    assert RING_KERNEL_MS.read(quiet) is None
    assert COLLECTIVE_MS.read(quiet) is None


def test_recorded_program_spans():
    """On a chip trace of one invocation with the program's spans: every
    stage runs once, nested in the one ``compiled.run`` on the invoking
    thread; the stages cover it; it covers the benchmark's invoke; and
    the ring kernels found by name are those found by operand layout."""
    with gzip.open(DATA / "gemm_wide_spans_invocation.json.gz", "rt") as f:
        ev = json.load(f)["events"]
    (_, i0, idur, thread), = [h for h in ev["host"] if h[0] == "invoke"]
    (_, r0, rdur, rthread), = [h for h in ev["host"]
                               if h[0] == "compiled.run"]
    assert rthread == thread and i0 <= r0 and r0 + rdur <= i0 + idur
    assert rdur >= 0.98 * idur
    stages = [h for h in ev["host"] if h[0] in STAGES]
    assert sorted(h[0] for h in stages) == sorted(STAGES)
    for name, s, d, t in stages:
        assert t == thread and r0 <= s and s + d <= r0 + rdur, name
    assert sum(d for _, _, d, _ in stages) >= 0.95 * rdur
    ctx = _ctx(tr.reduce(ev))
    assert RING_MS.read(ctx) > 0
    assert RING_KERNEL_MS.read(ctx) == pytest.approx(RING_MS.read(ctx),
                                                     rel=0.02)


def _tiles(seconds: float = 0.5) -> dict:
    return run.run_cell(BENCH, "gemm.tiles", 2**31 + 11, seconds, False,
                        require_tpu=False, sizes=TILES)


def test_tiles_sound_run_is_correct(fresh_caches):
    res = _tiles()
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_tiles_control_fails_a_limit(fresh_caches):
    limits = run.cell_spec(BENCH, "gemm.tiles").traffic["limits"]
    got = {}
    for r in control.readings("gemm.tiles", [5], [5, 6, 7], 0.3,
                              require_tpu=False, sizes=TILES):
        got.setdefault(r["kind"], []).append(r)
    assert all(r["correct"] for r in got["program"])
    for r in got["control"]:
        assert any(r[k] > lim for k, lim in limits.items()), r


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_left_out"])
def test_tiles_fault_makes_run_incorrect(fault, fresh_caches, monkeypatch):
    _broken_writeback(monkeypatch, fault)
    assert not _tiles()["correct"]
