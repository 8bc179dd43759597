"""The ``gemm.paper`` cell (the PolySA GEMM at the paper's 13x13 array)
at a small block on the CPU, and the ``guard_kernel_ms`` reader on a
reduced trace made by hand."""

from types import SimpleNamespace

import pytest

import control
import run

tr = run.load_module(run.BENCH / "trace_reduce.py")
GUARD_KERNEL_MS = run.load_module(run.BENCH / "metrics" /
                                  "guard_kernel_ms.py")
BENCH = run.read_json(run.ROOT / "BENCHMARK.json")
PAPER = {"traffic": {"n": 8, "K": 2}}     # the cell's array, CPU-sized


def _ctx(red: dict) -> SimpleNamespace:
    return SimpleNamespace(red=red, n=red["n_invokes"],
                           op_seconds=lambda p: tr.op_seconds(red, p))


def test_guard_kernel_by_name():
    guard = ("%eval_guards.7 = s32[208,128]{1,0} custom-call(s32[208,512] "
             '%p), custom_call_target="tpu_custom_call"')
    unnamed = ("%custom-call.7 = s32[208,128]{1,0} custom-call(s32[208,512] "
               '%p), custom_call_target="tpu_custom_call"')
    ring = ("%ring_push.3 = f32[1,8,128]{2,1,0} custom-call(s32[1]{0} %p), "
            'custom_call_target="tpu_custom_call"')
    host = [["invoke", 0, 10e6, "main"], ["invoke", 10e6, 10e6, "main"]]
    ctx = _ctx(tr.reduce({"devices": {"/device:TPU:0": [
        [guard, 0, 3e6], [ring, 3e6, 4e6], [guard, 12e6, 1e6]]},
        "host": host}))
    assert GUARD_KERNEL_MS.read(ctx) == pytest.approx(2.0)
    # a program that leaves the call unnamed reads nothing
    quiet = _ctx(tr.reduce({"devices": {"/device:TPU:0": [
        [unnamed, 0, 3e6], [ring, 3e6, 4e6]]}, "host": host}))
    assert GUARD_KERNEL_MS.read(quiet) is None


def test_paper_cell_is_correct(fresh_caches):
    res = run.run_cell(BENCH, "gemm.paper", 2**31 + 11, 0.5, False,
                       require_tpu=False, sizes=PAPER)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_paper_control_fails_a_limit(fresh_caches):
    limits = run.cell_spec(BENCH, "gemm.paper").traffic["limits"]
    got = {}
    for r in control.readings("gemm.paper", [5], [5, 6], 0.3,
                              require_tpu=False, sizes=PAPER):
        got.setdefault(r["kind"], []).append(r)
    assert all(r["correct"] for r in got["program"])
    for r in got["control"]:
        assert any(r[k] > lim for k, lim in limits.items()), r
