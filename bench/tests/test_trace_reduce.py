"""The reduction from a trace to the per-layer metrics' inputs."""

import gzip
import json
import re
from pathlib import Path

import pytest

import run

tr = run.load_module(run.BENCH / "trace_reduce.py")
RING_OPS = run.load_module(run.BENCH / "metrics" / "ring_ms.py").RING_OPS
DATA = Path(__file__).resolve().parent / "data"


def test_union_kernels_and_gaps_by_hand():
    ev = {"devices": {"/device:TPU:0": [["a", 0, 15], ["b", 5, 5],
                                        ["c", 30, 5], ["d", 50, 10]]},
          "host": [["invoke", 0, 20, "main"], ["refresh", 20, 5, "main"],
                   ["invoke", 25, 15, "main"], ["other", 15, 3, "bg"]]}
    red = tr.reduce(ev)
    # window [0, 40]; busy [0, 15] and [30, 35]; b runs inside a; op d
    # lies outside the window
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["n_invokes"] == 2
    assert tr.op_seconds(red, "^(a|b)$") == pytest.approx(20e-9)
    assert tr.op_seconds(red, "d") == 0.0
    # idle [15, 30]: 15-20 in the first invoke, 20-25 in the refresh,
    # 25-30 in the second invoke; idle [35, 40] in the second invoke.
    # The other thread's event is not the invoking thread's.
    assert red["gaps"] == {"invoke": pytest.approx(15e-9),
                           "refresh": pytest.approx(5e-9)}
    bd = tr.breakdown(red)
    assert bd["device_ops"] == [["a", pytest.approx(10e-9)],
                                ["b", pytest.approx(5e-9)],
                                ["c", pytest.approx(5e-9)]]


def test_busy_is_averaged_over_devices():
    ev = {"devices": {"/device:TPU:0": [["x", 0, 10]],
                      "/device:TPU:1": [["x", 0, 30]]},
          "host": [["invoke", 0, 40, "main"]]}
    red = tr.reduce(ev)
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["op_s"]["x"] == pytest.approx(20e-9)


@pytest.mark.parametrize("name", sorted(p.name for p in
                                         DATA.glob("*.json.gz")))
def test_recorded_chip_trace(name):
    """A slice of a real chip trace, with its reduction written down when
    it was recorded; the ring kernels are counted by the metric's own
    pattern."""
    with gzip.open(DATA / name, "rt") as f:
        rec = json.load(f)
    ev, want = rec["events"], rec["expect"]
    red = tr.reduce(ev)
    assert red["n_invokes"] == want["n_invokes"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    for pattern, secs in want["op_seconds"].items():
        assert tr.op_seconds(red, pattern) == pytest.approx(secs, rel=1e-9)
    ring = re.compile(RING_OPS)
    calls = [o for ops in ev["devices"].values() for o in ops
             if ring.search(o[0])]
    assert len(calls) == want["ring_calls"]
    assert tr.op_seconds(red, RING_OPS) == pytest.approx(
        sum(d for _, _, d in calls) / 1e9, rel=1e-9)
    assert set(red["gaps"]) == set(want["gap_names"])
    assert sum(red["gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
