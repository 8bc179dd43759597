"""Crash recovery: graph snapshots, supervised restart, serving journal.

Four sections:

* **Snapshots + chunked execution** — ``run_recoverable`` produces
  bit-identical mmap outputs vs. the plain engines, on every engine, with
  and without a persistent :class:`SnapshotStore`.
* **Fault matrix** — inject a :class:`CrashFault` (task-site or chunk
  boundary), let :func:`run_supervised` restore the latest snapshot, and
  assert the final outputs match the fault-free run bit for bit — on gemm
  AND page_rank (the feedback case), across the coroutine and compiled
  engines, including snapshot-under-one-engine -> restore-under-another.
* **Edge-case capture/restore** — a channel frozen mid-burst, a full
  channel, EoT-propagated-but-unread, and an ``AsyncMMap`` with an
  accepted-but-undelivered (in-flight) request.
* **Serving journal** — replay folding, torn-tail repair, exactly-once
  delivery across a simulated and a real SIGKILL crash, and
  no-recompute-on-replay.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import CrashFault, StepTask, channel, mmap
from repro.core.channel import EOT
from repro.core.faults import FaultPlan
from repro.core.interface import async_mmap
from repro.ft.recovery import (RestartPolicy, SnapshotStore, capture_channel,
                               capture_port, restore_channel, restore_port,
                               run_recoverable, run_supervised)
from repro.serve import (Request, ServeConfig, ServeJournal, ServingEngine,
                         serve_requests)

SRC = str(Path(__file__).resolve().parent.parent / "src")
# crash faults are count-based (seed moves nothing), but the CI chaos
# sweep runs this file under several seeds like test_faults.py
SEED = int(os.environ.get("REPRO_CHAOS_SEED", "0"))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _mmaps(args):
    """Every MMap in a (possibly nested) args tuple, in order."""
    from repro.core.interface import MMap
    out = []

    def walk(v):
        if isinstance(v, MMap):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                walk(x)
    walk(args)
    return out


def _outputs(args):
    return [np.array(np.asarray(m.data), copy=True) for m in _mmaps(args)]


def relay_pipeline(n_tokens=32, burst=8, capacity=16):
    fires = n_tokens // burst

    def source_step(k, out):
        out.write_burst(jnp.arange(burst, dtype=jnp.int32) + k * burst)
        return k + 1

    def relay_step(state, inp, out):
        out.write_burst(inp.read_burst(burst) * 2)
        return state

    def sink_step(k, inp, res):
        res.write_burst(k * burst, inp.read_burst(burst))
        return k + 1

    Source = StepTask(source_step, steps=fires, init=jnp.int32(0),
                      name="Source")
    Relay = StepTask(relay_step, steps=fires, name="Relay")
    Sink = StepTask(sink_step, steps=fires, init=jnp.int32(0), name="Sink")

    buf = np.zeros(n_tokens, np.int32)
    res = mmap(buf, "res")

    def Top(res):
        c0 = channel(capacity, "c0", dtype=np.int32, shape=())
        c1 = channel(capacity, "c1", dtype=np.int32, shape=())
        repro.task().invoke(Source, c0).invoke(Relay, c0, c1) \
            .invoke(Sink, c1, res)

    return Top, (res,), buf


def _build_app(app):
    if app == "gemm":
        from repro.apps import gemm
        return gemm.build_step(P=2, n=4, K=3, seed=0)
    from repro.apps import page_rank
    return page_rank.build_step(n_vertices=16, n_edges=48, n_pe=2,
                                n_iters=4, seed=0)


def _golden(app):
    top, args, check = _build_app(app)
    rep = repro.ENGINES["coroutine"]().run(top, *args)
    assert rep.ok, rep.error
    ok, err = check()
    assert ok, err
    return _outputs(args)


# ---------------------------------------------------------------------------
# snapshots + chunked execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine",
                         ["sequential", "thread", "coroutine", "compiled"])
def test_recoverable_matches_plain_every_engine(engine):
    top, args, buf = relay_pipeline()
    rep = repro.ENGINES["coroutine"]().run(top, *args)
    assert rep.ok
    golden = buf.copy()

    top, args, buf = relay_pipeline()
    rep = run_recoverable(engine, top, *args, snapshot_every=2)
    assert rep.ok, rep.error
    assert np.array_equal(buf, golden)


def test_recoverable_snapshots_cut_on_full_channels():
    """A tight capacity forces sweep cuts where channels are full — the
    snapshot must carry a full ring and restore it."""
    top, args, buf = relay_pipeline(n_tokens=48, burst=8, capacity=8)
    rep = repro.ENGINES["coroutine"]().run(top, *args)
    assert rep.ok
    golden = buf.copy()
    for engine in ("coroutine", "compiled"):
        top, args, buf = relay_pipeline(n_tokens=48, burst=8, capacity=8)
        rep = run_recoverable(engine, top, *args, snapshot_every=1)
        assert rep.ok, rep.error
        assert np.array_equal(buf, golden), engine


def test_store_resume_skips_completed_sweeps(tmp_path):
    top, args, buf = relay_pipeline()
    store = SnapshotStore(tmp_path)
    inj = FaultPlan(seed=SEED, crash={"chunk": 2}).injector()
    with pytest.raises(CrashFault):
        run_recoverable("coroutine", top, *args, store=store,
                        snapshot_every=1, faults=inj)
    partial = buf.copy()
    # the crash interrupted the run mid-way: some output rows are missing
    top2, args2, buf2 = relay_pipeline()
    rep = run_recoverable("coroutine", top2, *args2, store=store,
                          snapshot_every=1)
    assert rep.ok, rep.error
    top3, args3, buf3 = relay_pipeline()
    rep3 = repro.ENGINES["coroutine"]().run(top3, *args3)
    assert np.array_equal(buf2, buf3)
    assert not np.array_equal(partial, buf3)   # the crash really cut it


def test_stale_snapshot_of_other_graph_is_ignored(tmp_path):
    store = SnapshotStore(tmp_path)
    top, args, _ = relay_pipeline()
    rep = run_recoverable("coroutine", top, *args, store=store,
                          snapshot_every=2)
    assert rep.ok
    # a different graph with the same store directory starts from scratch
    top2, args2, buf2 = relay_pipeline(n_tokens=48, burst=8, capacity=8)
    rep = run_recoverable("coroutine", top2, *args2, store=store,
                          snapshot_every=2)
    assert rep.ok, rep.error
    top3, args3, buf3 = relay_pipeline(n_tokens=48, burst=8, capacity=8)
    repro.ENGINES["coroutine"]().run(top3, *args3)
    assert np.array_equal(buf2, buf3)


def test_abstract_schedule_matches_compiled_sweep_count():
    from repro.core.synth import elaborate_step_graph
    from repro.ft.recovery import _abstract_schedule
    top, args, _ = relay_pipeline(n_tokens=48, burst=8, capacity=8)
    plan, graph, _ = elaborate_step_graph(top, *args)
    cuts, stalled = _abstract_schedule(plan)
    assert not stalled
    top2, args2, _ = relay_pipeline(n_tokens=48, burst=8, capacity=8)
    rep = repro.ENGINES["compiled"]().run(top2, *args2)
    assert rep.ok
    assert rep.switches == len(cuts) - 1


# ---------------------------------------------------------------------------
# fault matrix: crash + supervised restart -> bit-identical outputs
# ---------------------------------------------------------------------------

_CRASHES = {
    # exact instance names (these graphs name instances explicitly)
    "gemm": [{"chunk": 1}, {"PE1_1": 4}],
    "page_rank": [{"chunk": 1}, {"Scatter0": 2}],
}


@pytest.mark.parametrize("app", ["gemm", "page_rank"])
@pytest.mark.parametrize("engine", ["coroutine", "compiled"])
def test_fault_matrix_recovery_parity(app, engine, tmp_path):
    golden = _golden(app)
    crashes = _CRASHES[app] if engine != "compiled" else \
        [c for c in _CRASHES[app] if "chunk" in c]
    for k, crash in enumerate(crashes):
        top, args, check = _build_app(app)
        store = SnapshotStore(tmp_path / f"{engine}_{k}")
        rep = run_supervised(engine, top, *args,
                             store=store, snapshot_every=2,
                             faults=FaultPlan(seed=SEED, crash=crash),
                             policy=RestartPolicy(max_restarts=2,
                                                  backoff_s=0.0))
        assert rep.ok, (crash, rep.error)
        got = _outputs(args)
        for a, b in zip(got, golden):
            assert np.array_equal(a, b), (crash, "output mismatch")
        ok, err = check()
        assert ok, (crash, err)


@pytest.mark.parametrize("app", ["gemm", "page_rank"])
@pytest.mark.parametrize("first,second", [("coroutine", "compiled"),
                                          ("compiled", "coroutine")])
def test_cross_engine_snapshot_restore_parity(app, first, second, tmp_path):
    """Crash under one engine, finish under the other, from the same
    persisted snapshot — outputs must be bit-identical to fault-free."""
    golden = _golden(app)
    store = SnapshotStore(tmp_path)
    top, args, _ = _build_app(app)
    inj = FaultPlan(seed=SEED, crash={"chunk": 1}).injector()
    with pytest.raises(CrashFault):
        run_recoverable(first, top, *args, store=store, snapshot_every=1,
                        faults=inj)
    top2, args2, check2 = _build_app(app)
    rep = run_recoverable(second, top2, *args2, store=store,
                          snapshot_every=1)
    assert rep.ok, rep.error
    got = _outputs(args2)
    for a, b in zip(got, golden):
        assert np.array_equal(a, b), "cross-engine output mismatch"
    ok, err = check2()
    assert ok, err


def test_supervisor_exhausts_restarts_and_raises():
    top, args, _ = relay_pipeline()
    # an unkeyed persistent crash: a fresh injector every attempt would
    # refire, but the SHARED injector fires once — so to exhaust restarts
    # we crash at three distinct boundaries
    with pytest.raises(CrashFault, match="still crashing"):
        run_supervised(
            "coroutine", top, *args,
            faults=FaultPlan(seed=SEED, crash={"Source": 0, "Relay": 0,
                                            "Sink": 0}),
            policy=RestartPolicy(max_restarts=1, backoff_s=0.0))


def test_supervisor_plain_delegation_without_store():
    """store=None is the zero-overhead path: plain engine run, and a
    crash restarts from scratch (shared injector fires once)."""
    top, args, buf = relay_pipeline()
    rep = run_supervised("coroutine", top, *args,
                         faults=FaultPlan(seed=SEED, crash={"Relay": 3}),
                         policy=RestartPolicy(max_restarts=2, backoff_s=0.0))
    assert rep.ok, rep.error
    top2, args2, buf2 = relay_pipeline()
    repro.ENGINES["coroutine"]().run(top2, *args2)
    assert np.array_equal(buf, buf2)


def test_supervisor_falls_back_for_non_step_graphs():
    """Outside the step subset (EoT termination) the supervisor degrades
    to restart-from-scratch — and still recovers from a crash."""
    got = []

    def producer(out):
        out.write_burst([1, 2, 3])
        out.close()

    def consumer(inp):
        got.append([int(t) for t in inp.read_transaction()])

    def Top():
        c = channel(8, "c", dtype=np.int32, shape=())
        repro.task().invoke(producer, c).invoke(consumer, c)

    rep = run_supervised("coroutine", Top,
                         store=None,
                         faults=FaultPlan(seed=SEED, crash={"producer": 1}),
                         policy=RestartPolicy(max_restarts=2, backoff_s=0.0))
    assert rep.ok, rep.error
    assert got[-1] == [1, 2, 3]


# ---------------------------------------------------------------------------
# edge-case capture/restore containers
# ---------------------------------------------------------------------------

def test_capture_restore_channel_mid_burst():
    """Freeze a channel halfway through a burst write (more tokens than a
    reader has consumed) and restore it into a fresh channel."""
    c = channel(8, "c", dtype=np.int32, shape=())
    for t in (1, 2, 3):
        c._push(t)
    st = capture_channel(c)
    c._pop(), c._push(9)                 # diverge after the capture
    c2 = channel(8, "c", dtype=np.int32, shape=())
    restore_channel(c2, st)
    assert list(c2._q) == [1, 2, 3]
    assert c2._eot_count == 0


def test_capture_restore_full_channel():
    c = channel(4, "c", dtype=np.int32, shape=())
    for t in range(4):
        c._push(t)
    st = capture_channel(c)
    c2 = channel(4, "c", dtype=np.int32, shape=())
    restore_channel(c2, st)
    assert len(c2._q) == c2.capacity == 4
    assert list(c2._q) == [0, 1, 2, 3]


def test_capture_restore_eot_propagated_but_unread():
    """EoT sits in the queue behind unread data: the restored channel
    must deliver the transaction then the EoT, exactly once."""
    c = channel(8, "c", dtype=np.int32, shape=())
    c._push(7)
    c._push(8)
    c._push(EOT)
    st = capture_channel(c)
    assert st.eot_count == 1
    c2 = channel(8, "c", dtype=np.int32, shape=())
    restore_channel(c2, st)
    assert c2._eot_count == 1
    got = []
    while c2._q:
        t = c2._pop()
        if t is EOT:
            break
        got.append(int(t))
    assert got == [7, 8]
    assert c2._eot_count == 0 and not c2._q       # EoT delivered exactly once


class _StubEngine:
    """Just enough engine surface for AsyncMMap.pump: a clock and an
    event list we can drain (or abandon, simulating a crash)."""
    clock = 0
    force_async = True
    faults = None

    def __init__(self):
        self.events = []

    def schedule_async(self, lat, fn):
        self.events.append(fn)

    def _iface_pop(self, ch):
        return ch._pop()

    def _iface_deliver(self, ch, v):
        ch._push(v)


def test_capture_restore_port_with_pending_response():
    data = np.arange(8, dtype=np.float32)
    port = async_mmap(data, name="m", latency=2, depth=4)
    eng = _StubEngine()
    port._raddr._push(3)
    port._raddr._push(5)
    port.pump(eng)
    assert port._pending_reads == 2            # accepted, not delivered
    assert port._inflight_reads == [3, 5]
    st = capture_port(port)

    # crash: the engine's event heap (delivery closures) is gone
    port2 = async_mmap(np.zeros(8, np.float32), name="m", latency=2, depth=4)
    restore_port(port2, st)
    assert np.array_equal(np.asarray(port2.data), data)
    assert port2._pending_reads == 0
    # the in-flight requests were re-queued ahead of anything unaccepted
    assert list(port2._raddr._q) == [3, 5]
    eng2 = _StubEngine()
    port2.pump(eng2)                           # re-accept
    for fn in list(eng2.events):               # deliver
        fn(eng2)
    assert [float(v) for v in port2._rdata._q] == [3.0, 5.0]
    assert port2._pending_reads == 0 and port2._inflight_reads == []


def test_capture_restore_port_inflight_write():
    data = np.zeros(8, np.float32)
    port = async_mmap(data, name="m", latency=1, depth=4)
    eng = _StubEngine()
    port._waddr._push(2)
    port._wdata._push(7.5)
    port.pump(eng)
    assert port._inflight_writes == [(2, 7.5)]
    st = capture_port(port)
    port2 = async_mmap(np.zeros(8, np.float32), name="m", latency=1, depth=4)
    restore_port(port2, st)
    eng2 = _StubEngine()
    port2.pump(eng2)
    for fn in list(eng2.events):
        fn(eng2)
    assert float(np.asarray(port2.data)[2]) == 7.5
    assert len(port2._wresp._q) == 1           # the ack materialized


# ---------------------------------------------------------------------------
# compiled latency queues in snapshots (resumable async_mmap)
# ---------------------------------------------------------------------------


def _async_gemm():
    from repro.apps import gemm
    return gemm.build_step_async(P=2, n=4, K=4, depth=4)


def _c_bytes(args):
    _, _, c_ports = args
    return np.stack([np.asarray(p.data) for p in c_ports]).tobytes()


@pytest.mark.slow
def test_python_engines_refuse_port_graphs(tmp_path):
    from repro.core import SynthesisError
    top, args, _ = _async_gemm()
    store = SnapshotStore(tmp_path)
    with pytest.raises(SynthesisError, match="async_mmap ports .*compiled"):
        run_recoverable("coroutine", top, *args, store=store,
                        snapshot_every=2)


@pytest.mark.slow
def test_compiled_port_chunks_match_plain(tmp_path):
    """Depth-4 async gemm run in snapshot chunks is a bit-twin of the
    unchunked compiled run, and the snapshot rows carry the four ports'
    full 16-row latency-queue carry."""
    top, args, check = _async_gemm()
    rep = repro.ENGINES["compiled"]().run(top, *args)
    assert rep.ok and check()[0]
    golden = _c_bytes(args)

    store = SnapshotStore(tmp_path)
    top2, args2, check2 = _async_gemm()
    rep2 = run_recoverable("compiled", top2, *args2, store=store,
                           snapshot_every=3)
    assert rep2.ok, rep2.error
    assert check2()[0]
    assert _c_bytes(args2) == golden

    from repro.core.synth import elaborate_step_graph
    plan, graph, _ = elaborate_step_graph(top2, *args2)
    snap = store.load_latest(plan, graph.structural_hash(),
                             [c.capacity for c in plan.channels])
    assert snap is not None
    assert len(snap.ports) == len(plan.ports) == 4
    assert all(len(pc) == 16 for pc in snap.ports)


@pytest.mark.slow
def test_compiled_port_crash_resume_supervised(tmp_path):
    """A crash between chunks resumes from the port-bearing snapshot and
    still produces the plain run's exact output bytes."""
    top, args, check = _async_gemm()
    rep = repro.ENGINES["compiled"]().run(top, *args)
    assert rep.ok and check()[0]
    golden = _c_bytes(args)

    store = SnapshotStore(tmp_path)
    top2, args2, check2 = _async_gemm()
    rep2 = run_supervised("compiled", top2, *args2, store=store,
                          snapshot_every=3,
                          faults=FaultPlan(seed=7, crash={"chunk": 2}),
                          policy=RestartPolicy(max_restarts=2, backoff_s=0.0))
    assert rep2.ok, rep2.error
    assert check2()[0]
    assert _c_bytes(args2) == golden


# ---------------------------------------------------------------------------
# serving journal
# ---------------------------------------------------------------------------

V = 16


def _toy_engine(scfg, journal=None, calls=None):
    def prefill(toks):
        if calls is not None:
            calls.append(("prefill", toks.shape))
        last = int(toks[0, -1]) % V
        return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}

    def decode(tok, cache):
        return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

    return ServingEngine(scfg, prefill, decode, journal=journal)


def _reqs(n=6, max_new=5):
    return [Request(rid=i, prompt=[i, i + 1], max_new=max_new)
            for i in range(n)]


def test_journal_replay_folds_records(tmp_path):
    j = ServeJournal(tmp_path / "j.jsonl")
    j.admit(0, [1, 2], 4, None)
    j.tok(0, 3)
    j.tok(0, 4)
    j.admit(1, [5], 4, None)
    j.retire(0, toks=[3, 4, 9, 9])
    j.retire(2, status="deadline", detail="late")
    j.close()
    completed, inflight = ServeJournal.replay(tmp_path / "j.jsonl")
    assert completed == {0: [3, 4, 9, 9], 2: ("deadline", "late")}
    assert inflight == {1: {"prompt": [5], "max_new": 4, "deadline": None,
                            "toks": []}}


def test_journal_torn_tail_dropped_and_repaired(tmp_path):
    p = tmp_path / "j.jsonl"
    j = ServeJournal(p)
    j.admit(0, [1], 3, None)
    j.tok(0, 2)
    j.close()
    with open(p, "a") as f:
        f.write('{"t":"tok","rid":0,"to')      # crash mid-append
    completed, inflight = ServeJournal.replay(p)
    assert inflight[0]["toks"] == [2]          # torn record dropped
    j2 = ServeJournal(p)                       # reopen repairs the tail
    j2.tok(0, 5)
    j2.close()
    completed, inflight = ServeJournal.replay(p)
    assert inflight[0]["toks"] == [2, 5]       # appended record readable


def test_exactly_once_after_simulated_crash(tmp_path):
    scfg = ServeConfig(batch_slots=2, max_seq=64)
    oracle = serve_requests(_toy_engine(scfg), _reqs())

    jp = tmp_path / "j.jsonl"
    serve_requests(_toy_engine(scfg, journal=jp), _reqs())
    lines = open(jp).read().splitlines()
    # SIGKILL mid-stream: keep a prefix that leaves requests in flight
    cut = tmp_path / "cut.jsonl"
    cut.write_text("\n".join(lines[:9]) + "\n")
    completed, inflight = ServeJournal.replay(cut)
    assert inflight                            # something really in flight

    res = serve_requests(_toy_engine(scfg, journal=cut), _reqs())
    assert sorted(res) == sorted(oracle)       # every rid exactly once
    for rid in oracle:
        assert res[rid] == oracle[rid], rid


def test_completed_rids_answer_from_journal_without_recompute(tmp_path):
    scfg = ServeConfig(batch_slots=2, max_seq=64)
    jp = tmp_path / "j.jsonl"
    oracle = serve_requests(_toy_engine(scfg, journal=jp), _reqs())
    calls = []
    res = serve_requests(_toy_engine(scfg, journal=jp, calls=calls),
                         _reqs())
    assert res == oracle
    assert calls == []                         # zero prefill recompute


def test_seeded_resume_counts_seeded_tokens_once(tmp_path):
    """A request killed at its second-to-last token resumes for exactly
    one more token — max_new accounting spans the crash."""
    scfg = ServeConfig(batch_slots=1, max_seq=64)
    jp = tmp_path / "j.jsonl"
    j = ServeJournal(jp)
    j.admit(0, [4, 5], 3, None)
    j.tok(0, 6)
    j.tok(0, 7)
    j.close()
    res = serve_requests(_toy_engine(scfg, journal=jp),
                         [Request(rid=0, prompt=[4, 5], max_new=3)])
    assert res[0] == [6, 7, 8]
    completed, inflight = ServeJournal.replay(jp)
    assert completed[0] == [6, 7, 8] and not inflight


_SERVE_PROC = r"""
import json, sys, time
import numpy as np
from repro.serve import Request, ServeConfig, ServingEngine, serve_requests

V = 16
journal, slow = sys.argv[1], float(sys.argv[2])

def prefill(toks):
    last = int(toks[0, -1]) % V
    return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}

def decode(tok, cache):
    time.sleep(slow)
    return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

scfg = ServeConfig(batch_slots=2, max_seq=64)
eng = ServingEngine(scfg, prefill, decode, journal=journal)
reqs = [Request(rid=i, prompt=[i, i + 1], max_new=6) for i in range(4)]
res = serve_requests(eng, reqs)
print("RESULTS " + json.dumps({str(k): v for k, v in res.items()}))
"""


def test_sigkill_mid_stream_exactly_once(tmp_path):
    """SIGKILL a serving process mid-decode; the restarted process drains
    the journal and delivers every result exactly once, matching the
    fault-free oracle."""
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    jp = tmp_path / "j.jsonl"

    # oracle: no journal, no crash, instant decode
    oracle_j = tmp_path / "oracle.jsonl"
    r = subprocess.run([sys.executable, "-c", _SERVE_PROC,
                        str(oracle_j), "0"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    oracle = json.loads(r.stdout.split("RESULTS ", 1)[1])

    # victim: slow decode so the parent can kill it mid-stream
    p = subprocess.Popen([sys.executable, "-c", _SERVE_PROC,
                          str(jp), "0.05"],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, env=env)
    deadline = time.time() + 120
    try:
        while time.time() < deadline:
            if jp.exists() and \
                    sum(1 for l in open(jp) if '"t":"tok"' in l) >= 5:
                break
            if p.poll() is not None:
                pytest.fail(f"victim exited early: "
                            f"{p.communicate()[1][-2000:]}")
            time.sleep(0.02)
        else:
            pytest.fail("victim made no journal progress")
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
    completed, inflight = ServeJournal.replay(jp)
    assert inflight, "SIGKILL landed after all requests finished"

    # restart: same command, same journal
    r = subprocess.run([sys.executable, "-c", _SERVE_PROC, str(jp), "0"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.split("RESULTS ", 1)[1])
    assert res == oracle                       # exactly once, bit-for-bit


# ---------------------------------------------------------------------------
# train driver: kill-and-resume through resume_or_init
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_kill_and_resume_falls_past_corrupt_step(tmp_path):
    """SIGKILL a training run mid-flight, corrupt its newest checkpoint,
    and assert the rerun resumes from the previous verified step."""
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    ckpt = tmp_path / "ckpt"
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "qwen3-0.6b", "--reduced", "--steps", "400", "--batch", "2",
           "--seq", "32", "--ckpt-dir", str(ckpt), "--ckpt-every", "2",
           "--log-every", "1000"]
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, env=env)
    deadline = time.time() + 240
    try:
        while time.time() < deadline:
            done = sorted(ckpt.glob("step_*/DONE"))
            if len(done) >= 2:
                break
            if p.poll() is not None:
                pytest.fail(f"train exited early: "
                            f"{p.communicate()[1][-3000:]}")
            time.sleep(0.1)
        else:
            pytest.fail("no checkpoints appeared before the deadline")
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()

    from repro.ckpt import CheckpointManager
    mgr = CheckpointManager(ckpt)
    steps = mgr.steps()
    assert len(steps) >= 2
    # corrupt the newest published step: truncate one leaf file
    victim = sorted((ckpt / f"step_{steps[-1]:08d}").rglob("*.npy"))[0]
    victim.write_bytes(victim.read_bytes()[:10])
    assert mgr.verify(steps[-1])               # really corrupt now

    r = subprocess.run(cmd[:cmd.index("400")] + [str(steps[-2] + 2)] +
                       cmd[cmd.index("400") + 1:],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode in (0, 1), r.stderr[-3000:]
    assert f"resumed from checkpoint step {steps[-2]}" in r.stdout
