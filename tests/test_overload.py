"""Overload robustness: traffic shaping, admission control, shedding,
the circuit breaker, and deterministic simulated-time overload runs.

Structure mirrors the overload layer (PR 8):

* trace generation — seeded determinism, scale knob, chaos overlays;
* admission controller units — DRR fairness, priority classes, the three
  shed mechanisms, the accounting invariant;
* circuit breaker — unit transitions on a fake clock plus end-to-end
  open/half-open/closed cycles against injected step faults;
* hardening satellites — capped deadline-aware retry backoff, bounded
  full-queue admission (both the blocking and fail-fast contracts);
* journal — shed records are write-ahead, replay exactly-once, and
  survive torn tails interleaved with admit/tok/retire;
* end-to-end virtual-time overload runs — every offered request answered,
  ``offered == admitted + shed``, byte-identical across runs and
  processes, and the shed-off arm demonstrably collapses where the
  shed-on arm stays inside its deadline.

The e2e tests honour ``REPRO_TRAFFIC_SEED`` (CI sweeps seeds 0..2).
"""

import hashlib
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core.faults import FaultPlan
from repro.serve import (AdmissionConfig, AdmissionController, BreakerOpen,
                         CircuitBreaker, Request, RequestError, ServeConfig,
                         ServeJournal, ServeMetrics, ServingEngine,
                         TenantSpec, VirtualClock, make_trace,
                         noisy_neighbor_mix, serve_requests, trace_digest,
                         uniform_mix)

SRC = str(Path(__file__).resolve().parent.parent / "src")
SEED = int(os.environ.get("REPRO_TRAFFIC_SEED", "0"))

V = 16   # toy vocab (next token = (prev + 1) % V)


def _toy_engine(scfg: ServeConfig, **kw) -> ServingEngine:
    def prefill(toks):
        last = int(toks[0, -1]) % V
        return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}

    def decode(tok, cache):
        return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

    return ServingEngine(scfg, prefill, decode, **kw)


def _virtual_setup(trace_kw=None, ctrl_kw=None, slots=2, step_dt=0.01,
                   shed=True, journal=None, duration=2.0, rate=35.0,
                   deadline_s=0.4, seed=SEED):
    """One deterministic overload run's parts: engine + trace + metrics.

    The VirtualClock is shared by the engine, the controller and the
    metrics (the engine ctor wires it through), so the entire run —
    arrivals, queue dynamics, sheds, TTFT percentiles — is a pure
    function of (seed, config).
    """
    vc = VirtualClock()
    metrics = ServeMetrics()
    ctrl = None
    if shed:
        ctrl = AdmissionController(AdmissionConfig(
            est_token_s=step_dt, queue_limit=8,
            **(ctrl_kw or {})))
    scfg = ServeConfig(batch_slots=slots, max_seq=64, prefill_buckets=(8,))
    eng = _toy_engine(scfg, admission=ctrl, metrics=metrics,
                      journal=journal, clock=vc, pace="virtual",
                      step_dt=step_dt)
    tenants = uniform_mix(2, rate=rate, deadline_s=deadline_s,
                          max_new=(4, 8), prompt_len=(2, 6))
    trace = make_trace(tenants, duration, seed=seed, vocab=V,
                       **(trace_kw or {}))
    if ctrl is not None:
        ctrl.register_tenants(tenants)
    return eng, trace, metrics


# ---------------------------------------------------------------------------
# traffic generation
# ---------------------------------------------------------------------------

def test_trace_same_seed_is_byte_identical():
    mix = uniform_mix(3, rate=11.0, deadline_s=0.25)
    a = make_trace(mix, 2.0, seed=SEED, vocab=64)
    b = make_trace(mix, 2.0, seed=SEED, vocab=64)
    assert a == b
    assert trace_digest(a) == trace_digest(b)
    c = make_trace(mix, 2.0, seed=SEED + 1, vocab=64)
    assert trace_digest(c) != trace_digest(a)


def test_trace_is_sorted_with_sequential_rids():
    t = make_trace(noisy_neighbor_mix(), 2.0, seed=SEED, vocab=64)
    assert [r.rid for r in t] == list(range(len(t)))
    arr = [r.t_arrival for r in t]
    assert arr == sorted(arr)
    assert {r.tenant for r in t} == {"victim", "flood"}


def test_trace_scale_densifies_not_reshapes():
    """2x scale doubles the arrival density but keeps every tenant's
    request-shape stream aligned (the 1x-vs-2x benchmark contract)."""
    mix = uniform_mix(2, rate=10.0)
    one = make_trace(mix, 3.0, seed=SEED, vocab=64)
    two = make_trace(mix, 3.0, seed=SEED, vocab=64, scale=2.0)
    assert len(two) > 1.5 * len(one)
    for tenant in ("t0", "t1"):
        a = [(r.prompt, r.max_new) for r in one if r.tenant == tenant]
        b = [(r.prompt, r.max_new) for r in two if r.tenant == tenant]
        # shape draws are keyed per-tenant by arrival index, so the
        # 1x stream is a prefix of the densified 2x stream
        assert b[:len(a)] == a


def test_trace_digest_matches_across_processes():
    mix = uniform_mix(2, rate=8.0, deadline_s=0.5)
    want = trace_digest(make_trace(mix, 2.0, seed=SEED, vocab=32))
    code = (
        "from repro.serve import make_trace, trace_digest, uniform_mix\n"
        f"mix = uniform_mix(2, rate=8.0, deadline_s=0.5)\n"
        f"t = make_trace(mix, 2.0, seed={SEED}, vocab=32)\n"
        "print(trace_digest(t))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == want


def test_arrival_burst_overlay_adds_arrivals_in_window():
    mix = uniform_mix(2, rate=5.0)
    base = make_trace(mix, 2.0, seed=SEED, vocab=32)
    plan = FaultPlan(seed=7, arrival_burst={
        "t0": {"at_s": 0.5, "dur_s": 0.5, "rate": 60.0}})
    inj = plan.injector()
    burst = make_trace(mix, 2.0, seed=SEED, vocab=32, faults=inj)
    extra = len(burst) - len(base)
    assert extra > 10
    # overlay arrivals land inside the window, on the targeted tenant
    base_t0 = [r.t_arrival for r in base if r.tenant == "t0"]
    burst_t0 = [r.t_arrival for r in burst if r.tenant == "t0"]
    new_times = sorted(set(burst_t0) - set(base_t0))
    assert new_times and all(0.5 <= t < 1.0 for t in new_times)
    assert [r.t_arrival for r in burst if r.tenant == "t1"] == \
        [r.t_arrival for r in base if r.tenant == "t1"]
    assert any(e[0] == "arrival_burst" and e[1] == "t0"
               for e in inj.log)


def test_tenant_flood_overlay_injects_low_priority_tenant():
    mix = uniform_mix(1, rate=4.0)
    plan = FaultPlan(seed=3, tenant_flood={
        "flood": {"rate": 50.0, "start_s": 0.0, "dur_s": 1.0}})
    inj = plan.injector()
    assert inj.affects_traffic
    t = make_trace(mix, 2.0, seed=SEED, vocab=32, faults=inj)
    flood = [r for r in t if r.tenant == "flood"]
    assert len(flood) > 20
    assert all(r.t_arrival < 1.0 for r in flood)
    assert any(e[0] == "tenant_flood" for e in inj.log)
    # fault seed is independent of the traffic seed: the base tenant's
    # arrivals are untouched by the overlay
    base = make_trace(mix, 2.0, seed=SEED, vocab=32)
    assert [r.t_arrival for r in t if r.tenant == "t0"] == \
        [r.t_arrival for r in base]


# ---------------------------------------------------------------------------
# admission controller: fair queuing
# ---------------------------------------------------------------------------

def _req(rid, tenant, max_new=8, prompt_len=0, deadline=None, t_arr=None):
    return Request(rid=rid, prompt=[1] * prompt_len, max_new=max_new,
                   deadline_s=deadline, tenant=tenant, t_arrival=t_arr)


def test_drr_equal_weights_alternate():
    # quantum == request cost: one serve per turn -> strict alternation
    ctrl = AdmissionController(AdmissionConfig(queue_limit=64,
                                               quantum_tokens=8.0))
    ctrl.register("a")
    ctrl.register("b")
    for i in range(8):
        assert ctrl.offer(_req(i, "a" if i < 4 else "b")) is None
    order = [ctrl.pop().tenant for _ in range(8)]
    assert order.count("a") == order.count("b") == 4
    assert all(x != y for x, y in zip(order, order[1:]))


def test_drr_weight_scales_token_share():
    ctrl = AdmissionController(AdmissionConfig(queue_limit=1000,
                                               quantum_tokens=8.0))
    ctrl.register("heavy", weight=2.0)
    ctrl.register("light", weight=1.0)
    for i in range(60):
        ctrl.offer(_req(i, "heavy" if i % 2 else "light", max_new=8))
    first = [ctrl.pop().tenant for _ in range(30)]
    share = first.count("heavy") / len(first)
    # weight 2 gets ~2/3 of the dispatched token budget while both are
    # backlogged
    assert 0.55 < share < 0.8, share


def test_priority_class_served_first():
    ctrl = AdmissionController(AdmissionConfig(queue_limit=64))
    ctrl.register("bulk", priority=1)
    ctrl.register("interactive", priority=0)
    for i in range(6):
        ctrl.offer(_req(i, "bulk"))
    for i in range(6, 9):
        ctrl.offer(_req(i, "interactive"))
    order = [ctrl.pop().tenant for _ in range(9)]
    assert order[:3] == ["interactive"] * 3
    assert order[3:] == ["bulk"] * 6


def test_unregistered_tenant_autoregisters():
    ctrl = AdmissionController(AdmissionConfig(queue_limit=4))
    assert ctrl.offer(_req(0, "surprise")) is None
    assert ctrl.pop().tenant == "surprise"
    assert ctrl.pop() is None


# ---------------------------------------------------------------------------
# admission controller: shedding
# ---------------------------------------------------------------------------

def test_reject_new_sheds_past_queue_limit():
    metrics = ServeMetrics(clock=lambda: 0.0)
    ctrl = AdmissionController(AdmissionConfig(queue_limit=2,
                                               retry_after_s=0.25),
                               metrics=metrics)
    verdicts = [ctrl.offer(_req(i, "t0")) for i in range(5)]
    assert verdicts[:2] == [None, None]
    for v in verdicts[2:]:
        assert isinstance(v, RequestError)
        assert v.status == "overloaded" and v.retry_after_s == 0.25
    assert ctrl.backlog() == 2 and ctrl.shed_total == 3
    assert metrics.shed_reasons == {"reject-new": 3}


def test_drop_oldest_evicts_lowest_priority_backlog():
    ctrl = AdmissionController(AdmissionConfig(shed_policy="drop-oldest",
                                               queue_limit=4))
    ctrl.register("victim", priority=0)
    ctrl.register("flood", priority=1)
    for i in range(2):
        assert ctrl.offer(_req(i, "victim")) is None
    for i in range(2, 4):
        assert ctrl.offer(_req(i, "flood")) is None
    # queue full: a new victim arrival evicts the FLOOD's oldest, not
    # its own tenant's — the flooder absorbs the shedding
    assert ctrl.offer(_req(4, "victim")) is None
    errs = ctrl.drain_errors()
    assert len(errs) == 1 and errs[0].rid == 2
    assert errs[0].status == "overloaded"
    assert ctrl.backlog() == 4
    tenants = []
    while (r := ctrl.pop()) is not None:
        tenants.append((r.rid, r.tenant))
    assert (2, "flood") not in tenants
    assert {rid for rid, _ in tenants} == {0, 1, 3, 4}


def test_deadline_infeasible_shed_at_offer():
    clock = VirtualClock()
    ctrl = AdmissionController(
        AdmissionConfig(est_token_s=0.1, queue_limit=64), clock=clock)
    # 8 tokens x 0.1 s/token = 0.8s estimated > 0.3s budget
    v = ctrl.offer(_req(0, "t0", max_new=8, deadline=0.3, t_arr=0.0))
    assert isinstance(v, RequestError) and v.status == "overloaded"
    assert "deadline" in v.detail
    # a feasible deadline is admitted
    assert ctrl.offer(_req(1, "t0", max_new=2, deadline=5.0,
                           t_arr=0.0)) is None


def test_deadline_infeasible_shed_at_dispatch():
    clock = VirtualClock()
    ctrl = AdmissionController(
        AdmissionConfig(est_token_s=0.01, queue_limit=64), clock=clock)
    assert ctrl.offer(_req(0, "t0", max_new=4, deadline=0.5,
                           t_arr=0.0)) is None
    clock.advance(10.0)                    # request went stale in queue
    assert ctrl.pop() is None
    errs = ctrl.drain_errors()
    assert [e.rid for e in errs] == [0]
    assert errs[0].status == "overloaded" and "unreachable" in errs[0].detail


def test_token_latency_ewma_refines_estimate():
    ctrl = AdmissionController(AdmissionConfig(est_token_s=0.0, ewma=0.5))
    assert ctrl.token_s == 0.0
    ctrl.observe_token_latency(0.1)        # first sample seeds the EWMA
    assert ctrl.token_s == pytest.approx(0.1)
    ctrl.observe_token_latency(0.2)
    assert ctrl.token_s == pytest.approx(0.15)
    ctrl.observe_token_latency(-1.0)       # non-positive samples ignored
    assert ctrl.token_s == pytest.approx(0.15)


def test_admission_config_rejects_unknown_policy():
    with pytest.raises(ValueError, match="shed_policy"):
        AdmissionConfig(shed_policy="fifo")


def test_metrics_accounting_invariant_catches_leaks():
    m = ServeMetrics(clock=lambda: 0.0)
    m.note_offered("a")
    m.note_admitted("a")
    m.note_offered("a")
    with pytest.raises(AssertionError, match="offered 2"):
        m.check_accounting()
    m.note_shed("a", "reject-new")
    m.check_accounting()


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_transitions_on_fake_clock():
    clock = VirtualClock()
    br = CircuitBreaker(fail_threshold=3, cooldown_s=1.0, clock=clock)
    br.failure("e1")
    br.failure("e2")
    assert br.state == "closed"
    br.check()                              # still closed: no-op
    br.failure("e3")
    assert br.state == "open"
    with pytest.raises(BreakerOpen) as ei:
        br.check()
    assert 0 < ei.value.retry_after_s <= 1.0
    clock.advance(1.5)                      # cooldown elapses
    br.check()                              # admits the probe
    assert br.state == "half-open"
    br.failure("probe died")
    assert br.state == "open"               # probe failure re-opens
    clock.advance(1.5)
    br.check()
    br.success()
    assert br.state == "closed" and br.consecutive == 0
    states = [(frm, to) for _, frm, to, _ in br.log]
    assert states == [("closed", "open"), ("open", "half-open"),
                      ("half-open", "open"), ("open", "half-open"),
                      ("half-open", "closed")]


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(fail_threshold=2, clock=VirtualClock())
    br.failure()
    br.success()
    br.failure()
    assert br.state == "closed"             # never two in a row


def test_breaker_e2e_fast_fails_requests_while_open():
    """First real step failure opens the breaker (threshold 1, huge
    cooldown): every subsequent request fast-fails with a structured
    "overloaded" verdict and a retry hint, no compute spent."""
    scfg = ServeConfig(batch_slots=1, max_seq=32, max_retries=0,
                       prefill_buckets=(8,))
    br = CircuitBreaker(fail_threshold=1, cooldown_s=1e9)
    eng = _toy_engine(scfg, breaker=br,
                      faults=FaultPlan(transient={"decode": 1}))
    reqs = [Request(rid=i, prompt=[i], max_new=3) for i in range(4)]
    res = serve_requests(eng, reqs)
    assert len(res) == 4
    assert isinstance(res[0], RequestError)         # the opening failure
    for rid in (1, 2, 3):
        assert isinstance(res[rid], RequestError), rid
        assert res[rid].status == "overloaded"
        assert res[rid].retry_after_s > 0
    assert br.state == "open"


def test_breaker_e2e_half_open_probe_recovers():
    """cooldown 0: after opening, the next step call is admitted as a
    half-open probe; once the injected transients run out the probe
    succeeds, the breaker closes, and serving finishes normally.

    The faults hit *prefill* so the failures are consecutive across
    requests — a decode failure retires its slot, and the next request's
    successful prefill would reset the consecutive count."""
    scfg = ServeConfig(batch_slots=1, max_seq=32, max_retries=0,
                       prefill_buckets=(8,))
    br = CircuitBreaker(fail_threshold=2, cooldown_s=0.0)
    eng = _toy_engine(scfg, breaker=br,
                      faults=FaultPlan(transient={"prefill": 3}))
    reqs = [Request(rid=i, prompt=[i], max_new=3) for i in range(6)]
    res = serve_requests(eng, reqs)
    assert len(res) == 6
    assert br.state == "closed"
    states = [(frm, to) for _, frm, to, _ in br.log]
    assert states == [("closed", "open"), ("open", "half-open"),
                      ("half-open", "open"), ("open", "half-open"),
                      ("half-open", "closed")]
    # the tail requests decode clean once the breaker closes
    ok = [rid for rid, v in res.items() if not isinstance(v, RequestError)]
    assert len(ok) >= 3


# ---------------------------------------------------------------------------
# hardening satellites: backoff + bounded admission wait
# ---------------------------------------------------------------------------

def test_backoff_total_capped_per_step_call():
    """Seed bug: base * 2**attempt backoff was uncapped — a few retries
    could stall the decode loop for minutes.  The total backoff for one
    step call is now bounded by retry_max_s."""
    scfg = ServeConfig(batch_slots=1, max_seq=32, max_retries=4,
                       retry_base_s=10.0, retry_max_s=0.05,
                       prefill_buckets=(8,))
    eng = _toy_engine(scfg, faults=FaultPlan(transient={"decode": 3}))
    t0 = time.perf_counter()
    res = serve_requests(eng, [Request(rid=0, prompt=[1], max_new=3)])
    wall = time.perf_counter() - t0
    assert res[0] == [2, 3, 4]              # retries eventually succeed
    assert len(eng.retry_log) == 3
    assert wall < 2.0, f"backoff not capped: {wall:.1f}s"


def test_backoff_never_sleeps_past_live_deadline():
    scfg = ServeConfig(batch_slots=1, max_seq=32,
                       retry_base_s=1.0, retry_max_s=60.0)
    clock = VirtualClock()
    eng = _toy_engine(scfg, clock=clock)
    slot = {"rid": 0, "deadline": 0.02, "t0": 0.0, "t_arr": None}
    t0 = time.perf_counter()
    slept = eng._backoff(6, 0.0, [slot])    # exponential term: 64s
    assert time.perf_counter() - t0 < 1.0
    assert slept <= 0.02 + 1e-6             # clamped to deadline remaining
    # without a deadline the cap is retry_max_s - slept
    slept = eng._backoff(6, 59.99, [{"rid": 1, "deadline": None,
                                     "t0": 0.0}])
    assert slept <= 0.01 + 1e-6


def test_full_queue_blocking_default_still_serves_all():
    """Seed behaviour preserved: without admit_timeout_s the frontend
    blocks on a full request channel (cooperative hand-off) and every
    request is eventually served."""
    scfg = ServeConfig(batch_slots=1, max_seq=32, queue_cap=2,
                       prefill_buckets=(8,))
    reqs = [Request(rid=i, prompt=[i % V], max_new=2) for i in range(12)]
    res = serve_requests(_toy_engine(scfg), reqs)
    assert len(res) == 12
    assert not any(isinstance(v, RequestError) for v in res.values())


def test_full_queue_fail_fast_with_admit_timeout(tmp_path):
    """With admit_timeout_s set, a frontend facing a persistently full
    channel sheds with a journaled structured "overloaded" error after
    the bounded wait instead of blocking forever."""
    def prefill(toks):
        last = int(toks[0, -1]) % V
        return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}

    def decode(tok, cache):
        time.sleep(0.01)                    # slow backend: queue backs up
        return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

    # queue_cap == one transaction (hdr + 1 prompt token + EoT): each
    # buffered request fills the channel exactly, so a stalled scheduler
    # leaves it observably full at the next offer — the stuck-backend
    # shape the bounded wait exists for
    scfg = ServeConfig(batch_slots=1, max_seq=32, queue_cap=3,
                       admit_timeout_s=0.01, prefill_buckets=(8,))
    jp = tmp_path / "j.jsonl"
    metrics = ServeMetrics()
    eng = ServingEngine(scfg, prefill, decode, journal=jp, metrics=metrics)
    reqs = [Request(rid=i, prompt=[i % V], max_new=4) for i in range(20)]
    res = serve_requests(eng, reqs, sim_engine="thread")
    assert len(res) == 20                   # nobody silently dropped
    shed = {r for r, v in res.items()
            if isinstance(v, RequestError) and v.status == "overloaded"}
    served = {r for r, v in res.items() if not isinstance(v, RequestError)}
    assert shed, "expected overload sheds from the full queue"
    assert served, "expected some requests served"
    assert shed | served == set(range(20))
    metrics.check_accounting()
    # every shed was journaled write-ahead: a replay folds it to a verdict
    completed, _ = ServeJournal.replay(jp)
    for rid in shed:
        assert completed[rid][0] == "overloaded", rid


# ---------------------------------------------------------------------------
# journal: overload records
# ---------------------------------------------------------------------------

def test_journal_shed_records_fold_to_verdicts(tmp_path):
    j = ServeJournal(tmp_path / "j.jsonl")
    j.admit(0, [1, 2], 4, None)
    j.tok(0, 3)
    j.shed(1, detail="queue full (8 backlogged)")
    j.retire(0, toks=[3, 4])
    j.shed(2, detail="deadline 0.2s unreachable")
    j.close()
    completed, inflight = ServeJournal.replay(tmp_path / "j.jsonl")
    assert completed[0] == [3, 4]
    assert completed[1] == ("overloaded", "queue full (8 backlogged)")
    assert completed[2] == ("overloaded", "deadline 0.2s unreachable")
    assert not inflight


def test_journal_shed_then_restart_never_readmits(tmp_path):
    """Crash-restart exactly-once for sheds: a rid shed before the crash
    answers from the journal on replay — it must not be recomputed or
    re-admitted even though capacity is now free."""
    jp = tmp_path / "j.jsonl"
    j = ServeJournal(jp)
    j.shed(1, detail="queue full")
    j.close()
    scfg = ServeConfig(batch_slots=2, max_seq=32, prefill_buckets=(8,))
    reqs = [Request(rid=i, prompt=[i + 1], max_new=2) for i in range(3)]
    res = serve_requests(_toy_engine(scfg, journal=jp), reqs)
    assert res[0] == [2, 3] and res[2] == [4, 5]
    assert isinstance(res[1], RequestError)
    assert res[1].status == "overloaded" and "queue full" in res[1].detail
    # the replayed verdict is not re-journaled as new work
    completed, inflight = ServeJournal.replay(jp)
    assert completed[1] == ("overloaded", "queue full") and not inflight


def test_controller_replays_journaled_shed_verdict(tmp_path):
    jp = tmp_path / "j.jsonl"
    j = ServeJournal(jp)
    j.shed(5, detail="dropped for newer arrival 9")
    j.retire(6, toks=[1, 2])
    j.close()
    metrics = ServeMetrics(clock=lambda: 0.0)
    ctrl = AdmissionController(AdmissionConfig(), journal=ServeJournal(jp),
                               metrics=metrics, clock=lambda: 0.0)
    v5 = ctrl.offer(_req(5, "t0"))
    assert v5 == ("replayed", ("overloaded", "dropped for newer arrival 9"))
    v6 = ctrl.offer(_req(6, "t0"))
    assert v6 == ("replayed", [1, 2])
    metrics.check_accounting()              # replays keep the invariant


def test_journal_torn_tail_with_interleaved_overload_records(tmp_path):
    p = tmp_path / "j.jsonl"
    j = ServeJournal(p)
    j.admit(0, [1], 3, None)
    j.shed(1, detail="reject-new")
    j.tok(0, 2)
    j.admit(2, [5], 2, None)
    j.retire(0, toks=[2, 3, 4])
    j.close()
    with open(p, "a") as f:
        f.write('{"t":"shed","rid":2,"de')   # crash mid-append
    completed, inflight = ServeJournal.replay(p)
    assert completed[0] == [2, 3, 4]
    assert completed[1] == ("overloaded", "reject-new")
    assert inflight[2]["toks"] == []         # torn shed dropped: still live
    j2 = ServeJournal(p)                     # reopen repairs the tail
    j2.shed(2, detail="re-shed after restart")
    j2.close()
    completed, inflight = ServeJournal.replay(p)
    assert completed[2] == ("overloaded", "re-shed after restart")
    assert not inflight


# ---------------------------------------------------------------------------
# end-to-end: deterministic virtual-time overload runs
# ---------------------------------------------------------------------------

def test_virtual_overload_accounting_and_total_answers():
    eng, trace, metrics = _virtual_setup()
    res = serve_requests(eng, trace)
    assert len(res) == len(trace)           # no silent absence, ever
    metrics.check_accounting()
    summ = metrics.summary()
    assert summ["offered"] == len(trace)
    assert summ["shed"] > 0, "overload run should shed"
    assert summ["admitted"] + summ["shed"] == summ["offered"]
    for r in trace:
        v = res[r.rid]
        assert isinstance(v, (list, RequestError)), r.rid


def test_virtual_overload_is_deterministic_in_process():
    runs = []
    for _ in range(2):
        eng, trace, metrics = _virtual_setup()
        res = serve_requests(eng, trace)
        runs.append((sorted(res.items(), key=lambda kv: kv[0]).__repr__(),
                     metrics.summary()))
    assert runs[0][0] == runs[1][0]
    assert runs[0][1] == runs[1][1]


def test_virtual_sheds_respect_priority_classes():
    """Noisy neighbor under drop-oldest: a full queue evicts from the
    lowest-priority backlogged tenant, so the flooder absorbs the
    shedding and the interactive victim keeps a materially higher admit
    rate (reject-new would shed whoever happens to arrive)."""
    vc = VirtualClock()
    metrics = ServeMetrics()
    ctrl = AdmissionController(AdmissionConfig(shed_policy="drop-oldest",
                                               est_token_s=0.02,
                                               queue_limit=6))
    scfg = ServeConfig(batch_slots=2, max_seq=64, prefill_buckets=(8,))
    eng = _toy_engine(scfg, admission=ctrl, metrics=metrics, clock=vc,
                      pace="virtual", step_dt=0.02)
    mix = noisy_neighbor_mix(victim_rate=4.0, flood_rate=40.0,
                             deadline_s=1.0)
    ctrl.register_tenants(mix)
    trace = make_trace(mix, 3.0, seed=SEED, vocab=V)
    res = serve_requests(eng, trace)
    assert len(res) == len(trace)
    metrics.check_accounting()
    t = metrics.summary()["tenants"]
    v_admit = t["victim"]["admitted"] / max(1, t["victim"]["offered"])
    f_admit = t["flood"]["admitted"] / max(1, t["flood"]["offered"])
    assert t["flood"]["shed"] > 0
    assert v_admit > f_admit + 0.2, (v_admit, f_admit)


@pytest.mark.parametrize("seed", [SEED, SEED + 1])
def test_shed_off_collapses_where_shed_on_holds(seed):
    """The benchmark's collapse arm, asserted in simulated time: the same
    supersaturated trace violates deadlines without admission control,
    while with shedding every admitted request's TTFT stays inside the
    deadline (the infeasible ones were shed up front)."""
    deadline = 0.3
    kw = dict(duration=1.5, rate=30.0, deadline_s=deadline, seed=seed,
              step_dt=0.02, slots=2)
    eng_off, trace, m_off = _virtual_setup(shed=False, **kw)
    res_off = serve_requests(eng_off, trace)
    late = [v for v in res_off.values()
            if isinstance(v, RequestError) and v.status == "deadline"]
    assert late, "shed-off arm must blow deadlines"
    assert m_off.deadline_violations == len(late)

    eng_on, trace_on, m_on = _virtual_setup(shed=True, **kw)
    assert trace_digest(trace_on) == trace_digest(trace)
    res_on = serve_requests(eng_on, trace_on)
    assert len(res_on) == len(trace_on)
    m_on.check_accounting()
    summ = m_on.summary()
    assert summ["shed"] > 0
    assert summ["deadline_violations"] < len(late)
    if summ["ttft_p99_s"] is not None:
        assert summ["ttft_p99_s"] <= deadline


_REPLAY_PROC = r"""
import sys
from repro.serve import (AdmissionConfig, AdmissionController, ServeConfig,
                         ServeMetrics, VirtualClock, make_trace,
                         serve_requests, uniform_mix)
import numpy as np
from repro.serve import ServingEngine

V = 16
def prefill(toks):
    last = int(toks[0, -1]) % V
    return np.eye(1, V, k=(last + 1) % V), {"n": toks.shape[1]}
def decode(tok, cache):
    return np.eye(1, V, k=int(tok[0] + 1) % V), {"n": cache["n"] + 1}

seed, path = int(sys.argv[1]), sys.argv[2]
vc = VirtualClock()
ctrl = AdmissionController(AdmissionConfig(est_token_s=0.01, queue_limit=8))
mix = uniform_mix(2, rate=35.0, deadline_s=0.4, max_new=(4, 8),
                  prompt_len=(2, 6))
ctrl.register_tenants(mix)
eng = ServingEngine(ServeConfig(batch_slots=2, max_seq=64,
                                prefill_buckets=(8,)),
                    prefill, decode, admission=ctrl, journal=path,
                    metrics=ServeMetrics(), clock=vc, pace="virtual",
                    step_dt=0.01)
trace = make_trace(mix, 2.0, seed=seed, vocab=V)
res = serve_requests(eng, trace)
assert len(res) == len(trace)
eng.journal.close()
"""


def test_overload_journal_is_byte_identical_across_processes(tmp_path):
    """The replay contract end-to-end: two processes running the same
    seeded overload trace under virtual time write byte-identical
    admit/shed/tok/retire journals."""
    digests = []
    for run in ("a", "b"):
        jp = tmp_path / f"{run}.jsonl"
        r = subprocess.run(
            [sys.executable, "-c", _REPLAY_PROC, str(SEED), str(jp)],
            capture_output=True, text=True, timeout=300,
            env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
                 "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
        assert r.returncode == 0, r.stderr[-3000:]
        blob = jp.read_bytes()
        assert b'"shed"' in blob            # the run actually shed
        digests.append(hashlib.sha256(blob).hexdigest())
    assert digests[0] == digests[1]
