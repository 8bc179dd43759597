"""Serving engine: continuous batching built on TAPA channels.

This subsystem uses the paper's two motivating APIs *as motivated*:

* **Transactions (EoT)** — one request's prompt tokens form one transaction
  on the request channel: the frontend writes the tokens then ``close()``s;
  the scheduler drains the stream until EoT.  Variable-length prompts need
  no length header and no sentinel values inside the token domain (paper
  Listing 2's exact argument).

* **Peek** — the admission scheduler ``peek``s the request channel to see
  the *next* request's header without consuming it, admitting it only if a
  batch slot is free — the network-switch pattern from the paper's
  introduction (forward based on content *and* availability, no manual
  buffer-and-state-machine).

Two decode paths share the scheduler:

* **Batched fast path** (``batched=`` a :class:`~repro.models.lm.
  ServingAdapter`): ONE jitted decode step per iteration regardless of
  live slot count.  All slots live in one packed KV cache ``[.., slots,
  ..]`` with a per-slot ``len`` vector; admission runs bucketed batched
  prefill and writes rows into slots (donated buffers, in-place under
  XLA); retirement zeroes ``len``; sampling happens on device so the host
  fetches one ``[slots]`` int32 array per step.  Every shape resolves
  through the persistent compile cache, so a warm process pays zero XLA
  compiles (see ``warmup``).

* **Per-slot fallback** (``prefill_fn``/``decode_fn`` closures): the seed
  path — one call per live slot per token, host argmax.  Kept for toy
  engines, recurrent families (whose prefill cannot pad), and as the
  baseline that ``benchmarks/serve_time.py`` measures the fast path
  against.

See docs/serving.md for the packed-cache layout and bucket policy, and
docs/robustness.md for the failure model.

Robustness (chaos-harness contract)
-----------------------------------

A serving process must degrade, not crash.  The failure surface and the
response to each, from least to most severe:

* **Transient step failure** — :class:`~repro.core.errors.TransientFault`
  (injected by the chaos harness before the step executes): retried with
  exponential backoff up to ``ServeConfig.max_retries`` times; retries are
  recorded in :attr:`ServingEngine.retry_log`.
* **Poisoned request** — :class:`~repro.core.errors.PoisonError` raised
  *before* the step function runs (donated buffers untouched): only the
  poisoned request is quarantined — it gets a :class:`RequestError` result
  and its slot is retired; everything else keeps decoding.
* **Per-slot deadline / cancellation** — a request past its
  ``deadline_s`` or cancelled by the fault plan is retired with a
  structured :class:`RequestError`; its partial output is dropped, its
  slot freed.
* **Unattributable batched failure** — the one jitted step covers every
  slot and donates the packed cache, so a real exception from inside it
  cannot be pinned on one request: every live request gets a
  :class:`RequestError` and the packed cache is rebuilt from scratch.
* **Batched path unavailable** — warmup or the pre-flight step
  resolution fails: the scheduler degrades to the per-slot path when the
  closures exist (the ladder is batched -> per-slot -> refuse).
* **Preemption** — a ``stop_flag`` (wired to
  :class:`~repro.ft.PreemptionGuard` by ``launch/serve.py``) makes the
  scheduler reject all queued/future admissions with ``"preempted"``
  errors, finish the in-flight slots, flush results and exit clean.

Overload (PR 8)
---------------

Sustained offered load above capacity is handled *before* compute is
spent on it (docs/serving.md, Overload section):

* an :class:`~repro.serve.admission.AdmissionController` (``admission=``)
  fronts the request channel with per-tenant fair queuing and cost-aware
  load shedding; every shed is a journaled, structured
  ``RequestError("overloaded", retry_after_s=...)`` deposited straight
  into ``results`` — the frontend never blocks indefinitely;
* without a controller, ``ServeConfig.admit_timeout_s`` bounds how long
  the direct frontend waits on a full request channel before failing
  fast the same way (thread engine; cooperative engines hand off);
* a :class:`~repro.serve.admission.CircuitBreaker` (``breaker=``) gates
  every ``_call_step``: consecutive step failures open it, further calls
  fast-fail with ``"overloaded"`` results while open, a half-open probe
  closes it again;
* traffic-paced runs (requests carrying ``t_arrival``, from
  ``serve/traffic.py``) run in one of two pacing modes: ``pace="wall"``
  sleeps to real arrival times under the thread engine, while
  ``pace="virtual"`` couples a :class:`~repro.serve.traffic.VirtualClock`
  to the decode loop through a capacity-1 tick channel — the scheduler
  advances time by ``step_dt`` per step and the frontend blocks on ticks
  until the next arrival is due, so the whole overload run (arrivals,
  queue dynamics, sheds, deadline violations) is a deterministic
  function of (traffic seed, fault seed, config).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import channel, task
from ..core.engines import ENGINES
from ..core.errors import PoisonError, TransientFault


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list          # token ids
    max_new: int = 8
    deadline_s: Optional[float] = None   # latency budget (see t_arrival)
    tenant: str = "default"              # fair-queuing / metrics key
    # arrival timestamp (trace-relative seconds) set by serve/traffic.py;
    # when present, deadlines anchor at arrival (queueing time counts),
    # otherwise at slot admission (the pre-PR8 behaviour)
    t_arrival: Optional[float] = None


@dataclasses.dataclass
class RequestError:
    """Structured failure result for one request (collector value).

    ``status`` is one of ``"poisoned"``, ``"deadline"``, ``"cancelled"``,
    ``"preempted"``, ``"overloaded"``, ``"error"``; ``detail`` is
    human-readable context.  ``retry_after_s`` is set on overload sheds
    and breaker fast-fails: the client's backoff hint.  A request either
    yields a token list or a RequestError — never a silent absence from
    ``results``.
    """
    rid: int
    status: str
    detail: str = ""
    retry_after_s: Optional[float] = None


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 4          # concurrent decode slots
    max_seq: int = 128
    eos_token: int = -1           # -1: only stop on max_new
    prefill_buckets: tuple = ()   # () = powers of two from 8 to max_seq
    queue_cap: int = 16           # bounded admission queue (channel capacity)
    max_retries: int = 2          # per step-call retry budget (transients)
    retry_base_s: float = 0.0     # exponential-backoff base (0: no sleep)
    retry_max_s: float = 1.0      # cap on TOTAL backoff per step call
    # direct-frontend bound on waiting for a full request channel before
    # failing fast with "overloaded" (None: block, the seed behaviour).
    # Honoured under the preemptive thread engine; cooperative engines
    # hand off on the blocking write instead.
    admit_timeout_s: Optional[float] = None


def _default_buckets(max_seq: int) -> tuple:
    out, b = [], 8
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)


def _pow2_at_least(n: int, cap: int) -> int:
    b = 1
    while b < n and b < cap:
        b *= 2
    return min(b, cap)


class ServingEngine:
    """Continuous-batching engine over a model's serving step functions.

    Per-slot mode: ``prefill_fn(tokens[B,S]) -> (logits[B,V], cache)`` and
    ``decode_fn(token[B], cache) -> (logits[B,V], cache)`` — typically the
    jit'd model steps; tests may pass toy closures.

    Batched mode: pass ``batched=lm.serving_adapter(...)`` instead; the
    step functions are compiled through the persistent compile cache and
    the decode loop runs one jitted call per step for all slots.
    """

    def __init__(self, scfg: ServeConfig, prefill_fn: Callable = None,
                 decode_fn: Callable = None, pad_token: int = 0,
                 batched: Any = None, faults: Any = None,
                 stop_flag: Callable = None, journal: Any = None,
                 admission: Any = None, metrics: Any = None,
                 breaker: Any = None, clock: Callable = None,
                 pace: Optional[str] = None, step_dt: float = 0.0):
        self.scfg = scfg
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.pad = pad_token
        self.batched = batched
        if batched is None and (prefill_fn is None or decode_fn is None):
            raise ValueError("need prefill_fn/decode_fn or batched=adapter")
        # chaos harness (repro.core.faults): poisoned/cancelled requests and
        # transient step failures; None in normal operation
        if faults is not None and not hasattr(faults, "serving_check"):
            faults = faults.injector()
        self.faults = faults
        # preemption: callable polled once per scheduler iteration; True ->
        # reject queued admissions, finish live slots, exit clean
        self.stop_flag = stop_flag
        # write-ahead serving journal (repro.serve.journal.ServeJournal or
        # a path): admission/token/retire records, fsync'd before the
        # corresponding effect is externally visible.  A restarted process
        # answers already-retired rids straight from the journal and
        # re-admits in-flight rids at their last journaled position —
        # exactly-once results across SIGKILL (docs/robustness.md).
        if journal is not None and not hasattr(journal, "retire"):
            from .journal import ServeJournal
            journal = ServeJournal(journal)
        self.journal = journal
        # -- overload layer (PR 8) ----------------------------------------
        # one clock for the whole stack: time.perf_counter in production,
        # a traffic.VirtualClock for deterministic simulated-time runs
        self.clock = clock or time.perf_counter
        # pacing for traffic-timed runs: None (legacy frontend), "wall"
        # (sleep to real arrival times, thread engine), or "virtual"
        # (tick-channel coupling, cooperative engines)
        if pace not in (None, "wall", "virtual"):
            raise ValueError(f"unknown pace {pace!r}")
        self.pace = pace
        self.step_dt = step_dt             # simulated seconds per decode step
        self.metrics = metrics             # admission.ServeMetrics or None
        if metrics is not None:
            metrics.clock = self.clock
        self.admission = admission         # admission.AdmissionController
        if admission is not None:
            if admission.journal is None:
                admission.journal = self.journal
            if admission.metrics is None:
                admission.metrics = self.metrics
            admission.clock = self.clock
        self.breaker = breaker             # admission.CircuitBreaker
        self.retry_log: list = []          # (site, attempt, error) tuples
        self.degraded: Optional[tuple] = None   # ("per-slot", reason) or None
        self._aot_prefill: dict = {}       # (B, S) -> executable
        self._aot_decode: Optional[tuple] = None   # (aval sig, executable)
        # batched mode: executables by shape key + where each came from
        self._exe: dict = {}
        self._cc = None
        self.compile_log: list = []        # (kind, shape, source) tuples

    def buckets(self) -> tuple:
        return self.scfg.prefill_buckets or _default_buckets(
            self.scfg.max_seq)

    # -- warmup through the persistent compile cache --------------------------

    def warmup(self, prompt_len: int = 8, cache=None,
               batch_sizes: tuple = (1,)) -> dict:
        """AOT-compile the serving steps through the compile cache.

        The first request a serving process sees should not pay an XLA
        compile: warmup resolves the steps from the content-addressed
        store (populated by any previous process running the same model
        and shapes) and pins the executables for the decode loop.

        Batched mode resolves one prefill executable per (batch-size,
        bucket) — ``batch_sizes`` x ``buckets()`` — plus the packed decode
        step, and reports the source of each (``compiled`` vs ``memory``/
        ``disk``).  Per-slot mode keeps the seed behaviour: a single
        ``(1, prompt_len)`` prefill plus the decode signature probe, and
        toy engines whose step functions are not jittable fall back to
        eager with ``{"ok": False}`` — warmup never breaks per-slot
        serving.  A batched adapter has no eager path: ``{"ok": False}``
        there means serving itself would fail the same way
        (``launch/serve.py`` stops with that reason).
        """
        from ..core.compile_cache import aval_signature, default_cache
        cc = cache if cache is not None else default_cache()
        if self.batched is not None:
            rep = self._warmup_batched(cc, batch_sizes)
            if rep.get("ok") or self.prefill_fn is None \
                    or self.decode_fn is None:
                return rep
            # degradation ladder: batched -> per-slot, for an engine built
            # with BOTH the adapter and the closures
            self.degraded = ("per-slot", rep.get("reason", ""))
            self.batched = None
        toks = np.zeros((1, prompt_len), np.int32)
        try:
            pre, src_p = cc.compile_cached(self.prefill_fn, (toks,),
                                           extra=self._key_salt())
            _, kv = pre(toks)
            tok = np.zeros((1,), np.int32)
            dec, src_d = cc.compile_cached(self.decode_fn, (tok, kv),
                                           extra=self._key_salt())
        except Exception as e:  # noqa: BLE001 - non-jittable step fns
            return {"ok": False, "reason": repr(e)[:200]}
        self._aot_prefill[(1, prompt_len)] = pre
        self._aot_decode = (aval_signature((tok, kv), {}), dec)
        return {"ok": True, "prefill": src_p, "decode": src_d}

    def _warmup_batched(self, cc, batch_sizes: tuple) -> dict:
        self._cc = cc
        report: dict = {"ok": True, "buckets": {}, "decode": None}
        try:
            for L in self.buckets():
                for bk in batch_sizes:
                    _, src = self._resolve_prefill(bk, L)
                    report["buckets"][f"{bk}x{L}"] = src
            _, src = self._resolve_step()
            report["decode"] = src
            # the small slot-maintenance executables, so the first
            # admission wave pays zero compiles of any size
            for bk in batch_sizes:
                self._resolve_write(bk)
            self._resolve_retire()
        except Exception as e:  # noqa: BLE001 - keep serving alive
            return {"ok": False, "reason": repr(e)[:200]}
        return report

    # -- batched-mode executable resolution -----------------------------------

    def _cache(self):
        if self._cc is None:
            from ..core.compile_cache import default_cache
            self._cc = default_cache()
        return self._cc

    @staticmethod
    def _key_salt():
        """Env-selected kernel dispatch is baked into the traced decode
        program (kernels/ops.decode_attention), so it must be part of the
        cache key for every serving executable — and only for those, so
        flipping it never invalidates unrelated cache entries."""
        import os
        return ("decode-attn", os.environ.get("REPRO_DECODE_ATTN", ""))

    def _resolve_prefill(self, bk: int, L: int):
        """Executable for the (bk, L) prefill bucket, via the compile
        cache (disk hit in a warm process, one XLA compile otherwise)."""
        key = ("prefill", bk, L)
        if key in self._exe:
            return self._exe[key], "pinned"
        sds = jax.ShapeDtypeStruct
        args = (self.batched.params, sds((bk, L), jnp.int32),
                sds((bk,), jnp.int32), sds((), jnp.int32))
        exe, src = self._cache().compile_cached(self.batched.prefill_fn,
                                                args,
                                                extra=self._key_salt())
        self._exe[key] = exe
        self.compile_log.append(("prefill", (bk, L), src))
        return exe, src

    def _resolve_step(self):
        key = ("step",)
        if key in self._exe:
            return self._exe[key], "pinned"
        sds = jax.ShapeDtypeStruct
        slots = self.scfg.batch_slots
        packed = self.batched.init_slots(slots, abstract=True)
        args = (self.batched.params, sds((slots,), jnp.int32), packed,
                sds((), jnp.int32))
        exe, src = self._cache().compile_cached(
            self.batched.step_fn, args, extra=self._key_salt(),
            jit_kwargs={"donate_argnums": (2,)})
        self._exe[key] = exe
        self.compile_log.append(("decode_step", (slots,), src))
        return exe, src

    def _resolve_write(self, bk: int):
        key = ("write", bk)
        if key in self._exe:
            return self._exe[key]
        sds = jax.ShapeDtypeStruct
        slots = self.scfg.batch_slots
        packed = self.batched.init_slots(slots, abstract=True)
        cache = jax.eval_shape(
            lambda p, t, n: self.batched.prefill_fn(p, t, n,
                                                    jnp.int32(0))[1],
            self.batched.params, sds((bk, self.scfg.max_seq), jnp.int32),
            sds((bk,), jnp.int32))
        args = (packed, cache, sds((), jnp.int32), sds((), jnp.int32))
        exe, src = self._cache().compile_cached(
            self.batched.write_slot_fn, args,
            jit_kwargs={"donate_argnums": (0,)})
        self._exe[key] = exe
        self.compile_log.append(("write_slot", (bk,), src))
        return exe

    def _resolve_retire(self):
        key = ("retire",)
        if key in self._exe:
            return self._exe[key]
        sds = jax.ShapeDtypeStruct
        packed = self.batched.init_slots(self.scfg.batch_slots,
                                         abstract=True)
        exe, src = self._cache().compile_cached(
            self.batched.retire_fn, (packed, sds((), jnp.int32)),
            jit_kwargs={"donate_argnums": (0,)})
        self._exe[key] = exe
        self.compile_log.append(("retire", (), src))
        return exe

    # -- task bodies ---------------------------------------------------------

    def _write_req(self, req_out, r) -> None:
        """One request as one EoT-delimited transaction:
        [hdr(rid, max_new, deadline, tenant, t_arr), tok0, ...] <EoT>."""
        req_out.write(("hdr", r.rid, r.max_new,
                       getattr(r, "deadline_s", None),
                       getattr(r, "tenant", "default"),
                       getattr(r, "t_arrival", None)))
        req_out.write_burst([("tok", t) for t in r.prompt])
        req_out.close()

    def _offer_direct(self, req_out, r, results) -> bool:
        """Write one request transaction, failing fast on a full channel.

        With ``admit_timeout_s`` unset this is the seed behaviour: the
        write blocks until the scheduler drains (a cooperative hand-off
        under run-to-block engines).  With it set and the channel full,
        the frontend waits at most that long, then sheds the request with
        a journaled ``RequestError("overloaded")`` instead of blocking
        the producer indefinitely.  Returns True iff the request was
        written."""
        tmo = self.scfg.admit_timeout_s
        if tmo is not None and results is not None and req_out.full():
            give_up = time.monotonic() + tmo
            while req_out.full() and time.monotonic() < give_up:
                time.sleep(min(0.002, max(tmo * 0.25, 1e-4)))
            if req_out.full():
                detail = (f"request queue full "
                          f"(cap {self.scfg.queue_cap}) for {tmo}s")
                if self.journal is not None:
                    self.journal.shed(r.rid, detail=detail)
                if self.metrics is not None:
                    self.metrics.note_shed(
                        getattr(r, "tenant", "default"), "queue-full")
                results[r.rid] = RequestError(r.rid, "overloaded", detail,
                                              retry_after_s=tmo)
                return False
        self._write_req(req_out, r)
        return True

    def frontend(self, requests: list, req_out, results: dict = None) -> None:
        """Direct (un-paced) frontend: requests are offered back-to-back."""
        for r in requests:
            if self.metrics is not None:
                self.metrics.note_offered(getattr(r, "tenant", "default"))
            if self._offer_direct(req_out, r, results) \
                    and self.metrics is not None:
                self.metrics.note_admitted(getattr(r, "tenant", "default"))
        # final empty transaction marks shutdown
        req_out.close()

    # -- traffic-paced frontend (overload path) --------------------------------

    def _deliver(self, results: dict, rid: int, done) -> None:
        """Deposit a journal-replayed result (exactly-once, no recompute)."""
        if isinstance(done, tuple):
            results[rid] = RequestError(rid, done[0], done[1])
        else:
            results[rid] = list(done)

    @staticmethod
    def _drain_ticks(tick_in) -> None:
        """Consume pending ticks before a potentially-blocking request
        write.  This is the virtual-pacing deadlock guard: it guarantees
        an idle scheduler's blocking tick write (:meth:`_timed_idle`) has
        space to complete, so the scheduler is always runnable to consume
        whatever the frontend is about to write."""
        if tick_in is not None:
            while tick_in.try_read()[0]:
                pass

    def _pump(self, req_out, results: dict, tick_in=None,
              drain: bool = False) -> None:
        """Move dispatchable requests from the admission controller into
        the request channel, in fair-queue order.  Normally stops at a
        full channel (the backlog stays in the controller where it can
        still be shed); ``drain=True`` pushes everything through with
        blocking writes (end of trace — the scheduler is consuming)."""
        ctrl = self.admission
        if ctrl is None:
            return
        while True:
            for e in ctrl.drain_errors():      # dispatch-time sheds
                results[e.rid] = e
            if not drain and req_out.full():
                return
            r = ctrl.pop()
            if r is None:
                break
            self._drain_ticks(tick_in)
            self._write_req(req_out, r)
        for e in ctrl.drain_errors():
            results[e.rid] = e

    def _offer_timed(self, r, req_out, results: dict, tick_in=None) -> None:
        ctrl = self.admission
        if ctrl is None:
            if self.metrics is not None:
                self.metrics.note_offered(r.tenant)
            self._drain_ticks(tick_in)
            if self._offer_direct(req_out, r, results) \
                    and self.metrics is not None:
                self.metrics.note_admitted(r.tenant)
            return
        verdict = ctrl.offer(r)
        if verdict is None:
            return                             # queued; _pump dispatches
        if isinstance(verdict, RequestError):
            results[verdict.rid] = verdict     # shed at offer
        else:                                  # ("replayed", done)
            self._deliver(results, r.rid, verdict[1])

    def traffic_frontend(self, trace: list, req_out, tick_in,
                         results: dict) -> None:
        """Open-loop frontend: release each request at its ``t_arrival``.

        Wall pacing sleeps to real arrival times (thread engine).
        Virtual pacing blocks on the tick channel until the scheduler —
        which advances the shared VirtualClock by ``step_dt`` per decode
        step, or fast-forwards to ``clock.next_event`` when idle — has
        moved simulated time past the next arrival.  Arrival timestamps
        are rebased onto the engine clock (``t_start``), so deadlines and
        TTFT anchor at *arrival*, queueing time included.
        """
        virtual = self.pace == "virtual" and tick_in is not None
        t_start = self.clock()
        for r in trace:
            t_abs = t_start + (r.t_arrival or 0.0)
            if virtual:
                self.clock.next_event = t_abs
                while self.clock() < t_abs:
                    tick_in.read()             # cooperative hand-off
                self.clock.next_event = None
            else:
                wait = t_abs - self.clock()
                if wait > 0:
                    time.sleep(wait)
            self._offer_timed(dataclasses.replace(r, t_arrival=t_abs),
                              req_out, results, tick_in)
            self._pump(req_out, results, tick_in)
        self._pump(req_out, results, tick_in, drain=True)
        self._drain_ticks(tick_in)             # unblock a mid-write scheduler
        req_out.close()                        # shutdown transaction
        self._drain_ticks(tick_in)

    # -- admission (shared by both paths) -------------------------------------

    def _admit_one(self, req_in, can_wait: bool):
        """Try to consume one whole request transaction.

        The caller guarantees a free slot, so admission is the paper's
        switch pattern: ``peek`` the header to inspect the pending request,
        then consume it — the peeked value IS the header (no double read).
        Returns ``("req", rid, max_new, prompt)``, ``("shutdown",)``, or
        ``("none",)`` when nothing is pending and ``can_wait`` is False.

        With ``can_wait=True`` (no live slot, nothing else to do) this
        *blocks* on the channel — a cooperative engine hand-off, not a
        busy poll of ``try_*`` in a spin loop.
        """
        avail, is_eot = req_in.try_eot()
        if not avail:
            if not can_wait:
                return ("none",)
            is_eot = req_in.eot()          # block until the next transaction
        if is_eot:                          # empty transaction = shutdown
            req_in.open()
            return ("shutdown",)
        kind, rid, max_new, deadline, tenant, t_arr = req_in.peek()
        assert kind == "hdr", kind
        req_in.read()                       # consume the peeked header
        prompt = [t for (_, t) in req_in.read_transaction()]
        # normalize: empty prompts decode from a single pad token; overlong
        # prompts keep their most recent max_seq-1 tokens so one decode
        # position remains
        prompt = (prompt or [self.pad])[-(self.scfg.max_seq - 1):]
        return ("req", rid, max_new, prompt, deadline, tenant, t_arr)

    def _emit(self, out_chan, rid: int, new: list, slot: dict = None) -> None:
        if self.metrics is not None and slot is not None:
            self.metrics.note_done(slot.get("tenant", "default"),
                                   slot.get("t_arr"), slot.get("t_first"),
                                   len(new))
        if self.journal is not None:
            # write-ahead: the retire record hits disk before the result
            # transaction exists, so a crash in between re-delivers from
            # the journal instead of losing the finished request
            self.journal.retire(rid, toks=[int(t) for t in new])
        out_chan.write(("hdr", rid))
        out_chan.write_burst([("tok", int(t)) for t in new])
        out_chan.close()

    def _emit_err(self, out_chan, rid: int, status: str,
                  detail: str = "", slot: dict = None,
                  retry_after: Optional[float] = None) -> None:
        """One error transaction; the collector turns it into a
        :class:`RequestError` result."""
        if retry_after is None and status == "overloaded" \
                and self.breaker is not None:
            retry_after = self.breaker.retry_after()   # client backoff hint
        if self.metrics is not None and slot is not None:
            self.metrics.note_failed(slot.get("tenant", "default"), status)
        if self.journal is not None:
            self.journal.retire(rid, status=status, detail=detail)
        out_chan.write(("err", rid, status, detail, retry_after))
        out_chan.close()

    def _note_tok(self, s: dict, t: int) -> None:
        """Append one emitted token to a slot, journaling it first — the
        single funnel for every token either decode path produces."""
        if "t_first" not in s:
            s["t_first"] = self.clock()    # TTFT stamp (first real token)
        if self.journal is not None:
            self.journal.tok(s["rid"], t)
        s["new"].append(t)

    # -- hardening helpers -----------------------------------------------------

    def _backoff(self, attempt: int, slept: float, slots) -> float:
        """One retry backoff sleep; returns the seconds actually slept.

        The exponential term is clamped two ways: ``retry_max_s`` caps
        the *total* backoff for one step call (the seed's uncapped
        ``base * 2**attempt`` could stall the whole batched decode loop),
        and no sleep ever extends past the earliest remaining deadline
        among the live slots — backing off for one slot's transient must
        not blow every neighbour's budget."""
        dt = self.scfg.retry_base_s * 2 ** attempt
        dt = min(dt, max(0.0, self.scfg.retry_max_s - slept))
        if slots:
            now = self.clock()
            for s in slots:
                if s is None or s.get("deadline") is None:
                    continue
                anchor = s["t_arr"] if s.get("t_arr") is not None else s["t0"]
                dt = min(dt, max(0.0, s["deadline"] - (now - anchor)))
        if dt > 0:
            time.sleep(dt)
        return dt

    def _call_step(self, site: str, rids: list, fn, *args, slots=None):
        """Run one step function under the serving fault contract.

        Consults the circuit breaker and the fault injector *before*
        ``fn`` executes, so :class:`~repro.serve.admission.BreakerOpen`
        (fast-fail while the backend is suspect), :class:`PoisonError`
        (re-raised for the caller to quarantine) and
        :class:`TransientFault` (retried here with capped,
        deadline-aware backoff) all fire while any donated buffers in
        ``args`` are still valid.  Only *final* step outcomes reach the
        breaker: a retried transient that eventually succeeds counts as
        success.
        """
        if self.breaker is not None:
            self.breaker.check()           # may raise BreakerOpen
        slept = 0.0
        for attempt in range(self.scfg.max_retries + 1):
            try:
                if self.faults is not None:
                    self.faults.serving_check(site, rids)
                out = fn(*args)
            except PoisonError:
                raise                      # per-request, not a backend fault
            except TransientFault as e:
                self.retry_log.append((site, attempt, repr(e)))
                if attempt >= self.scfg.max_retries:
                    if self.breaker is not None:
                        self.breaker.failure(repr(e))
                    raise
                if self.scfg.retry_base_s > 0:
                    slept += self._backoff(attempt, slept, slots)
                continue
            except Exception as e:  # noqa: BLE001 - real backend failure
                if self.breaker is not None:
                    self.breaker.failure(repr(e))
                raise
            if self.breaker is not None:
                self.breaker.success()
            return out

    def _abnormal(self, s: dict) -> Optional[tuple]:
        """(status, detail) if the slot must be retired abnormally."""
        err = s.get("error")
        if err is not None:
            return err
        dl = s.get("deadline")
        # arrival-anchored when the request carries t_arrival (queueing
        # time counts against the budget), slot-admission-anchored (t0)
        # for legacy requests — the pre-PR8 contract
        anchor = s["t_arr"] if s.get("t_arr") is not None else s["t0"]
        if dl is not None and self.clock() - anchor > dl:
            return ("deadline", f"deadline {dl}s exceeded after "
                                f"{len(s['new'])} tokens")
        if self.faults is not None and \
                self.faults.cancelled(s["rid"], len(s["new"])):
            return ("cancelled", f"cancelled after {len(s['new'])} tokens")
        return None

    def _stop_requested(self) -> bool:
        return self.stop_flag is not None and bool(self.stop_flag())

    def _drain_reject(self, req_in, out_chan) -> None:
        """Preemption path: consume every queued/future request transaction
        up to the frontend's shutdown marker, answering each with a
        ``"preempted"`` error — the frontend never blocks on a full channel
        and the collector still sees one result per request."""
        while True:
            r = self._admit_one(req_in, can_wait=True)
            if r[0] == "shutdown":
                return
            if r[0] == "none":      # unreachable with can_wait=True
                continue
            self._emit_err(out_chan, r[1], "preempted",
                           "serving preempted; request rejected")

    def _finished(self, s: dict) -> bool:
        if len(s["new"]) >= s["max_new"]:
            return True
        eos = self.scfg.eos_token
        if eos >= 0 and s["new"] and s["new"][-1] == eos:
            return True
        # cache-capacity stop: the next decode would scatter at
        # prompt_len + len(new) - 1; retire one step early.  Journal-seeded
        # tokens are counted once — they are part of the re-prefilled
        # prompt AND of ``new`` — so subtract the overlap.
        return s["plen"] + len(s["new"]) - s.get("seeded", 0) \
            >= self.scfg.max_seq

    # -- scheduler -------------------------------------------------------------

    def scheduler(self, req_in, out_chan, tick_out=None) -> None:
        """Admission + continuous batch decode."""
        batched = self.batched is not None
        if batched:
            # pre-flight: resolving the packed decode step is the batched
            # path's single point of no return; if it fails and the
            # per-slot closures exist, degrade instead of dying with the
            # whole request queue unanswered
            try:
                self._resolve_step()
            except Exception as e:  # noqa: BLE001 - degrade, don't crash
                if self.prefill_fn is None or self.decode_fn is None:
                    raise
                self.degraded = ("per-slot", repr(e)[:200])
                batched = False
        if batched:
            self._scheduler_batched(req_in, out_chan, tick_out)
        else:
            self._scheduler_per_slot(req_in, out_chan, tick_out)
        out_chan.close()                   # shutdown transaction

    def _timed_idle(self, tick_out) -> None:
        """Idle under virtual pacing: hand simulated time to the frontend.

        Nothing is decoding, so the only pending event is the frontend's
        next arrival (``clock.next_event``): fast-forward to it and tick.
        The second, *blocking* tick write is the cooperative yield — the
        run-to-block engine switches to the frontend there, which reads
        the tick, sees its arrival due, and writes the next request."""
        clk = self.clock
        ne = getattr(clk, "next_event", None)
        if ne is not None and hasattr(clk, "advance_to"):
            clk.advance_to(ne)
        tick_out.try_write(clk())      # fill the capacity-1 channel...
        tick_out.write(clk())          # ...then block until it drains

    def _after_step(self, tick_out, t_wall0) -> None:
        """Per-decode-step bookkeeping: advance virtual time + tick, and
        feed the measured (or simulated) per-token latency to the
        admission controller's deadline-infeasibility estimator."""
        if tick_out is not None:
            self.clock.advance(self.step_dt)
            tick_out.try_write(self.clock())   # lossy: frontend may lag
            dt = self.step_dt
        else:
            dt = (time.perf_counter() - t_wall0) \
                if t_wall0 is not None else None
        if self.admission is not None and dt:
            self.admission.observe_token_latency(dt)

    def _mk_slot(self, rid, max_new, prompt, deadline,
                 tenant: str = "default", t_arr: Optional[float] = None,
                 seeded: Optional[list] = None) -> dict:
        """One decode-slot record.  ``seeded`` (journal replay) pre-loads
        tokens the crashed process already emitted: they join the prompt
        for the re-prefill — greedy decoding of a causal model then
        continues exactly where the journal left off — and pre-fill
        ``new`` so ``max_new`` / result accounting stay unchanged."""
        seeded = list(seeded or [])
        prompt = (list(prompt) + seeded)[-(self.scfg.max_seq - 1):]
        return {"rid": rid, "prompt": prompt, "plen": len(prompt),
                "max_new": max_new, "new": seeded, "seeded": len(seeded),
                "deadline": deadline, "tenant": tenant, "t_arr": t_arr,
                "t0": self.clock()}

    def _slot_for(self, r, out_chan) -> Optional[dict]:
        """Journal-aware slot construction for one admitted request.

        Returns None when no slot is needed: the rid already retired (its
        result re-emits straight from the journal — never recomputed), or
        the request finishes inline (``max_new <= 0``, or a journal-seeded
        slot that was already at its last token when the process died).
        Fresh rids are journaled *before* any compute happens for them.
        """
        _, rid, max_new, prompt, deadline, tenant, t_arr = r
        j = self.journal
        if j is not None:
            done = j.completed.get(rid)
            if done is not None:
                if isinstance(done, tuple):
                    self._emit_err(out_chan, rid, done[0], done[1])
                else:
                    self._emit(out_chan, rid, done)
                return None
            rec = j.inflight.pop(rid, None)
            if rec is not None:
                s = self._mk_slot(rid, rec["max_new"], rec["prompt"],
                                  rec.get("deadline"), tenant, t_arr,
                                  seeded=rec["toks"])
                if s["new"] and self._finished(s):
                    self._emit(out_chan, rid, s["new"], slot=s)
                    return None
                return s
            j.admit(rid, prompt, max_new, deadline)
        if max_new <= 0:
            self._emit(out_chan, rid, [],
                       slot={"tenant": tenant, "t_arr": t_arr})
            return None
        return self._mk_slot(rid, max_new, prompt, deadline, tenant, t_arr)

    def _scheduler_per_slot(self, req_in, out_chan, tick_out=None) -> None:
        scfg = self.scfg
        coop = tick_out is not None        # virtual pacing (tick coupling)
        slots: list[Optional[dict]] = [None] * scfg.batch_slots
        shutdown = False
        while True:
            if not shutdown and self._stop_requested():
                self._drain_reject(req_in, out_chan)
                shutdown = True
            # Admit while a slot is free; block only when fully idle
            # (under virtual pacing never block here — _timed_idle is the
            # yield point, so the frontend can still advance time).
            while not shutdown:
                free = next((i for i, s in enumerate(slots) if s is None),
                            None)
                if free is None:
                    break
                r = self._admit_one(
                    req_in, can_wait=not coop and not any(
                        s is not None for s in slots))
                if r[0] == "shutdown":
                    shutdown = True
                    break
                if r[0] == "none":
                    break
                s = self._slot_for(r, out_chan)
                if s is not None:
                    slots[free] = s

            live = [s for s in slots if s is not None]
            if not live:
                if shutdown:
                    break
                if coop:
                    self._timed_idle(tick_out)
                continue

            t_wall0 = time.perf_counter() \
                if (self.admission is not None and not coop) else None
            self._step_batch(slots)
            self._after_step(tick_out, t_wall0)

            # retire finished/failed slots (one transaction per request)
            for i, s in enumerate(slots):
                if s is None:
                    continue
                ab = self._abnormal(s)
                if ab is not None:
                    self._emit_err(out_chan, s["rid"], *ab, slot=s)
                    slots[i] = None
                elif self._finished(s):
                    self._emit(out_chan, s["rid"], s["new"], slot=s)
                    slots[i] = None

    def _do_prefill(self, s: dict) -> None:
        toks = np.asarray(s["prompt"], np.int32)[None, :]
        prefill = self._aot_prefill.get(toks.shape, self.prefill_fn)
        logits, cache = prefill(toks)
        s["cache"] = cache
        s["next"] = int(np.argmax(np.asarray(logits)[0]))
        self._note_tok(s, s["next"])
        # decide the AOT-vs-eager decode path once per slot, not
        # per token (the kv signature is fixed after prefill)
        if self._aot_decode is not None:
            from ..core.compile_cache import aval_signature
            sig, exe = self._aot_decode
            tok0 = np.zeros((1,), np.int32)
            s["aot_decode"] = exe if aval_signature(
                (tok0, cache), {}) == sig else None

    def _do_decode(self, s: dict) -> None:
        tok = np.asarray([s["next"]], np.int32)
        decode = s.get("aot_decode") or self.decode_fn
        try:
            logits, s["cache"] = decode(tok, s["cache"])
        except (TypeError, ValueError):
            # a decode_fn that reshapes its cache mid-stream falls off
            # the AOT fast path instead of erroring
            if decode is self.decode_fn:
                raise
            s["aot_decode"] = None
            logits, s["cache"] = self.decode_fn(tok, s["cache"])
        s["next"] = int(np.argmax(np.asarray(logits)[0]))
        self._note_tok(s, s["next"])

    def _step_slot(self, site: str, s: dict, fn) -> None:
        """One per-slot step with quarantine: a failing request marks only
        its own slot (``s["error"]``); neighbours keep decoding."""
        from .admission import BreakerOpen
        try:
            self._call_step(site, [s["rid"]], fn, s, slots=[s])
        except PoisonError as e:
            s["error"] = ("poisoned", str(e))
        except BreakerOpen as e:
            s["error"] = ("overloaded", str(e))
        except Exception as e:  # noqa: BLE001 - incl. exhausted transients
            s["error"] = ("error", repr(e)[:200])

    def _step_batch(self, slots: list) -> None:
        """One prefill-or-decode step over the live slots (per-slot path)."""
        # prefill any slot that has no cache yet
        for s in slots:
            if s is not None and "cache" not in s and "error" not in s:
                self._step_slot("prefill", s, self._do_prefill)
        # decode all live slots, one call per slot (the seed hot loop the
        # batched path replaces)
        for s in slots:
            if s is None or "error" in s or self._finished(s):
                continue
            self._step_slot("decode", s, self._do_decode)

    # -- batched fast path -----------------------------------------------------

    def _scheduler_batched(self, req_in, out_chan, tick_out=None) -> None:
        from .admission import BreakerOpen
        scfg = self.scfg
        coop = tick_out is not None        # virtual pacing (tick coupling)
        n = scfg.batch_slots
        slots: list[Optional[dict]] = [None] * n
        packed = self.batched.init_slots(n)
        step_exe, _ = self._resolve_step()
        retire_exe = self._resolve_retire()
        toks = np.zeros((n,), np.int32)    # reused host-side staging buffer
        shutdown = False
        step_i = 0

        while True:
            if not shutdown and self._stop_requested():
                self._drain_reject(req_in, out_chan)
                shutdown = True
            # -- admission: collect requests for every free slot ----------
            newly = []
            while not shutdown and sum(s is None for s in slots) > len(newly):
                r = self._admit_one(
                    req_in,
                    can_wait=not coop and not newly and not any(
                        s is not None for s in slots))
                if r[0] == "shutdown":
                    shutdown = True
                    break
                if r[0] == "none":
                    break
                s = self._slot_for(r, out_chan)
                if s is not None:
                    newly.append(s)
            if newly:
                packed, step_i = self._prefill_admit(newly, slots, packed,
                                                     step_i, out_chan)
                # a request can finish at prefill (max_new == 1 / eos)
                for i, s in enumerate(slots):
                    if s is not None and self._finished(s):
                        self._emit(out_chan, s["rid"], s["new"], slot=s)
                        packed = retire_exe(packed, np.int32(i))
                        slots[i] = None

            # -- retire deadline-blown / cancelled slots before stepping --
            for i, s in enumerate(slots):
                if s is None:
                    continue
                ab = self._abnormal(s)
                if ab is not None:
                    self._emit_err(out_chan, s["rid"], *ab, slot=s)
                    packed = retire_exe(packed, np.int32(i))
                    slots[i] = None

            if not any(s is not None for s in slots):
                if shutdown:
                    break
                if coop:
                    self._timed_idle(tick_out)
                continue

            # -- ONE jitted decode step for the whole slot array ----------
            toks.fill(0)
            for i, s in enumerate(slots):
                if s is not None:
                    toks[i] = s["next"]
            rids = [s["rid"] for s in slots if s is not None]
            t_wall0 = time.perf_counter() \
                if (self.admission is not None and not coop) else None
            try:
                nxt, packed = self._call_step("decode", rids, step_exe,
                                              self.batched.params, toks,
                                              packed, np.int32(step_i),
                                              slots=slots)
            except PoisonError as e:
                # raised before the step executed, so the donated packed
                # cache is still valid: retire only the poisoned slot
                for i, s in enumerate(slots):
                    if s is not None and s["rid"] == e.rid:
                        self._emit_err(out_chan, e.rid, "poisoned", str(e),
                                       slot=s)
                        packed = retire_exe(packed, np.int32(i))
                        slots[i] = None
                continue
            except BreakerOpen as e:
                # also raised before the step executed (donated cache
                # valid): fast-fail every live request with a structured
                # overload error — no compute is spent while the backend
                # is suspect; the half-open probe will test recovery
                for i, s in enumerate(slots):
                    if s is not None:
                        self._emit_err(out_chan, s["rid"], "overloaded",
                                       str(e), slot=s)
                        packed = retire_exe(packed, np.int32(i))
                        slots[i] = None
                continue
            except Exception as e:  # noqa: BLE001 - unattributable failure
                # the one jitted step covers every slot and donated the
                # packed cache — the failure cannot be pinned on a single
                # request and the cache may be consumed.  Fail all live
                # requests with structured errors and rebuild the cache:
                # the scheduler survives to serve what is still queued.
                for i, s in enumerate(slots):
                    if s is not None:
                        self._emit_err(out_chan, s["rid"], "error",
                                       repr(e)[:200], slot=s)
                        slots[i] = None
                packed = self.batched.init_slots(n)
                continue
            step_i += 1
            self._after_step(tick_out, t_wall0)
            nxt = np.asarray(nxt)   # [slots] — the only per-step transfer

            for i, s in enumerate(slots):
                if s is None:
                    continue
                t = int(nxt[i])
                self._note_tok(s, t)
                s["next"] = t
                if self._finished(s):
                    self._emit(out_chan, s["rid"], s["new"], slot=s)
                    packed = retire_exe(packed, np.int32(i))
                    slots[i] = None

    def _prefill_admit(self, newly: list, slots: list, packed,
                       step_i: int, out_chan):
        """Bucketed batched prefill for a group of admitted requests.

        Prompts are right-padded to the smallest power-of-two bucket and
        same-bucket requests share one prefill call whose batch dimension
        is itself padded to a power of two — so the shape space stays
        bounded and every shape is a compile-cache key.  Returns
        ``(packed, step_i)``: the step counter advances once per prefill
        call so every sampler invocation folds a distinct key.

        A poisoned request is isolated here: it gets an error transaction
        and its group retries without it (PoisonError fires before the
        prefill executes, so nothing is torn).  A real prefill failure
        fails only the group sharing that call, never the whole wave.
        """
        buckets = self.buckets()
        groups: dict[int, list] = {}
        for s in newly:
            # a prompt longer than every configured bucket pads straight to
            # max_seq (admission already truncated it to max_seq - 1)
            L = next((b for b in buckets if b >= s["plen"]),
                     self.scfg.max_seq)
            groups.setdefault(L, []).append(s)
        free = iter(i for i, s in enumerate(slots) if s is None)
        for L, grp in sorted(groups.items()):
            while grp:
                bk = _pow2_at_least(len(grp), self.scfg.batch_slots)
                toks = np.full((bk, L), self.pad, np.int32)
                lens = np.zeros((bk,), np.int32)
                for row, s in enumerate(grp):
                    toks[row, :s["plen"]] = s["prompt"]
                    lens[row] = s["plen"]
                exe, _ = self._resolve_prefill(bk, L)
                rids = [s["rid"] for s in grp]
                try:
                    first, cache = self._call_step("prefill", rids, exe,
                                                   self.batched.params,
                                                   toks, lens,
                                                   np.int32(step_i),
                                                   slots=grp)
                except PoisonError as e:
                    bad = next(s for s in grp if s["rid"] == e.rid)
                    self._emit_err(out_chan, e.rid, "poisoned", str(e),
                                   slot=bad)
                    grp = [s for s in grp if s["rid"] != e.rid]
                    continue                # retry the group without it
                except Exception as e:  # noqa: BLE001 - group-level failure
                    from .admission import BreakerOpen
                    st = "overloaded" if isinstance(e, BreakerOpen) \
                        else "error"
                    for s in grp:
                        self._emit_err(out_chan, s["rid"], st,
                                       str(e) if st == "overloaded"
                                       else repr(e)[:200], slot=s)
                    break
                step_i += 1
                first = np.asarray(first)  # [bk] sampled on device
                write = self._resolve_write(bk)
                for row, s in enumerate(grp):
                    slot = next(free)
                    packed = write(packed, cache, np.int32(row),
                                   np.int32(slot))
                    s["next"] = int(first[row])
                    self._note_tok(s, s["next"])
                    slots[slot] = s
                break
        return packed, step_i

    def collector(self, out_in, results: dict) -> None:
        while True:
            if out_in.eot():               # shutdown transaction
                out_in.open()
                break
            hdr = out_in.read()
            if hdr[0] == "err":            # quarantined/rejected request
                _, rid, status, detail, retry_after = hdr
                for _ in out_in.read_transaction():
                    pass
                results[rid] = RequestError(rid, status, detail,
                                            retry_after_s=retry_after)
                continue
            kind, rid = hdr
            assert kind == "hdr"
            results[rid] = [t for (_, t) in out_in.read_transaction()]

    # -- top ------------------------------------------------------------------

    def top(self, requests: list, results: dict) -> None:
        cap = self.scfg.queue_cap          # bounded admission queue
        req = channel(capacity=cap, name="requests")
        out = channel(capacity=cap, name="outputs")
        # traffic-timed mode: requests carrying arrival times, an
        # admission controller, or an explicit pace select the paced
        # frontend; plain request lists keep the seed task graph
        timed = (self.admission is not None or self.pace is not None
                 or any(getattr(r, "t_arrival", None) is not None
                        for r in requests))
        if timed:
            tick = channel(capacity=1, name="ticks") \
                if self.pace == "virtual" else None
            task() \
                .invoke(self.traffic_frontend, requests, req, tick,
                        results) \
                .invoke(self.scheduler, req, out, tick) \
                .invoke(self.collector, out, results)
        else:
            task() \
                .invoke(self.frontend, requests, req, results) \
                .invoke(self.scheduler, req, out) \
                .invoke(self.collector, out, results)


def serve_requests(engine: ServingEngine, requests: list,
                   sim_engine: str = "coroutine", faults: Any = None,
                   watchdog_s: Optional[float] = None) -> dict:
    """One-call host API for serving (paper Section 3.1.4).

    ``faults`` (a FaultPlan or FaultInjector) arms BOTH the serving-level
    faults (poison/cancel/transient, via ``engine.faults``) and the
    channel/task-level faults of the simulation engine that hosts the
    serving task graph; ``watchdog_s`` bounds the whole run's wall clock
    with the unified deadlock watchdog.
    """
    results: dict = {}
    if faults is not None:
        if not hasattr(faults, "serving_check"):
            faults = faults.injector()
        engine.faults = faults
    rep = ENGINES[sim_engine](faults=faults,
                              watchdog_s=watchdog_s).run(
        engine.top, requests, results)
    if not rep.ok:
        raise RuntimeError(f"serving failed: {rep.error}")
    return results
