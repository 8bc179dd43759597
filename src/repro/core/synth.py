"""Whole-graph synthesis: lower a task graph to ONE compiled XLA program.

The paper's two-sided contract (Fig. 2) is *simulate for correctness,
synthesize for QoR*.  Until now this repo's "codegen" jitted stage
functions one at a time while host Python shuttled every token between
them — the interconnect (FIFOs, task firing control) stayed in software.
TAPA's insight is that the win comes from synthesizing exactly that
interconnect; hlslib's is that channels must become typed, fixed-capacity
hardware objects for the lowering to exist.  This module is the XLA
analogue:

* every :class:`~repro.core.channel.Channel` becomes a fixed-capacity
  **on-device ring buffer** — ``(buf[capacity, *elem_shape], head, size)``
  carried through a ``lax.while_loop``;
* every task becomes a **guarded step**: it fires only when its declared
  reads are available and its writes fit, mirroring the engines' blocking
  semantics exactly;
* bursts become slice transfers (gather/scatter over the ring);
* mmap buffers and scalars flow through the PR-4 ``lower_spec`` path —
  mmaps are runtime inputs of the executable, scalars static constants.

The synthesizable subset is the **step-function form**: a leaf task is a
:class:`StepTask` whose phases are pure jax-traceable functions

    ``state, *port_views -> state``

with *static* I/O rates (reads/writes per firing fixed at trace time).
The same StepTask runs unmodified under the Python engines — its
``__call__`` is the **simulation twin**, executing the phase functions
against real blocking streams — so one graph definition is both the
correctness vehicle and the compiled artifact, bit-for-bit.

Whole-graph lowerings are keyed in the PR-2 compile cache by the graph's
structural hash + input avals: a second process re-running the same graph
performs **zero XLA compiles**.

Since schema ``synth3`` a graph can also be **partitioned** across a
1-D device mesh (``CompiledEngine(mesh=N)``): the floorplanner
(:mod:`repro.core.floorplan`) assigns tasks to devices on real per-task
costs, and ``_build_partitioned_program`` lowers the cut channels to
``lax.ppermute`` exchanges inside a sweep-synchronous ``shard_map``
body that is a bit-twin of the single-device program.  Placements are
content-addressed artifacts; the owners vector folds into the compile
key, so re-partitioning and recompiling are both zero on reuse.

The ring-buffer ops themselves (pop/push bursts, fused guard
evaluation) dispatch through :mod:`repro.kernels.ring` — Pallas kernels
on TPU, a bit-exact vectorized XLA reference elsewhere, interpret mode
for parity tests — selected per engine (``ring_impl=``) or process
(``$REPRO_RING_IMPL``).

``async_mmap`` ports ARE synthesizable (since schema ``synth2``): the
five member channels lower to ordinary ring buffers and the memory
endpoint becomes a fixed-``depth`` latency queue in the while_loop
carry, serviced once per sweep — requests are accepted issue-ahead up
to ``depth`` outstanding and responses delivered ``latency`` sweeps
later in per-port FIFO order, matching the simulator contract.  See
``docs/synthesis.md`` ("kernel lowering").

Anything outside the subset is *refused with a diagnostic naming the
task/channel* (:class:`~repro.core.errors.SynthesisError`), never
miscompiled: non-step leaf tasks (e.g. availability-routed switches using
``peek``/``select``), channels without a declared element spec,
data-dependent I/O rates, async_mmap ports with an unbounded in-flight
window (``depth=None``) or used for both reads and writes (response-
timing-dependent), and mmaps both written and read across tasks
(schedule-dependent).  See ``docs/synthesis.md``.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import time
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from .channel import Channel, IStream, OStream
from .compile_cache import (_stable_repr, aval_signature, default_cache,
                            toolchain_tag)
from .context import clear_context, set_context
from .engines import ENGINES, EngineBase, SimReport
from .errors import (ChannelMisuse, DeadlockReport, GraphValidationError,
                     SynthesisError)
from .graph import extract_graph
from .interface import AsyncMMap, MMap
from .task import (AutoStream, TaskInstance, bind_streams,
                   builder_stack_depth, join_pending_builders)
from ..kernels.dispatch import resolve_impl
from ..kernels.ring import (RING_CHOICES, RING_ENV, eval_guards, ring_pop,
                            ring_push)

SYNTH_SCHEMA = "synth6"


def _canon_dtype(dtype: Any) -> np.dtype:
    """The dtype a ring buffer (or mmap input) actually carries on device:
    the declared dtype after jax canonicalization (x64 -> x32 when 64-bit
    mode is off).  Element checks compare against THIS, so a float64
    declaration is not misreported as the task's fault."""
    return np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype)))


def _materialize_state(init: Any) -> Any:
    """Canonicalize an initial-state pytree to jax arrays — the same
    representation the twin and the compiled program both carry, so float
    semantics (incl. x64 canonicalization) agree between them."""
    return jax.tree.map(jnp.asarray, init)


def _state_spec(state: Any) -> Any:
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), state)


# ---------------------------------------------------------------------------
# the step-function task form
# ---------------------------------------------------------------------------

class StepTask:
    """A leaf task in traceable step-function form.

    Up to three phases, each a pure function ``state, *ports -> state``
    with static per-firing I/O rates:

    * ``warmup`` — fires ``n_warmup`` times (pipeline fill: e.g. read the
      first stencil row without emitting);
    * ``step``   — the steady state, fires ``steps`` times;
    * ``flush``  — fires ``n_flush`` times (drain: e.g. emit the
      accumulated result block).

    ``init`` is the initial state pytree.  Ports are the invoke arguments:
    channels appear as stream views (``read``/``read_burst``/``write``/
    ``write_burst`` only — no EoT, no peek: termination is by firing
    count), mmaps as memory views, scalars as plain values.

    Calling a StepTask *is* its simulation twin: the classic engines
    invoke it like any task body, and it runs the phase functions against
    the real blocking streams.  ``CompiledEngine`` instead lowers every
    firing into a guarded step of one jitted whole-graph program.
    """

    is_step_task = True

    def __init__(self, step: Callable, *, steps: int, init: Any = None,
                 warmup: Optional[Callable] = None, n_warmup: int = 1,
                 flush: Optional[Callable] = None, n_flush: int = 1,
                 close_outputs: bool = False, name: Optional[str] = None):
        if not isinstance(steps, int) or steps < 0:
            raise ValueError("StepTask steps must be a static int >= 0")
        self.step = step
        self.steps = steps
        self.init = init
        self.warmup = warmup
        self.n_warmup = int(n_warmup) if warmup is not None else 0
        self.flush = flush
        self.n_flush = int(n_flush) if flush is not None else 0
        # interop with EoT-consuming free-form tasks: the twin closes every
        # written stream after its last firing.  EoT is outside the
        # synthesizable subset, so synthesis refuses such tasks.
        self.close_outputs = close_outputs
        self.__name__ = name or getattr(step, "__name__", "step_task")
        try:
            sig = inspect.signature(step)
            params = list(sig.parameters.values())[1:]   # drop ``state``
            self.__signature__ = sig.replace(parameters=params)
        except (TypeError, ValueError):
            pass

    def phases(self) -> list[tuple[str, Callable, int]]:
        out = []
        if self.warmup is not None and self.n_warmup:
            out.append(("warmup", self.warmup, self.n_warmup))
        if self.steps:
            out.append(("step", self.step, self.steps))
        if self.flush is not None and self.n_flush:
            out.append(("flush", self.flush, self.n_flush))
        return out

    @property
    def total_fires(self) -> int:
        return sum(n for _, _, n in self.phases())

    # -- simulation twin -----------------------------------------------------
    def __call__(self, *args, **kwargs):
        streams: list[_TwinStream] = []
        views = tuple(_twin_view(a, streams) for a in args)
        kw = {k: _twin_view(v, streams) for k, v in kwargs.items()}
        state = _materialize_state(self.init)
        for _, fn, n in self.phases():
            for _ in range(n):
                state = fn(state, *views, **kw)
        if self.close_outputs:
            for s in streams:
                # close written streams, and annotated output ports even
                # when this instance never fired (an empty schedule must
                # still end its downstream consumer's transaction)
                if s._wrote or isinstance(s._s, OStream):
                    s._s.close()
        return state

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"StepTask({self.__name__!r}, "
                f"fires={self.total_fires})")


class _TwinStream:
    """Simulation-twin stream view: the synthesizable port API
    (``read``/``read_burst``/``write``/``write_burst``) over a real
    blocking stream.  Burst reads stack to an array so the phase function
    sees the exact value shape synthesis hands it."""

    __slots__ = ("_s", "_wrote")

    def __init__(self, s):
        self._s = s
        self._wrote = False

    def read(self):
        return self._s.read()

    def read_burst(self, n: int):
        toks = self._s.read_burst(n)
        if len(toks) != n:
            raise ChannelMisuse(
                f"step task read_burst({n}) hit EoT after {len(toks)} "
                f"tokens on channel {self._s.channel.name!r}; step graphs "
                f"terminate by firing counts, not EoT")
        return jnp.stack([jnp.asarray(t) for t in toks])

    def write(self, tok) -> None:
        self._wrote = True
        self._s.write(tok)

    def write_burst(self, arr) -> None:
        self._wrote = True
        self._s.write_burst(list(arr))


class _TwinPort:
    """Simulation-twin view of an async memory port: the five member
    streams wrapped as :class:`_TwinStream` so burst reads stack to
    arrays — the exact value shapes synthesis hands the phase function.
    Port streams are never EoT-closed (memory request channels carry no
    transactions), so they stay off the ``close_outputs`` list."""

    __slots__ = ("_p", "read_addr", "read_data", "write_addr",
                 "write_data", "write_resp")

    def __init__(self, p: AsyncMMap):
        self._p = p
        self.read_addr = _TwinStream(p.read_addr)
        self.read_data = _TwinStream(p.read_data)
        self.write_addr = _TwinStream(p.write_addr)
        self.write_data = _TwinStream(p.write_data)
        self.write_resp = _TwinStream(p.write_resp)

    @property
    def shape(self) -> tuple:
        return tuple(self._p.shape)

    @property
    def dtype(self):
        return self._p.dtype

    @property
    def latency(self) -> int:
        return self._p.latency

    @property
    def depth(self):
        return self._p.depth

    @property
    def name(self) -> str:
        return self._p.name

    def __len__(self) -> int:
        return len(self._p)

    def read_pipelined(self, addrs) -> list:
        return self._p.read_pipelined(addrs)


def _twin_view(v: Any, streams: Optional[list] = None) -> Any:
    if isinstance(v, (IStream, OStream, AutoStream)):
        tw = _TwinStream(v)
        if streams is not None:
            streams.append(tw)
        return tw
    if isinstance(v, AsyncMMap):
        return _TwinPort(v)
    if isinstance(v, (list, tuple)):
        return type(v)(_twin_view(x, streams) for x in v)
    return v


# ---------------------------------------------------------------------------
# trace-time views (shared by the counting pass and the real lowering)
# ---------------------------------------------------------------------------

class _Ctx:
    """Mutable trace-time context: the functional channel/mmap states a
    firing reads and replaces."""

    __slots__ = ("chans", "mmaps", "ring_impl")

    def __init__(self, chans: dict, mmaps: dict, ring_impl: str = "xla"):
        self.chans = chans      # ci -> (buf, head, size)
        self.mmaps = mmaps      # mi -> array
        self.ring_impl = ring_impl


class _Recorder:
    """Counting-pass sink: per-phase I/O rates + endpoint/direction
    registration.  Absent (None) during the real lowering trace — the
    counts are already validated identical because the trace is the same
    Python."""

    def __init__(self, inst: TaskInstance):
        self.inst = inst
        self.reads: dict[int, int] = {}
        self.writes: dict[int, int] = {}
        self.mmap_loads: dict[int, int] = {}     # element counts
        self.mmap_stores: dict[int, int] = {}
        self.mmap_load_ops: dict[int, int] = {}  # transfer counts
        self.mmap_store_ops: dict[int, int] = {}
        self.mmap_read: set = set()
        self.mmap_written: set = set()


class _SynthStream:
    """Trace-time stream view over a ring buffer in the carry."""

    __slots__ = ("_ctx", "_ci", "_chan", "_inst", "_rec")

    def __init__(self, ctx: _Ctx, ci: int, chan: Channel,
                 inst: TaskInstance, rec: Optional[_Recorder]):
        self._ctx = ctx
        self._ci = ci
        self._chan = chan
        self._inst = inst
        self._rec = rec

    # -- reads ---------------------------------------------------------------
    def read(self):
        buf, head, size = self._ctx.chans[self._ci]
        self._account("read", 1)
        toks, head, size = ring_pop(buf, head, size, 1,
                                    impl=self._ctx.ring_impl)
        self._ctx.chans[self._ci] = (buf, head, size)
        return toks[0]

    def read_burst(self, n: int):
        n = self._static(n, "read_burst")
        buf, head, size = self._ctx.chans[self._ci]
        self._account("read", n)
        toks, head, size = ring_pop(buf, head, size, n,
                                    impl=self._ctx.ring_impl)
        self._ctx.chans[self._ci] = (buf, head, size)
        return toks

    # -- writes --------------------------------------------------------------
    def write(self, tok) -> None:
        tok = jnp.asarray(tok)
        self._check_elem(tok, burst=False)
        buf, head, size = self._ctx.chans[self._ci]
        self._account("write", 1)
        self._ctx.chans[self._ci] = ring_push(buf, head, size, tok[None],
                                              impl=self._ctx.ring_impl)

    def write_burst(self, arr) -> None:
        arr = jnp.asarray(arr) if not isinstance(arr, (list, tuple)) \
            else jnp.stack([jnp.asarray(t) for t in arr])
        self._check_elem(arr, burst=True)
        n = int(arr.shape[0])
        buf, head, size = self._ctx.chans[self._ci]
        self._account("write", n)
        self._ctx.chans[self._ci] = ring_push(buf, head, size, arr,
                                              impl=self._ctx.ring_impl)

    # -- everything else is outside the synthesizable subset -----------------
    def _unsupported(self, op: str):
        raise SynthesisError(
            f"task {self._inst.name!r} used stream op {op!r} on channel "
            f"{self._chan.name!r}: step-function tasks may only "
            f"read/read_burst/write/write_burst (termination is by firing "
            f"count, availability routing needs the simulation engines)")

    def close(self):
        self._unsupported("close")

    def peek(self):
        self._unsupported("peek")

    def eot(self):
        self._unsupported("eot")

    def open(self):
        self._unsupported("open")

    def empty(self):
        self._unsupported("empty")

    def full(self):
        self._unsupported("full")

    def try_read(self):
        self._unsupported("try_read")

    def try_write(self, v):
        self._unsupported("try_write")

    # -- helpers -------------------------------------------------------------
    def _static(self, n: Any, op: str) -> int:
        if not isinstance(n, (int, np.integer)):
            raise SynthesisError(
                f"task {self._inst.name!r}: {op} size on channel "
                f"{self._chan.name!r} is data-dependent (a traced value); "
                f"synthesis needs static I/O rates")
        return int(n)

    def _check_elem(self, arr, burst: bool) -> None:
        c = self._chan
        got_shape = tuple(arr.shape[1:]) if burst else tuple(arr.shape)
        if got_shape != c.shape:
            raise SynthesisError(
                f"task {self._inst.name!r} wrote a token of shape "
                f"{got_shape} to channel {c.name!r} declaring element "
                f"shape {c.shape}")
        if np.dtype(arr.dtype) != _canon_dtype(c.dtype):
            raise SynthesisError(
                f"task {self._inst.name!r} wrote a token of dtype "
                f"{arr.dtype} to channel {c.name!r} declaring element "
                f"dtype {c.dtype} (canonicalized {_canon_dtype(c.dtype)})")

    def _account(self, op: str, n: int) -> None:
        rec = self._rec
        if rec is None:
            return
        if op == "read":
            self._chan._bind("consumer", self._inst)
            rec.reads[self._ci] = rec.reads.get(self._ci, 0) + n
        else:
            self._chan._bind("producer", self._inst)
            rec.writes[self._ci] = rec.writes.get(self._ci, 0) + n


class _SynthMMap:
    """Trace-time memory view: the MMap API over a carry array, updated
    functionally.  Loads/stores may use traced indices (they lower to
    gathers / dynamic slices)."""

    __slots__ = ("_ctx", "_mi", "_mmap", "_inst", "_rec")

    def __init__(self, ctx: _Ctx, mi: int, mmap: MMap,
                 inst: TaskInstance, rec: Optional[_Recorder]):
        self._ctx = ctx
        self._mi = mi
        self._mmap = mmap
        self._inst = inst
        self._rec = rec

    @property
    def shape(self) -> tuple:
        return tuple(self._mmap.shape)

    @property
    def dtype(self):
        return self._ctx.mmaps[self._mi].dtype

    def __len__(self) -> int:
        return len(self._mmap)

    def __getitem__(self, idx):
        v = self._ctx.mmaps[self._mi][idx]
        self._account("read", v)
        return v

    def __setitem__(self, idx, value) -> None:
        value = jnp.asarray(value)
        self._account("write", value)
        self._ctx.mmaps[self._mi] = \
            self._ctx.mmaps[self._mi].at[idx].set(value)

    def read_burst(self, start, n: int):
        if not isinstance(n, (int, np.integer)):
            raise SynthesisError(
                f"task {self._inst.name!r}: mmap {self._mmap.name!r} "
                f"read_burst size is data-dependent; synthesis needs a "
                f"static transfer size")
        out = jax.lax.dynamic_slice_in_dim(
            self._ctx.mmaps[self._mi], jnp.asarray(start, jnp.int32),
            int(n), axis=0)
        self._account("read", out)
        return out

    def write_burst(self, start, seq) -> None:
        seq = jnp.asarray(seq)
        self._account("write", seq)
        self._ctx.mmaps[self._mi] = jax.lax.dynamic_update_slice_in_dim(
            self._ctx.mmaps[self._mi], seq, jnp.asarray(start, jnp.int32),
            axis=0)

    def _account(self, op: str, v) -> None:
        rec = self._rec
        if rec is None:
            return
        n = int(np.prod(np.shape(v))) if np.shape(v) else 1
        if op == "read":
            rec.mmap_read.add(self._mi)
            rec.mmap_loads[self._mi] = rec.mmap_loads.get(self._mi, 0) + n
            rec.mmap_load_ops[self._mi] = \
                rec.mmap_load_ops.get(self._mi, 0) + 1
        else:
            rec.mmap_written.add(self._mi)
            rec.mmap_stores[self._mi] = rec.mmap_stores.get(self._mi, 0) + n
            rec.mmap_store_ops[self._mi] = \
                rec.mmap_store_ops.get(self._mi, 0) + 1
        b = self._mmap._by_inst.get(self._inst.uid)
        if b is not None:
            b.direction.add(op)


# ---------------------------------------------------------------------------
# lowering plan
# ---------------------------------------------------------------------------

class _ChanRef:
    __slots__ = ("ci",)

    def __init__(self, ci: int):
        self.ci = ci


class _MMapRef:
    __slots__ = ("mi",)

    def __init__(self, mi: int):
        self.mi = mi


class _PortRef:
    __slots__ = ("pi", "cis")

    def __init__(self, pi: int, cis: tuple):
        self.pi = pi
        self.cis = cis      # (raddr, rdata, waddr, wdata, wresp) chan ids


class _SynthAsyncPort:
    """Trace-time view of an async memory port: the five member streams
    are ordinary :class:`_SynthStream` views over their ring buffers in
    the carry — so port I/O gets guards and static-rate counting for
    free — while the memory endpoint itself is serviced once per sweep
    by the lowered latency queue (see ``_build_program``)."""

    __slots__ = ("_port", "_inst", "read_addr", "read_data", "write_addr",
                 "write_data", "write_resp")

    def __init__(self, ctx: _Ctx, cis: tuple, port: AsyncMMap,
                 inst: TaskInstance, rec: Optional[_Recorder],
                 plan: "_Plan"):
        self._port = port
        self._inst = inst
        mk = lambda ci: _SynthStream(  # noqa: E731
            ctx, ci, plan.channels[ci], inst, rec)
        ra, rd, wa, wd, wr = cis
        self.read_addr = mk(ra)
        self.read_data = mk(rd)
        self.write_addr = mk(wa)
        self.write_data = mk(wd)
        self.write_resp = mk(wr)

    @property
    def shape(self) -> tuple:
        return tuple(self._port.shape)

    @property
    def dtype(self):
        return self._port.dtype

    @property
    def latency(self) -> int:
        return self._port.latency

    @property
    def depth(self):
        return self._port.depth

    @property
    def name(self) -> str:
        return self._port.name

    def __len__(self) -> int:
        return len(self._port)

    def read_pipelined(self, addrs):
        raise SynthesisError(
            f"task {self._inst.name!r} used read_pipelined on async_mmap "
            f"{self._port.name!r}: its issue/drain interleaving is "
            f"availability-routed (try_write/select), outside the static-"
            f"rate subset.  Software-pipeline it instead: issue addresses "
            f"with write/write_burst on read_addr and drain read_data with "
            f"read/read_burst across warmup/step/flush phases (see "
            f"docs/synthesis.md, kernel lowering)")


@dataclass
class _PhasePlan:
    label: str
    fn: Callable
    count: int
    reads: dict = field(default_factory=dict)    # ci -> tokens per firing
    writes: dict = field(default_factory=dict)
    mmap_loads: dict = field(default_factory=dict)    # mi -> elems/firing
    mmap_stores: dict = field(default_factory=dict)
    mmap_load_ops: dict = field(default_factory=dict)  # mi -> transfers
    mmap_store_ops: dict = field(default_factory=dict)


@dataclass
class _TaskPlan:
    inst: TaskInstance
    task: StepTask
    t_args: tuple = ()
    t_kwargs: dict = field(default_factory=dict)
    chan_ids: list = field(default_factory=list)
    mmap_ids: list = field(default_factory=list)
    port_ids: list = field(default_factory=list)
    phases: list = field(default_factory=list)   # [_PhasePlan]
    state0: Any = None

    @property
    def total(self) -> int:
        return sum(p.count for p in self.phases)

    @property
    def bounds(self) -> list[int]:
        out, acc = [], 0
        for p in self.phases:
            acc += p.count
            out.append(acc)
        return out


class _Plan:
    def __init__(self):
        self.channels: list[Channel] = []
        self._chan_idx: dict[int, int] = {}
        self.mmaps: list[MMap] = []
        self._mmap_idx: dict[int, int] = {}
        self.ports: list[AsyncMMap] = []
        self._port_idx: dict[int, int] = {}
        self.port_chan_ids: dict[int, tuple] = {}   # pi -> 5 member cis
        self.port_dirs: list[set] = []              # pi -> {"read","write"}
        self.ring_impl: str = "xla"
        self.tasks: list[_TaskPlan] = []
        self.n_phase_traces = 0     # jax.eval_shape calls of _count_phase
        self.structural_hash = ""   # the graph's, keying memo and executable

    def chan_index(self, c: Channel) -> int:
        i = self._chan_idx.get(id(c))
        if i is None:
            i = self._chan_idx[id(c)] = len(self.channels)
            self.channels.append(c)
        return i

    def mmap_index(self, m: MMap) -> int:
        i = self._mmap_idx.get(id(m))
        if i is None:
            i = self._mmap_idx[id(m)] = len(self.mmaps)
            self.mmaps.append(m)
        return i

    def port_index(self, p: AsyncMMap) -> int:
        i = self._port_idx.get(id(p))
        if i is None:
            i = self._port_idx[id(p)] = len(self.ports)
            self.ports.append(p)
            self.port_dirs.append(set())
        return i


def _build_template(v: Any, plan: _Plan, tp: _TaskPlan) -> Any:
    """Replace bound stream/mmap views with carry references; everything
    else (scalars, None, raw arrays — trace-time constants) passes
    through."""
    if isinstance(v, (IStream, OStream, AutoStream)):
        ci = plan.chan_index(v.channel)
        if ci not in tp.chan_ids:
            tp.chan_ids.append(ci)
        return _ChanRef(ci)
    if isinstance(v, MMap):
        mi = plan.mmap_index(v)
        if mi not in tp.mmap_ids:
            tp.mmap_ids.append(mi)
        return _MMapRef(mi)
    if isinstance(v, AsyncMMap):
        if not isinstance(v.depth, int):
            raise SynthesisError(
                f"task {tp.inst.name!r} binds async_mmap {v.name!r} with "
                f"an unbounded in-flight window (depth=None): synthesis "
                f"sizes the latency queue in the while_loop carry from a "
                f"static depth — give the port a bounded depth (e.g. "
                f"depth=4) or run on a simulation engine")
        pi = plan.port_index(v)
        if pi not in tp.port_ids:
            tp.port_ids.append(pi)
        cis = []
        for ch in v.channels():
            ci = plan.chan_index(ch)
            if ci not in tp.chan_ids:
                tp.chan_ids.append(ci)
            cis.append(ci)
        plan.port_chan_ids[pi] = tuple(cis)
        return _PortRef(pi, tuple(cis))
    if isinstance(v, (list, tuple)):
        conv = [_build_template(x, plan, tp) for x in v]
        return type(v)(conv) if isinstance(v, tuple) else conv
    return v


def _instantiate(t: Any, ctx: _Ctx, plan: _Plan, inst: TaskInstance,
                 rec: Optional[_Recorder]) -> Any:
    if isinstance(t, _ChanRef):
        return _SynthStream(ctx, t.ci, plan.channels[t.ci], inst, rec)
    if isinstance(t, _MMapRef):
        return _SynthMMap(ctx, t.mi, plan.mmaps[t.mi], inst, rec)
    if isinstance(t, _PortRef):
        return _SynthAsyncPort(ctx, t.cis, plan.ports[t.pi], inst, rec,
                               plan)
    if isinstance(t, (list, tuple)):
        conv = [_instantiate(x, ctx, plan, inst, rec) for x in t]
        return type(t)(conv) if isinstance(t, tuple) else conv
    return t


def _chan_specs(plan: _Plan, tp: _TaskPlan) -> tuple:
    out = []
    for ci in tp.chan_ids:
        c = plan.channels[ci]
        out.append((
            jax.ShapeDtypeStruct((c.capacity,) + c.shape,
                                 _canon_dtype(c.dtype)),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)))
    return tuple(out)


def _mmap_specs(plan: _Plan, tp: _TaskPlan) -> tuple:
    # canonical dtype: what jnp.asarray(m.data) will produce at run time
    return tuple(
        jax.ShapeDtypeStruct(
            tuple(plan.mmaps[mi].shape),
            jax.dtypes.canonicalize_dtype(np.dtype(plan.mmaps[mi].dtype)))
        for mi in tp.mmap_ids)


def _phase_probe(plan: _Plan, tp: _TaskPlan, fn: Callable,
                 rec: Optional[_Recorder]) -> Callable:
    """The single firing body shared by the counting pass (abstract, via
    eval_shape) and the real lowering (traced into the while_loop)."""

    def probe(state, chans, mmaps):
        ctx = _Ctx(dict(zip(tp.chan_ids, chans)),
                   dict(zip(tp.mmap_ids, mmaps)), plan.ring_impl)
        args = tuple(_instantiate(t, ctx, plan, tp.inst, rec)
                     for t in tp.t_args)
        kw = {k: _instantiate(t, ctx, plan, tp.inst, rec)
              for k, t in tp.t_kwargs.items()}
        new_state = fn(state, *args, **kw)
        return (new_state,
                tuple(ctx.chans[ci] for ci in tp.chan_ids),
                tuple(ctx.mmaps[mi] for mi in tp.mmap_ids))

    return probe


def _count_phase(plan: _Plan, tp: _TaskPlan, label: str, fn: Callable,
                 count: int) -> _PhasePlan:
    rec = _Recorder(tp.inst)
    probe = _phase_probe(plan, tp, fn, rec)
    spec = _state_spec(tp.state0)
    plan.n_phase_traces += 1
    try:
        out_state, _, _ = jax.eval_shape(
            probe, spec, _chan_specs(plan, tp), _mmap_specs(plan, tp))
    except (SynthesisError, ChannelMisuse, GraphValidationError):
        raise
    except Exception as e:
        raise SynthesisError(
            f"task {tp.inst.name!r}: phase {label!r} failed to trace "
            f"({type(e).__name__}: {e}); step-function bodies must be "
            f"jax-traceable with static I/O rates") from e
    got = jax.tree.map(lambda x: (tuple(x.shape), np.dtype(x.dtype)),
                       out_state)
    want = jax.tree.map(lambda x: (tuple(x.shape), np.dtype(x.dtype)), spec)
    if got != want:
        raise SynthesisError(
            f"task {tp.inst.name!r}: phase {label!r} changed the state "
            f"spec from {want} to {got}; step state must be shape- and "
            f"dtype-stable across firings")
    for ci, r in rec.reads.items():
        c = plan.channels[ci]
        if r > c.capacity:
            raise SynthesisError(
                f"task {tp.inst.name!r}: phase {label!r} reads {r} tokens "
                f"per firing from channel {c.name!r} of capacity "
                f"{c.capacity}; it could never fire")
    for ci, w in rec.writes.items():
        c = plan.channels[ci]
        if w > c.capacity:
            raise SynthesisError(
                f"task {tp.inst.name!r}: phase {label!r} writes {w} tokens "
                f"per firing to channel {c.name!r} of capacity "
                f"{c.capacity}; it could never fire")
    return _PhasePlan(label=label, fn=fn, count=count, reads=rec.reads,
                      writes=rec.writes, mmap_loads=rec.mmap_loads,
                      mmap_stores=rec.mmap_stores,
                      mmap_load_ops=rec.mmap_load_ops,
                      mmap_store_ops=rec.mmap_store_ops)


# A phase's per-firing counts, each keyed by an index into the task's own
# channel or mmap list.  The lowering memo stores them by position in that
# list, so they apply to any invocation of the same structure whatever
# global indices it assigns.
_COUNTS = (("reads", "chan_ids"), ("writes", "chan_ids"),
           ("mmap_loads", "mmap_ids"), ("mmap_stores", "mmap_ids"),
           ("mmap_load_ops", "mmap_ids"), ("mmap_store_ops", "mmap_ids"))


def _lowering_key(structural_hash: str, ring_impl: str) -> str:
    """Key of a graph's counted phase plans: its structure covers every
    input of a phase trace (step bodies and ``init`` by content, channel
    specs, mmap avals, scalars); the rest is what shapes the traced avals
    (``_mmap_specs`` canonicalises dtypes under the x64 flag)."""
    h = hashlib.sha256()
    h.update(f"lower:{structural_hash}:ring={ring_impl}:{SYNTH_SCHEMA}:"
             f"jax:{jax.__version__}:{toolchain_tag()}:"
             f"x64={jax.config.jax_enable_x64}".encode())
    return h.hexdigest()


def _positional_counts(tp: _TaskPlan, ph: _PhasePlan) -> tuple:
    return tuple(
        tuple((getattr(tp, ids).index(i), n)
              for i, n in getattr(ph, name).items())
        for name, ids in _COUNTS)


def _remembered_phase(plan: _Plan, tp: _TaskPlan, label: str, fn: Callable,
                      count: int, counts: tuple) -> _PhasePlan:
    """A phase plan from the memo's counts, with the endpoint and
    direction registrations that counting would have made (``_account``):
    ``graph.validate()`` and the one-writer check read them."""
    ph = _PhasePlan(label=label, fn=fn, count=count, **{
        name: {getattr(tp, ids)[k]: n for k, n in c}
        for (name, ids), c in zip(_COUNTS, counts, strict=True)})
    for ci in ph.reads:
        plan.channels[ci]._bind("consumer", tp.inst)
    for ci in ph.writes:
        plan.channels[ci]._bind("producer", tp.inst)
    for op, mis in (("read", ph.mmap_loads), ("write", ph.mmap_stores)):
        for mi in mis:
            b = plan.mmaps[mi]._by_inst.get(tp.inst.uid)
            if b is not None:
                b.direction.add(op)
    return ph


# ---------------------------------------------------------------------------
# the whole-graph program
# ---------------------------------------------------------------------------

def _port_carry0(port: AsyncMMap) -> tuple:
    """Initial latency-queue carry for one async port: the device copy of
    the buffer, two fixed-``depth`` in-flight rings (read: addr+due;
    write: addr+due+value), and the six always-on request counters."""
    data = jnp.asarray(port.data)
    d = port.depth
    zv = jnp.zeros((d,), jnp.int32)
    zs = jnp.zeros((), jnp.int32)
    return (data,
            zv, zv, zs, zs,                                  # read queue
            zv, zv, jnp.zeros((d,) + data.shape[1:], data.dtype),
            zs, zs,                                          # write queue
            zs, zs, zs, zs, zs, zs)                          # counters

# _port_carry0 tuple indices (shared by the program and the stats fill)
_P_DATA, _P_RADDR, _P_RDUE, _P_RHEAD, _P_RSIZE = 0, 1, 2, 3, 4
_P_WADDR, _P_WDUE, _P_WVAL, _P_WHEAD, _P_WSIZE = 5, 6, 7, 8, 9
_P_ACC_R, _P_DEL_R, _P_ACC_W, _P_DEL_W, _P_MAX_R, _P_MAX_W = \
    10, 11, 12, 13, 14, 15


class _SweepTables(NamedTuple):
    """The plan as the sweep reads it, as constants: per (task, phase)
    read/write token needs over every channel, the cumulative phase
    bounds (padded with int32-max so shorter tasks never advance past
    their last phase; None when every task has one phase), each
    channel's capacity and each task's firing total."""
    need_r: np.ndarray
    need_w: np.ndarray
    bounds: Optional[np.ndarray]
    caps: list
    totals: np.ndarray


def _sweep_tables(plan: _Plan) -> _SweepTables:
    n_tasks = len(plan.tasks)
    n_chans = len(plan.channels)
    n_ph_max = max((len(tp.phases) for tp in plan.tasks), default=1)
    need_r_np = np.zeros((n_tasks, n_ph_max, max(n_chans, 1)), np.int32)
    need_w_np = np.zeros_like(need_r_np)
    for ti, tp in enumerate(plan.tasks):
        for pi, ph in enumerate(tp.phases):
            for ci, r in ph.reads.items():
                need_r_np[ti, pi, ci] = r
            for ci, w in ph.writes.items():
                need_w_np[ti, pi, ci] = w
    bounds_np = None
    if n_ph_max > 1:
        bounds_np = np.full((n_tasks, n_ph_max - 1),
                            np.iinfo(np.int32).max, np.int32)
        for ti, tp in enumerate(plan.tasks):
            b = tp.bounds[:-1]
            bounds_np[ti, :len(b)] = b
    return _SweepTables(
        need_r=need_r_np, need_w=need_w_np, bounds=bounds_np,
        caps=[c.capacity for c in plan.channels],
        totals=np.asarray([tp.total for tp in plan.tasks], np.int32))


def _empty_rings(plan: _Plan) -> tuple:
    """Every channel's ring ``(buf, head, size)``, empty."""
    return tuple(
        (jnp.zeros((c.capacity,) + c.shape, _canon_dtype(c.dtype)),
         jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        for c in plan.channels)


def _ring_sizes(chans) -> jax.Array:
    """The rings' sizes as one vector (a zero where there are none)."""
    return (jnp.stack([c[2] for c in chans]) if chans
            else jnp.zeros((1,), jnp.int32))


def _sweep_phases(tables: _SweepTables, fires, totals_v) -> tuple:
    """Each task's phase at sweep start (the phase bounds its firing
    count has passed) and whether it has firings left."""
    if tables.bounds is not None:
        phase_vec = jnp.sum(
            (fires[:, None] >= jnp.asarray(tables.bounds))
            .astype(jnp.int32), axis=1)
    else:
        phase_vec = jnp.zeros((fires.shape[0],), jnp.int32)
    return phase_vec, fires < totals_v


def _sweep_guards(tables: _SweepTables, chans, phase_vec, live,
                  ring_impl: str) -> tuple:
    """Firing guards, evaluated *fused at sweep start*: one
    :func:`repro.kernels.ring.eval_guards` call computes every task's
    fire predicate from the ring sizes.  This is sound — and
    stall-for-stall equivalent to sequential mid-sweep guards — because
    each channel has one producer and one consumer: a consumer's
    available tokens can only shrink through its own firing, and a
    producer's free space only through its own, so a guard true at sweep
    start is still true when the task's effects apply in task order.
    Returns ``(fire_vec, sizes, nr, nw)``: the sizes read and each task's
    current-phase needs per channel (None without channels)."""
    if not chans:
        return live, None, None, None
    sizes = _ring_sizes(chans)
    nr = jnp.take_along_axis(
        jnp.asarray(tables.need_r), phase_vec[:, None, None],
        axis=1)[:, 0, :]
    nw = jnp.take_along_axis(
        jnp.asarray(tables.need_w), phase_vec[:, None, None],
        axis=1)[:, 0, :]
    fire_vec = eval_guards(sizes, jnp.asarray(tables.caps, jnp.int32), nr,
                           nw, live, impl=ring_impl)
    return fire_vec, sizes, nr, nw


def _fire_tasks(plan: _Plan, fire_vec, phase_vec, chans: list,
                states: list, mmaps: list,
                owns: Optional[Callable] = None):
    """Every task's guarded firing, in plan order: a ``lax.cond`` on its
    fire predicate (and on ``owns(ti)``, where a mesh device fires only
    its own tasks) over its current phase, whose state, rings and mmaps
    replace the task's entries of ``states``, ``chans`` and ``mmaps`` in
    place.  Yields each task's plan once its firing has landed."""
    for ti, tp in enumerate(plan.tasks):
        fire = fire_vec[ti] if owns is None else fire_vec[ti] & owns(ti)
        probes = [_phase_probe(plan, tp, ph.fn, rec=None)
                  for ph in tp.phases]
        fire_fn = probes[0] if len(probes) == 1 else functools.partial(
            jax.lax.switch, phase_vec[ti], probes)
        new_sub = jax.lax.cond(fire, fire_fn, lambda *s: s, states[ti],
                               tuple(chans[ci] for ci in tp.chan_ids),
                               tuple(mmaps[mi] for mi in tp.mmap_ids))
        states[ti] = new_sub[0]
        for k, ci in enumerate(tp.chan_ids):
            chans[ci] = new_sub[1][k]
        for k, mi in enumerate(tp.mmap_ids):
            mmaps[mi] = new_sub[2][k]
        yield tp


def _rebase_port_dues(pc: tuple, sweeps) -> tuple:
    """Rewrite one port carry's due stamps from chunk-local absolute
    sweeps to "sweeps remaining" (in-use slots only; free slots zero),
    so a restored snapshot replays response timing against a fresh
    chunk's counter."""
    d = pc[_P_RADDR].shape[0]
    iota = jnp.arange(d, dtype=jnp.int32)
    in_r = ((iota - pc[_P_RHEAD]) % d) < pc[_P_RSIZE]
    in_w = ((iota - pc[_P_WHEAD]) % d) < pc[_P_WSIZE]
    out = list(pc)
    out[_P_RDUE] = jnp.where(in_r, pc[_P_RDUE] - sweeps, 0)
    out[_P_WDUE] = jnp.where(in_w, pc[_P_WDUE] - sweeps, 0)
    return tuple(out)


def _build_program(plan: _Plan, resumable: bool = False) -> Callable:
    """One jitted function for the whole graph.

    carry = (chans, states, mmaps, ports, fires, progress, sweeps,
    maxocc); one while_loop iteration is one *sweep*: every task instance
    gets one guarded chance to fire (:func:`_sweep_guards`,
    :func:`_fire_tasks`), then every async port gets one service step.
    Each channel's occupancy highwater is sampled after every firing.
    The loop runs until every task exhausted its firing budget and every
    port drained its in-flight window, or a full sweep made no progress
    (the compiled analogue of the engines' deadlock detection).

    Each async port is a fixed-``depth`` latency queue: the service step
    accepts queued requests issue-ahead (up to ``depth`` outstanding per
    direction), stamps them due ``latency`` sweeps ahead, and delivers
    due responses in per-port FIFO order — deferring, never dropping,
    when the response ring is full.  That is exactly the simulator's
    ``AsyncMMap.pump`` contract, so a port-using graph keeps its
    bit-identical coroutine twin.

    With ``resumable=True`` the program instead takes the full channel
    and port state, the firing counters and a sweep budget as inputs and
    returns the complete carry: ``program(states0, mmaps0, chans0,
    ports0, fires0, max_sweeps)`` runs at most ``max_sweeps`` sweeps and
    hands back ``(chans, states, mmaps, ports, fires, progress, sweeps,
    maxocc, sizes)`` — the ``lax.while_loop`` carry *is* the snapshot,
    which is how the recovery subsystem (:mod:`repro.ft.recovery`)
    checkpoints compiled runs between carry sweeps.  In-flight port
    requests stamp their due sweep against the *chunk-local* sweep
    counter, so before returning, every latency-queue due entry is
    rebased to "sweeps remaining" (``due - sweeps`` for in-use slots) —
    a snapshot restored into a fresh chunk replays delivery timing
    exactly.  Both variants trace the identical sweep body, so a chunked
    resumable run lands on the same fires — and therefore bit-identical
    channel/mmap/port contents — as one uninterrupted program."""
    tables = _sweep_tables(plan)
    caps = tables.caps

    def _service_ports(chans, ports, sweeps):
        """One per-sweep service step for every port: deliver due
        responses (FIFO, reads then writes), then accept queued requests
        into freed window slots (reads then writes) — the order
        ``AsyncMMap.pump`` re-pumps after each delivery."""
        chans = list(chans)
        ports = list(ports)
        activity = jnp.zeros((), jnp.bool_)
        waiting = jnp.zeros((), jnp.bool_)
        for pi, port in enumerate(plan.ports):
            d, lat = port.depth, port.latency
            ra, rd, wa, wd, wr = plan.port_chan_ids[pi]
            (data, r_addr, r_due, r_head, r_size,
             w_addr, w_due, w_val, w_head, w_size,
             acc_r, del_r, acc_w, del_w, max_r, max_w) = ports[pi]
            nrow = data.shape[0]
            # deliver due reads (up to ``depth`` per sweep, as response
            # ring space allows)
            rd_buf, rd_head, rd_size = chans[rd]
            for _ in range(d):
                can = ((r_size > 0) & (r_due[r_head] <= sweeps)
                       & (rd_size < caps[rd]))
                addr = jnp.clip(r_addr[r_head], 0, nrow - 1)
                slot = (rd_head + rd_size) % caps[rd]
                rd_buf = rd_buf.at[slot].set(
                    jnp.where(can, data[addr], rd_buf[slot]))
                rd_size = rd_size + can.astype(jnp.int32)
                r_head = jnp.where(can, (r_head + 1) % d, r_head)
                r_size = r_size - can.astype(jnp.int32)
                del_r = del_r + can.astype(jnp.int32)
                activity = activity | can
            chans[rd] = (rd_buf, rd_head, rd_size)
            # deliver due writes
            wr_buf, wr_head, wr_size = chans[wr]
            for _ in range(d):
                can = ((w_size > 0) & (w_due[w_head] <= sweeps)
                       & (wr_size < caps[wr]))
                addr = jnp.clip(w_addr[w_head], 0, nrow - 1)
                data = data.at[addr].set(
                    jnp.where(can, w_val[w_head], data[addr]))
                slot = (wr_head + wr_size) % caps[wr]
                wr_buf = wr_buf.at[slot].set(
                    jnp.where(can, True, wr_buf[slot]))
                wr_size = wr_size + can.astype(jnp.int32)
                w_head = jnp.where(can, (w_head + 1) % d, w_head)
                w_size = w_size - can.astype(jnp.int32)
                del_w = del_w + can.astype(jnp.int32)
                activity = activity | can
            chans[wr] = (wr_buf, wr_head, wr_size)
            # accept queued reads into the in-flight window
            ra_buf, ra_head, ra_size = chans[ra]
            for _ in range(d):
                can = (ra_size > 0) & (r_size < d)
                addr = ra_buf[ra_head]
                ra_head = jnp.where(can, (ra_head + 1) % caps[ra], ra_head)
                ra_size = ra_size - can.astype(jnp.int32)
                slot = (r_head + r_size) % d
                r_addr = r_addr.at[slot].set(
                    jnp.where(can, addr, r_addr[slot]))
                r_due = r_due.at[slot].set(
                    jnp.where(can, sweeps + lat, r_due[slot]))
                r_size = r_size + can.astype(jnp.int32)
                acc_r = acc_r + can.astype(jnp.int32)
                activity = activity | can
            chans[ra] = (ra_buf, ra_head, ra_size)
            max_r = jnp.maximum(max_r, r_size)
            # accept queued writes (need an address AND a value token)
            wa_buf, wa_head, wa_size = chans[wa]
            wd_buf, wd_head, wd_size = chans[wd]
            for _ in range(d):
                can = (wa_size > 0) & (wd_size > 0) & (w_size < d)
                addr = wa_buf[wa_head]
                val = wd_buf[wd_head]
                wa_head = jnp.where(can, (wa_head + 1) % caps[wa], wa_head)
                wa_size = wa_size - can.astype(jnp.int32)
                wd_head = jnp.where(can, (wd_head + 1) % caps[wd], wd_head)
                wd_size = wd_size - can.astype(jnp.int32)
                slot = (w_head + w_size) % d
                w_addr = w_addr.at[slot].set(
                    jnp.where(can, addr, w_addr[slot]))
                w_due = w_due.at[slot].set(
                    jnp.where(can, sweeps + lat, w_due[slot]))
                w_val = w_val.at[slot].set(
                    jnp.where(can, val, w_val[slot]))
                w_size = w_size + can.astype(jnp.int32)
                acc_w = acc_w + can.astype(jnp.int32)
                activity = activity | can
            chans[wa] = (wa_buf, wa_head, wa_size)
            chans[wd] = (wd_buf, wd_head, wd_size)
            max_w = jnp.maximum(max_w, w_size)
            # liveness: an in-flight request due in the future is progress
            # pending — keep sweeping (the compiled analogue of the
            # simulators fast-forwarding the clock to the next delivery)
            iota = jnp.arange(d, dtype=jnp.int32)
            waiting = waiting | jnp.any(
                (iota < r_size) & (r_due[(r_head + iota) % d] > sweeps))
            waiting = waiting | jnp.any(
                (iota < w_size) & (w_due[(w_head + iota) % d] > sweeps))
            ports[pi] = (data, r_addr, r_due, r_head, r_size,
                         w_addr, w_due, w_val, w_head, w_size,
                         acc_r, del_r, acc_w, del_w, max_r, max_w)
        return chans, tuple(ports), activity, waiting

    def _run_loop(chans0, states0, mmaps0, ports0, fires0, budget):
        totals_v = jnp.asarray(tables.totals)
        maxocc0 = jnp.zeros((max(len(caps), 1),), jnp.int32)

        def cond(carry):
            _, _, _, ports, fires, progress, sweeps, _ = carry
            pending = jnp.zeros((), jnp.bool_)
            for p in ports:
                pending = pending | (p[_P_RSIZE] > 0) | (p[_P_WSIZE] > 0)
            live = progress & (jnp.any(fires < totals_v) | pending)
            if budget is not None:
                live = live & (sweeps < budget)
            return live

        def body(carry):
            chans, states, mmaps, ports, fires, _, sweeps, maxocc = carry
            chans = list(chans)
            states = list(states)
            mmaps = list(mmaps)
            phase_vec, live = _sweep_phases(tables, fires, totals_v)
            fire_vec = _sweep_guards(tables, chans, phase_vec, live,
                                     plan.ring_impl)[0]
            for tp in _fire_tasks(plan, fire_vec, phase_vec, chans, states,
                                  mmaps):
                if tp.chan_ids:
                    # occupancy highwater sampled after every firing (a
                    # sweep-boundary sample would always see drained FIFOs)
                    maxocc = maxocc.at[jnp.asarray(tp.chan_ids)].max(
                        jnp.stack([chans[ci][2] for ci in tp.chan_ids]))
            fires = fires + fire_vec.astype(jnp.int32)
            fired_any = jnp.any(fire_vec)
            if plan.ports:
                chans, ports, activity, waiting = _service_ports(
                    chans, ports, sweeps)
                progress = fired_any | activity | waiting
                maxocc = jnp.maximum(maxocc, _ring_sizes(chans))
            else:
                progress = fired_any
            return (tuple(chans), tuple(states), tuple(mmaps), ports,
                    fires, progress, sweeps + 1, maxocc)

        carry0 = (chans0, tuple(states0), tuple(mmaps0), tuple(ports0),
                  fires0, jnp.ones((), jnp.bool_),
                  jnp.zeros((), jnp.int32), maxocc0)
        return jax.lax.while_loop(cond, body, carry0)

    if resumable:
        def program(states0: tuple, mmaps0: tuple, chans0: tuple,
                    ports0: tuple, fires0, max_sweeps):
            chans, states, mmaps, ports, fires, progress, sweeps, maxocc \
                = _run_loop(tuple(tuple(c) for c in chans0), states0,
                            mmaps0, tuple(tuple(p) for p in ports0),
                            jnp.asarray(fires0, jnp.int32),
                            jnp.asarray(max_sweeps, jnp.int32))
            ports = tuple(_rebase_port_dues(p, sweeps) for p in ports)
            return (tuple(chans), tuple(states), tuple(mmaps), ports,
                    fires, progress, sweeps, maxocc, _ring_sizes(chans))
    else:
        def program(states0: tuple, mmaps0: tuple, ports0: tuple):
            chans0 = _empty_rings(plan)
            fires0 = jnp.zeros((len(plan.tasks),), jnp.int32)
            chans, states, mmaps, ports, fires, _, sweeps, maxocc = \
                _run_loop(chans0, states0, mmaps0, ports0, fires0, None)
            return (tuple(mmaps), ports, fires, sweeps, maxocc,
                    _ring_sizes(chans))

    return program


def _build_partitioned_program(plan: _Plan, owners, mesh,
                               axis: str = "dev") -> Callable:
    """The multi-device twin of :func:`_build_program`: one
    ``shard_map`` whose per-device body runs the same sweep, firing only
    the tasks ``owners`` assigns to that device (the ownership predicate
    of :func:`_fire_tasks`), and ends each sweep with the head/size
    resynchronisation and the cut channels' exchange below.  A plan
    with async ports is refused before this is built.

    The partition invariant is *sweep-synchronous SPMD*: at every sweep
    start, all devices agree on every channel's head/size and every
    task's firing count, and agree on the buffer contents of every
    channel they might touch.  The sweep body maintains it with zero
    mid-sweep communication:

    * **guards/fires are replicated by construction** — ``eval_guards``
      reads only head/size vectors, which every device carries and
      advances identically, so the fire vector (and hence phase indices
      and the loop condition) needs no collective;
    * **a device executes only its own tasks** (``lax.cond`` on
      ``owner == axis_index``), paying compute only for its partition;
    * **head/size are re-synchronized by arithmetic, not exchange**: a
      firing's pops/pushes move head/size by the *static* per-phase
      token counts, so sweep-end metadata is recomputed globally as
      ``head += Σ fired·reads``, ``size += Σ fired·(writes - reads)``
      and overwritten on every device — for locally-fired tasks this
      lands exactly where the local ring ops already did;
    * **cut channels ship their ring once per sweep**: pops never
      mutate buffer contents and pushes land at ``(head+size+i) % cap``
      — invariant under the consumer's concurrent pops — so sending the
      producer's post-push buffer to the consumer via ``lax.ppermute``
      (and adopting it with a ``where`` on the receiver) restores full
      agreement.  Intra-device channels never hit the interconnect.

    Under this invariant the partitioned run executes the identical
    firing schedule, pops the identical values and writes the identical
    mmap cells as the single-device lowering — bit-identical outputs.
    Channel ``max_occupancy`` is sweep-granular here, sampled from the
    resynchronised sweep-end sizes: mid-sweep, a device's ring sizes
    reflect only its own tasks' firings.

    Outputs are stacked across the mesh axis (every leaf gains a
    leading device dimension); the caller fetches only the authoritative
    shard — the writer task's owner's for each written mmap, row 0's for
    the replicated fires/sweeps/maxocc/sizes — and never an unwritten
    mmap.
    """
    from .floorplan import channel_endpoints
    tables = _sweep_tables(plan)
    n_chans = len(plan.channels)
    owners_np = np.asarray(owners, np.int32)
    caps_np = np.asarray(tables.caps, np.int32) if n_chans else \
        np.zeros((1,), np.int32)
    # cut edges: (channel, producer device, consumer device)
    cuts = [(ci, int(owners_np[p]), int(owners_np[c]))
            for ci, (p, c) in enumerate(channel_endpoints(plan))
            if p >= 0 and c >= 0 and owners_np[p] != owners_np[c]]

    def device_body(states0, mmaps0):
        me = jax.lax.axis_index(axis)
        owners_v = jnp.asarray(owners_np)
        totals_v = jnp.asarray(tables.totals)
        caps_v = jnp.asarray(caps_np)
        chans0 = _empty_rings(plan)
        fires0 = jnp.zeros((len(plan.tasks),), jnp.int32)
        maxocc0 = jnp.zeros((max(n_chans, 1),), jnp.int32)

        def cond(carry):
            _, _, _, fires, progress, sweeps, _ = carry
            return progress & jnp.any(fires < totals_v)

        def body(carry):
            chans, states, mmaps, fires, _, sweeps, maxocc = carry
            chans = list(chans)
            states = list(states)
            mmaps = list(mmaps)
            phase_vec, live = _sweep_phases(tables, fires, totals_v)
            if n_chans:
                heads0 = jnp.stack([c[1] for c in chans])
            fire_vec, sizes0, nr, nw = _sweep_guards(
                tables, chans, phase_vec, live, plan.ring_impl)
            for _ in _fire_tasks(plan, fire_vec, phase_vec, chans, states,
                                 mmaps, owns=lambda ti: owners_v[ti] == me):
                pass
            if n_chans:
                fv = fire_vec.astype(jnp.int32)
                delta_r = jnp.sum(fv[:, None] * nr, axis=0)
                delta_w = jnp.sum(fv[:, None] * nw, axis=0)
                new_heads = (heads0 + delta_r) % jnp.maximum(caps_v, 1)
                new_sizes = sizes0 + delta_w - delta_r
                for ci, src, dst in cuts:
                    buf = chans[ci][0]
                    recv = jax.lax.ppermute(buf, axis, [(src, dst)])
                    chans[ci] = (jnp.where(me == dst, recv, buf),) \
                        + chans[ci][1:]
                chans = [(chans[ci][0], new_heads[ci], new_sizes[ci])
                         for ci in range(n_chans)]
                maxocc = jnp.maximum(maxocc, new_sizes)
            fires = fires + fire_vec.astype(jnp.int32)
            return (tuple(chans), tuple(states), tuple(mmaps), fires,
                    jnp.any(fire_vec), sweeps + 1, maxocc)

        carry0 = (chans0, tuple(states0), tuple(mmaps0), fires0,
                  jnp.ones((), jnp.bool_), jnp.zeros((), jnp.int32),
                  maxocc0)
        chans, states, mmaps, fires, _, sweeps, maxocc = \
            jax.lax.while_loop(cond, body, carry0)
        out = (tuple(mmaps), fires, sweeps, maxocc, _ring_sizes(chans))
        # every leaf gains a leading device axis; the concatenated
        # global view lets the host pick the authoritative row
        return jax.tree.map(lambda x: jnp.asarray(x)[None], out)

    from jax.sharding import PartitionSpec as _P

    def program(states0: tuple, mmaps0: tuple):
        return jax.shard_map(device_body, mesh=mesh,
                             in_specs=(_P(), _P()), out_specs=_P(axis),
                             check_vma=False)(states0, mmaps0)

    return program


def _mmap_writers(plan: _Plan) -> dict:
    """``{mmap index: index of the task that stores to it}``; ``_lower``'s
    one-writer rule makes the task unique."""
    return {mi: ti for ti, tp in enumerate(plan.tasks)
            for ph in tp.phases for mi in ph.mmap_stores}


def _row_shard(stacked: jax.Array, row: int) -> jax.Array:
    """The device-resident shard of a mesh-stacked output that holds
    ``row`` of its leading device axis (found by the shard's index, not
    by device order), shaped ``(1, ...)``."""
    return next(s.data for s in stacked.addressable_shards
                if (s.index[0].start or 0) == row)


# ---------------------------------------------------------------------------
# the fourth engine
# ---------------------------------------------------------------------------

class CompiledEngine(EngineBase):
    """Whole-graph synthesis engine (the compiled twin of the simulators).

    ``run(top, *args)`` elaborates the graph by executing the *wiring*
    bodies (parents that instantiate channels and invoke children) and
    recording every :class:`StepTask` leaf, then lowers the entire graph
    into one jitted XLA program through the compile cache, executes it,
    writes mmap results back into the host buffers, and returns a real
    :class:`SimReport` (fires, token counts, occupancy highwater marks,
    sweep count as ``switches``).

    A graph outside the synthesizable subset raises
    :class:`SynthesisError` naming the offending task/channel; a lowered
    graph that stalls (a genuine dataflow deadlock) returns
    ``ok=False`` with the blocked tasks listed, mirroring the simulation
    engines.
    """

    name = "compiled"

    def __init__(self, track_stats: bool = False, cache: Any = None,
                 ring_impl: Optional[str] = None, mesh: Any = None,
                 placement: Any = None, **kw):
        super().__init__(track_stats, **kw)
        self.cache = cache          # CompileCache | None=default | False=off
        # interconnect kernel backend: "pallas" | "interpret" | "xla";
        # None defers to $REPRO_RING_IMPL / the backend default
        self.ring_impl = ring_impl
        # multi-device floorplan: mesh = device count (int) or a 1-D
        # jax.sharding.Mesh; placement = manual {task_name: device}
        # overrides (partial pins OK) or a floorplan.Placement to reuse
        self.mesh = mesh
        self.placement = placement
        self._cur: Optional[TaskInstance] = None
        # post-run introspection (tests / benchmarks)
        self.compile_source: Optional[str] = None
        self.compile_key: Optional[str] = None
        self.compile_s = 0.0            # executable resolve (compile/load)
        self.ring_impl_used: Optional[str] = None
        self.n_sweeps = 0
        self.writeback_bytes = 0        # mmap/port results fetched to host
        self.n_phase_traces = 0         # _lower's jax.eval_shape traces
        self.lower_source = None        # "traced" | "memory" | None
        self.n_digests = 0              # definition digests of the last run
        self.placement_used = None      # floorplan.Placement after a run
        self.partition_source = None    # "partitioned" | "memo" | None

    # -- runtime protocol: any live stream op means "not step form" ----------
    def _refuse(self, op: str):
        name = self._cur.name if self._cur is not None else "<top>"
        raise SynthesisError(
            f"task {name!r} performed a runtime stream operation ({op}) "
            f"during synthesis elaboration: it is not in step-function "
            f"form.  CompiledEngine only lowers graphs whose leaf tasks "
            f"are StepTask definitions (availability-routed designs using "
            f"peek/select stay on the simulation engines); see "
            f"docs/synthesis.md")

    def wait(self, chan, side):
        self._refuse("wait")

    def wait_many(self, keys):
        self._refuse("select")

    def push(self, chan, tok):
        self._refuse("write")

    def pop(self, chan):
        self._refuse("read")

    def push_burst(self, chan, toks):
        self._refuse("write_burst")

    def pop_burst(self, chan, n):
        self._refuse("read_burst")

    def schedule_async(self, delay, deliver):
        # compiled runs service async_mmap ports inside the lowered
        # program (the latency queue in the while_loop carry); a live
        # delivery callback during elaboration means a *wiring body*
        # performed memory I/O, which is not step-function form
        name = self._cur.name if self._cur is not None else "<top>"
        raise SynthesisError(
            f"task {name!r} issued an async_mmap request during synthesis "
            f"elaboration: memory I/O belongs in StepTask phase bodies "
            f"(where it lowers to the compiled latency queue), not in "
            f"wiring bodies; see docs/synthesis.md")

    # -- elaboration ---------------------------------------------------------
    def spawn(self, inst: TaskInstance) -> None:
        self._register(inst)
        if getattr(inst.fn, "is_step_task", False):
            return                  # recorded; lowered later, never executed
        self._exec(inst)            # wiring body runs inline

    def join(self, insts: list[TaskInstance]) -> None:
        for i in insts:
            if i.state == "failed" and i.error is not None:
                raise i.error

    def _exec(self, inst: TaskInstance) -> Any:
        prev = self._cur
        self._cur = inst
        set_context(self, inst)
        depth = builder_stack_depth()
        inst.state = "running"
        try:
            a, k = bind_streams(inst)
            out = inst.fn(*a, **k)
            join_pending_builders(depth)
            inst.state = "finished"
            return out
        except BaseException as e:
            inst.state = "failed"
            inst.error = e
            raise
        finally:
            self._cur = prev
            set_context(self, prev)

    # -- lowering ------------------------------------------------------------
    def _lower(self) -> tuple[_Plan, Any]:
        """Plan the graph: bind each StepTask's ports, count each phase's
        I/O rates (a ``jax.eval_shape`` per phase, or the compile cache's
        memo of a structure lowered before), then check the whole."""
        step_insts = [i for i in self.instances
                      if getattr(i.fn, "is_step_task", False)]
        if not step_insts:
            raise SynthesisError(
                "graph contains no step-function tasks; CompiledEngine "
                "lowers StepTask leaves (see docs/synthesis.md)")
        plan = _Plan()
        plan.ring_impl = resolve_impl("ring", RING_ENV, RING_CHOICES,
                                      fallback="xla",
                                      impl=getattr(self, "ring_impl", None))
        self.ring_impl_used = plan.ring_impl
        bound = []
        for inst in step_insts:
            a, k = bind_streams(inst)
            bound.append((inst, a, k))
        for inst, a, k in bound:
            if inst.fn.close_outputs:
                raise SynthesisError(
                    f"task {inst.name!r} closes its outputs (EoT) after "
                    f"its last firing; EoT-terminated streams are outside "
                    f"the synthesizable subset — downstream consumers "
                    f"must terminate by firing count instead")
            tp = _TaskPlan(inst=inst, task=inst.fn)
            tp.t_args = tuple(_build_template(x, plan, tp) for x in a)
            tp.t_kwargs = {key: _build_template(x, plan, tp)
                           for key, x in k.items()}
            tp.state0 = _materialize_state(inst.fn.init)
            plan.tasks.append(tp)
        for c in plan.channels:
            if c.shape is None or not isinstance(c.dtype, np.dtype):
                raise SynthesisError(
                    f"channel {c.name!r} has no declared element spec; "
                    f"synthesis sizes its ring buffer from "
                    f"Channel(dtype=..., shape=...)")
        graph = extract_graph(self)
        plan.structural_hash = graph.structural_hash()
        cc = self._store()
        lkey = _lowering_key(plan.structural_hash, plan.ring_impl)
        memo = cc.lowering_get(lkey) if cc is not None else None
        self.lower_source = "traced" if memo is None else "memory"
        for ti, tp in enumerate(plan.tasks):
            for pi, (label, fn, count) in enumerate(tp.task.phases()):
                tp.phases.append(
                    _count_phase(plan, tp, label, fn, count) if memo is None
                    else _remembered_phase(plan, tp, label, fn, count,
                                           memo[ti][pi]))
            if not tp.phases:
                raise SynthesisError(
                    f"task {tp.inst.name!r} has zero total firings")
        # async ports: record each port's direction from its member-channel
        # traffic, and refuse read+write ports — a read racing an in-flight
        # write to the same buffer resolves by response timing, which the
        # sweep schedule must not be allowed to decide
        for tp in plan.tasks:
            for ph in tp.phases:
                for ci in list(ph.reads) + list(ph.writes):
                    c = plan.channels[ci]
                    pi = plan._port_idx.get(id(c.iface)) \
                        if c.iface is not None else None
                    if pi is None:
                        continue
                    p = plan.ports[pi]
                    if c is p._raddr or c is p._rdata:
                        plan.port_dirs[pi].add("read")
                    else:
                        plan.port_dirs[pi].add("write")
        for pi, dirs in enumerate(plan.port_dirs):
            if dirs >= {"read", "write"}:
                raise SynthesisError(
                    f"async_mmap {plan.ports[pi].name!r} is both read and "
                    f"written in the synthesized graph: read-after-write "
                    f"through an async port depends on response timing; "
                    f"use one port per direction (or route the value "
                    f"through a channel)")
        # schedule-independence: an mmap written by one task and read by
        # another would make results depend on sweep order — refuse
        readers: dict[int, set] = {}
        writers: dict[int, set] = {}
        for tp in plan.tasks:
            for ph in tp.phases:
                for mi in ph.mmap_loads:
                    readers.setdefault(mi, set()).add(tp.inst.name)
                for mi in ph.mmap_stores:
                    writers.setdefault(mi, set()).add(tp.inst.name)
        for mi, ws in writers.items():
            m = plan.mmaps[mi]
            if len(ws) > 1:
                raise SynthesisError(
                    f"mmap {m.name!r} has multiple writers {sorted(ws)} "
                    f"(one-writer rule)")
            others = readers.get(mi, set()) - ws
            if others:
                raise SynthesisError(
                    f"mmap {m.name!r} is written by {sorted(ws)} and read "
                    f"by {sorted(others)}: cross-task read-after-write "
                    f"through memory is schedule-dependent; route the "
                    f"value through a channel instead")
        try:
            graph.validate()
        except GraphValidationError as e:
            raise SynthesisError(f"graph failed validation: {e}") from e
        self.n_phase_traces = plan.n_phase_traces
        if cc is not None and memo is None:
            cc.lowering_put(lkey, tuple(
                tuple(_positional_counts(tp, ph) for ph in tp.phases)
                for tp in plan.tasks))
        return plan, graph

    def _store(self):
        """The compile cache this engine uses, or None with
        ``cache=False``."""
        if self.cache is False:
            return None
        return self.cache if self.cache is not None else default_cache()

    def _cache_key(self, structural_hash: str, args: tuple,
                   ring_impl: str = "xla", extra: str = "") -> str:
        h = hashlib.sha256()
        h.update(structural_hash.encode())
        h.update(_stable_repr(aval_signature(args, {})).encode())
        h.update(f"jax:{jax.__version__}:{toolchain_tag()}:"
                 f"{SYNTH_SCHEMA}:ring={ring_impl}:{extra}".encode())
        return h.hexdigest()

    # -- run -----------------------------------------------------------------
    def _exec_root(self, top: Callable, args: tuple, kwargs: dict):
        """Execute the wiring bodies under a fresh root instance; the
        caller owns ``clear_context()``."""
        root = TaskInstance(top, args, kwargs, detach=False, parent=None,
                            name=getattr(top, "__name__", "top"))
        set_context(self, None)
        self._register(root)
        return self._exec(root)

    def _elaborate(self, top: Callable, *args, **kwargs):
        """Execute the wiring bodies and lower to a plan, without running
        the compiled program.  Returns ``(plan, graph, result)`` — the
        shared front half of :meth:`run`, also used by the recovery
        subsystem to build its chunk schedule.  The caller owns
        ``clear_context()``."""
        result = self._exec_root(top, args, kwargs)
        plan, graph = self._lower()
        return plan, graph, result

    def run(self, top: Callable, *args, **kwargs) -> SimReport:
        """Elaborate, lower, key, resolve, copy in, execute, write back.

        On a mesh, :meth:`_partition` places the tasks and lowers the
        partitioned program after the plan is lowered, and
        :meth:`_fetch_rows` brings back only the shards the host needs;
        every other stage is the same on both paths.

        Each stage runs under a ``jax.profiler.TraceAnnotation`` named
        ``compiled.<stage>``, nested in one ``compiled.run``, so that a
        profiler trace puts the device's idle time down to a stage; the
        annotations record nothing unless a trace is active."""
        t0 = time.perf_counter()
        self.writeback_bytes = 0
        with _span("compiled.run"):
            try:
                with _span("compiled.elaborate"):
                    result = self._exec_root(top, args, kwargs)
                with _span("compiled.lower"):
                    plan, graph = self._lower()
                    if self.mesh is None:
                        program = _build_program(plan)
                owners, extra = None, ""
                if self.mesh is not None:
                    program, owners, extra = self._partition(plan, graph)
                self.n_digests = graph.n_digests
                with _span("compiled.copy_in"):
                    inputs = (tuple(tp.state0 for tp in plan.tasks),
                              tuple(jnp.asarray(m.data) for m in plan.mmaps))
                    if owners is None:
                        inputs += (
                            tuple(_port_carry0(p) for p in plan.ports),)
                with _span("compiled.key"):
                    key = self._cache_key(plan.structural_hash, inputs,
                                          plan.ring_impl, extra)
                with _span("compiled.resolve"):
                    exe = self._resolve(program, inputs, key)
                with _span("compiled.execute"):
                    out = jax.block_until_ready(exe(*inputs))
                with _span("compiled.writeback"):
                    if owners is not None:
                        out = self._fetch_rows(plan, owners, out)
                    return self._finish(plan, *out, result, t0)
            finally:
                clear_context()

    def _resolve(self, program: Callable, args: tuple, key: str):
        """The executable for ``program`` through the compile cache (or a
        plain compile with ``cache=False``); records its source, key and
        the seconds it took."""
        t0 = time.perf_counter()
        cc = self._store()
        if cc is None:
            exe = jax.jit(program).lower(*args).compile()
            source = "compiled"
        else:
            exe, source = cc.compile_cached(program, args, key=key)
        self.compile_source = source
        self.compile_key = key
        self.compile_s = time.perf_counter() - t0
        return exe

    def _resolve_mesh(self):
        """``self.mesh`` as a validated 1-D Mesh: an int means "the
        first N visible devices on a fresh axis" (see
        ``distributed.sharding.device_mesh``)."""
        from jax.sharding import Mesh
        if isinstance(self.mesh, Mesh):
            mesh = self.mesh
            if len(mesh.axis_names) != 1:
                raise SynthesisError(
                    f"partitioned synthesis takes a 1-D mesh; got axes "
                    f"{mesh.axis_names!r} — task graphs are placed along "
                    f"one device axis")
            return mesh
        from ..distributed.sharding import device_mesh
        return device_mesh(int(self.mesh))

    def _partition(self, plan: _Plan, graph) -> tuple:
        """The mesh's part of lowering: place the tasks (a placement to
        reuse, or the floorplanner's) and build the partitioned program.
        Returns ``(program, owners, extra)``, ``extra`` being the mesh
        and the owners as the executable's key holds them."""
        from .floorplan import Placement, plan_placement
        mesh = self._resolve_mesh()
        axis = mesh.axis_names[0]
        n_dev = mesh.devices.size
        if plan.ports:
            users = sorted({tp.inst.name for tp in plan.tasks
                            if tp.port_ids})
            raise SynthesisError(
                f"partitioned synthesis does not cover async_mmap ports "
                f"yet: port(s) {[p.name for p in plan.ports]} bound by "
                f"task(s) {users} — the latency queue is serviced by one "
                f"device's sweep and has no cut protocol; run the graph "
                f"single-device (mesh=None) or route the memory traffic "
                f"through channels")
        with _span("compiled.place"):
            if isinstance(self.placement, Placement):
                placement = self.placement
                if placement.n_devices != n_dev or \
                        len(placement.owners) != len(plan.tasks):
                    raise SynthesisError(
                        f"placement reuse mismatch: placement is for "
                        f"{placement.n_devices} devices / "
                        f"{len(placement.owners)} tasks, graph has "
                        f"{len(plan.tasks)} tasks on a {n_dev}-device mesh")
            else:
                placement = plan_placement(
                    plan, graph, n_dev, overrides=self.placement,
                    cache=self.cache)
        self.placement_used = placement
        self.partition_source = placement.source
        owners = np.asarray(placement.owners, np.int32)

        with _span("compiled.lower"):
            program = _build_partitioned_program(plan, owners, mesh, axis)
        return program, owners, \
            f"mesh={axis}:{n_dev}:owners={owners.tolist()}"

    def _fetch_rows(self, plan: _Plan, owners, out) -> tuple:
        """The partitioned program's results on the host, from outputs
        stacked over the mesh: the writer's owner's row of each written
        mmap (``None`` for the rest, which stay on the device and which
        ``_writeback`` never reads) and row 0 of the replicated fires,
        sweeps, maxocc and sizes, in one ``jax.device_get`` so that the
        chips' transfers overlap.  Shaped as the one-device program's
        outputs, with no ports."""
        mm_st, *replicated = out
        writer_of = {mi: int(owners[ti])
                     for mi, ti in _mmap_writers(plan).items()}
        written = sorted(writer_of)
        rows = jax.device_get(
            [_row_shard(mm_st[mi], writer_of[mi]) for mi in written]
            + [_row_shard(x, 0) for x in replicated])
        rows = [r[0] for r in rows]
        mm_final = [None] * len(mm_st)
        for mi, row in zip(written, rows):
            mm_final[mi] = row
            self.writeback_bytes += row.nbytes
        return (tuple(mm_final), (), *rows[len(written):])

    def _finish(self, plan: _Plan, mm_final, ports_final, fires, sweeps,
                maxocc, sizes, result, t0: float) -> SimReport:
        """Shared back half of a compiled run: write mmaps and ports back
        to host, fill stats, diagnose stalls, build the report."""
        self._fill_port_stats(plan, ports_final)
        fires = np.asarray(fires)
        maxocc = np.asarray(maxocc)
        sizes = np.asarray(sizes)
        self.n_sweeps = self.switches = int(sweeps)
        self._writeback(plan, mm_final, ports_final)
        self._fill_stats(plan, fires, maxocc)
        totals = np.asarray([tp.total for tp in plan.tasks], np.int32)
        stuck = bool(np.any(fires < totals))
        for tp, f, tot in zip(plan.tasks, fires, totals):
            tp.inst.state = "finished" if f >= tot else "blocked"
        err = None
        if stuck:
            blocked = [tp.inst.name for tp, f, tot
                       in zip(plan.tasks, fires, totals) if f < tot]
            occ = {c.name: int(s)
                   for c, s in zip(plan.channels, sizes)}
            err = (f"synthesized graph stalled after {self.switches} "
                   f"sweeps; blocked tasks: {blocked}; channel "
                   f"occupancy at stall: {occ}")
            # unified diagnostic (docs/robustness.md): the same
            # structured payload the simulation engines attach
            self._deadlock_report = DeadlockReport(
                engine=self.name, reason="stall",
                blocked=[(n, "stalled") for n in blocked],
                occupancy=occ, clock=self.switches,
                switches=self.switches,
                wall_s=time.perf_counter() - t0)
        return self._report(not stuck, time.perf_counter() - t0, err,
                            result)

    def _writeback(self, plan: _Plan, mm_final: tuple,
                   ports_final: tuple) -> None:
        """Copy device results back into the host buffers of the written
        mmaps and ports, so the same ``check()`` that verifies a
        simulation run verifies the compiled run."""
        for mi in sorted(_mmap_writers(plan)):
            self._copy_back(plan.mmaps[mi], mm_final[mi])
        for p, dirs, pc in zip(plan.ports, plan.port_dirs, ports_final):
            if "write" in dirs:
                self._copy_back(p, pc[_P_DATA])

    def _copy_back(self, target, x) -> None:
        """``x`` into ``target.data``, in place where that is a host
        array; a device array's bytes count in ``writeback_bytes``."""
        out = np.asarray(x)
        if isinstance(x, jax.Array):
            self.writeback_bytes += out.nbytes
        if isinstance(target.data, np.ndarray):
            np.copyto(target.data, out)
        else:
            target.data = out

    def _fill_port_stats(self, plan: _Plan, ports_final: tuple) -> None:
        """Fill each port's always-on request counters from the compiled
        carry, so ``SimReport.interfaces`` carries real numbers — the
        compiled twin of ``AsyncMMap.pump``'s bookkeeping."""
        for p, pc in zip(plan.ports, ports_final):
            p.read_reqs = int(pc[_P_ACC_R])
            p.read_resps = int(pc[_P_DEL_R])
            p.write_reqs = int(pc[_P_ACC_W])
            p.write_resps = int(pc[_P_DEL_W])
            p.max_outstanding_reads = int(pc[_P_MAX_R])
            p.max_outstanding_writes = int(pc[_P_MAX_W])
            # service-side member-channel totals (the task side is
            # reconstructed from firing counters in _fill_stats)
            p._raddr.total_read += p.read_reqs
            p._rdata.total_written += p.read_resps
            p._waddr.total_read += p.write_reqs
            p._wdata.total_read += p.write_reqs
            p._wresp.total_written += p.write_resps

    def _fill_stats(self, plan: _Plan, fires: np.ndarray,
                    maxocc: np.ndarray) -> None:
        """Reconstruct per-channel token counts and occupancy highwater
        marks from the firing counters — the compiled analogue of the
        simulators' per-push statistics."""
        for tp, f in zip(plan.tasks, fires):
            start = 0
            for ph in tp.phases:
                k = int(np.clip(int(f) - start, 0, ph.count))
                start += ph.count
                for ci, r in ph.reads.items():
                    plan.channels[ci].total_read += r * k
                for ci, w in ph.writes.items():
                    plan.channels[ci].total_written += w * k
                if self.track_stats:
                    for mi, n in ph.mmap_loads.items():
                        plan.mmaps[mi].loads += ph.mmap_load_ops[mi] * k
                        plan.mmaps[mi].load_elems += n * k
                    for mi, n in ph.mmap_stores.items():
                        plan.mmaps[mi].stores += ph.mmap_store_ops[mi] * k
                        plan.mmaps[mi].store_elems += n * k
        for c, occ in zip(plan.channels, maxocc):
            c.max_occupancy = int(occ)


def elaborate_step_graph(top: Callable, *args, **kwargs):
    """Elaborate a step-form graph without executing it.

    Runs the wiring bodies under a throwaway :class:`CompiledEngine` and
    returns ``(plan, graph, result)`` — the lowering plan (task order,
    phase I/O rates, channel/mmap tables), the validated graph IR, and
    the top body's return value.  Raises :class:`SynthesisError` for
    graphs outside the synthesizable subset.  This is the entry point
    the recovery subsystem uses to derive its abstract sweep schedule:
    the plan it returns is byte-for-byte the one ``CompiledEngine.run``
    would lower, so chunk quotas computed from it apply to every engine.

    NOTE: elaboration *executes the wiring bodies*, which binds channel
    endpoints to the throwaway engine's task instances.  Callers that
    re-run the same channel objects under another engine must reset the
    endpoints first (see ``repro.ft.recovery._reset_endpoints``).
    """
    eng = CompiledEngine()
    try:
        return eng._elaborate(top, *args, **kwargs)
    finally:
        clear_context()


ENGINES["compiled"] = CompiledEngine
