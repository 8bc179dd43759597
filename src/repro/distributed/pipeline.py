"""Pipeline parallelism as a TAPA task graph, lowered to shard_map+ppermute.

This is where the paper's programming model becomes a first-class feature
of the LM framework:

1. The pipeline schedule *is* a task graph — each stage is a task, each
   microbatch hand-off is a bounded channel (capacity = in-flight
   microbatches).  ``schedule_task_graph`` builds it with the Table-2 API
   and the coroutine engine *verifies* it (deadlock-freedom, occupancy
   bounds, schedule length) in milliseconds — the paper's
   fast-correctness-cycle applied to a distributed schedule instead of an
   RTL design (Fig. 2).

2. The verified schedule is then lowered to the TPU: one mesh axis hosts
   the stages, activations move between neighbouring stages with
   ``lax.ppermute`` (the ICI is the channel), and the GPipe time loop is a
   differentiable ``lax.scan`` so ``jax.grad`` runs the *reverse* pipeline
   automatically — backward microbatches flow through the same channels in
   the opposite direction, which is exactly the 1F1B dataflow without
   hand-scheduling it.

The TAPA channel *capacity* maps to the number of microbatches in flight;
the simulation reports ``max_occupancy`` per channel, which must not exceed
what the compiled buffer (one ppermute slot per step) provides — the
property test in tests/test_pipeline.py checks both sides.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import channel, task
from ..core.engines import ENGINES, SimReport


# ---------------------------------------------------------------------------
# 1. the schedule as a TAPA task graph (simulation / verification side)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    channel_capacity: int = 2        # in-flight microbatches per hand-off

    @property
    def bubble_fraction(self) -> float:
        """GPipe bubble: (S-1) / (M + S - 1)."""
        S, M = self.n_stages, self.n_microbatches
        return (S - 1) / (M + S - 1)


def schedule_task_graph(pcfg: PipelineConfig,
                        engine: str = "coroutine",
                        payloads: Optional[list] = None) -> SimReport:
    """Run the pipeline schedule as a task-parallel program.

    Feeder -> Stage_0 -> ... -> Stage_{S-1} -> Collector, every hand-off a
    bounded channel.  Returns the SimReport; ``report.result`` holds the
    microbatch ids in arrival order (must be FIFO) and per-channel
    occupancy statistics ride on the report's channel list.
    """
    S, M = pcfg.n_stages, pcfg.n_microbatches
    payloads = payloads if payloads is not None else list(range(M))

    def Feeder(out):
        for p in payloads:
            out.write(p)
        out.close()

    def Stage(inp, out):
        for p in inp:                 # drain one transaction
            out.write(p)              # unit of work per microbatch
        out.close()

    def Collector(inp, sink: list):
        for p in inp:
            sink.append(p)

    def Top(sink):
        chans = [channel(capacity=pcfg.channel_capacity, name=f"mb{i}")
                 for i in range(S + 1)]
        t = task().invoke(Feeder, chans[0])
        for i in range(S):
            t = t.invoke(Stage, chans[i], chans[i + 1], name=f"stage{i}")
        t.invoke(Collector, chans[S], sink)

    sink: list = []
    # stats on: the whole point of this simulation is verifying channel
    # occupancy against the ppermute buffer bound (max_occupancy below)
    rep = ENGINES[engine](track_stats=True).run(Top, sink)
    rep.result = sink
    return rep


# ---------------------------------------------------------------------------
# 2. the compiled GPipe schedule (shard_map + ppermute)
# ---------------------------------------------------------------------------

def spmd_pipeline(stage_fn: Callable, n_stages: int, n_microbatches: int,
                  axis: str = "stage"):
    """Build the per-device pipeline body (to run inside shard_map).

    ``stage_fn(stage_params, x) -> y`` is one stage's compute; the returned
    function has signature ``(stage_params_local, microbatches) -> outputs``
    where ``microbatches`` is ``[M, mb, ...]`` (replicated across stages)
    and ``outputs`` is ``[M, mb, ...]`` (valid on every stage after the
    final psum-broadcast).

    The time loop is ``lax.scan`` over T = M + S - 1 steps; each step does
    compute then a neighbour ``ppermute`` — exactly one channel slot per
    edge per step, matching the verified task-graph schedule.
    """
    S, M = n_stages, n_microbatches
    T = M + S - 1

    def pipe(stage_params, xs):
        stage = jax.lax.axis_index(axis)
        x0 = jnp.zeros_like(xs[0])

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clamped; garbage beyond M is
            # never written to outputs)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, feed, state)
            out = stage_fn(stage_params, inp)
            # hand off to the next stage over the ICI "channel"
            nxt = jax.lax.ppermute(
                out, axis, [(i, i + 1) for i in range(S - 1)])
            # the last stage retires microbatch t-(S-1)
            widx = t - (S - 1)
            valid = (stage == S - 1) & (widx >= 0)
            cw = jnp.clip(widx, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, cw, 0,
                                               keepdims=False)
            new = jnp.where(valid, out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, new, cw, 0)
            return (nxt, outputs), None

        outputs0 = jnp.zeros((M,) + jax.eval_shape(
            stage_fn, stage_params, x0).shape, x0.dtype)
        (_, outputs), _ = jax.lax.scan(step, (x0, outputs0),
                                       jnp.arange(T))
        # broadcast the last stage's outputs to every stage
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    return pipe


def compile_pipeline(mesh: Mesh, stage_fn: Callable, stacked_params: Any,
                     microbatches: jax.Array, *, axis: str = "stage",
                     cache=None):
    """AOT-compile the shard_mapped GPipe body through the compile cache.

    The cache key is the *user's stage definition* (structural hash — the
    shard_map wrapper's internals would only add noise) plus the digest of
    the schedule builder itself (editing ``spmd_pipeline``'s
    ppermute/rotation logic must dirty cached pipelines), the schedule
    geometry, and the mesh topology.  An unchanged pipeline loads from the
    content-addressed store instead of re-lowering; editing the stage body
    or the schedule dirties exactly this entry.  Returns
    ``(executable, source)``.
    """
    from ..core.compile_cache import default_cache, structural_digest
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    pipe = spmd_pipeline(stage_fn, S, M, axis)
    shmapped = jax.shard_map(
        pipe, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    cc = cache if cache is not None else default_cache()
    return cc.compile_cached(
        shmapped, (stacked_params, microbatches),
        hash_fn=stage_fn,
        extra=("spmd_pipeline", structural_digest(spmd_pipeline),
               axis, int(S), int(M),
               tuple(sorted((k, int(v)) for k, v in mesh.shape.items())),
               tuple(str(d) for d in mesh.devices.flat)))


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stacked_params: Any,
                   microbatches: jax.Array, *, axis: str = "stage",
                   verify: bool = True, cache=False) -> jax.Array:
    """High-level entry: verify the schedule in simulation (C2), then run
    the compiled pipeline on the mesh.

    ``stacked_params``: pytree with a leading [S, ...] stage axis.
    ``microbatches``: [M, mb, ...].  ``cache``: ``False`` traces eagerly
    (the seed behaviour); ``None`` routes the compile through the
    process-default :class:`~repro.core.compile_cache.CompileCache`; a
    cache instance uses that store.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    if verify:
        rep = schedule_task_graph(PipelineConfig(S, M))
        if not rep.ok:
            raise RuntimeError(f"pipeline schedule failed simulation: "
                               f"{rep.error}")
        assert rep.result == list(range(M)), "schedule is not FIFO"

    if cache is not False:
        exe, _ = compile_pipeline(mesh, stage_fn, stacked_params,
                                  microbatches, axis=axis, cache=cache)
        return exe(stacked_params, microbatches)

    pipe = spmd_pipeline(stage_fn, S, M, axis)
    shmapped = jax.shard_map(
        pipe, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    return shmapped(stacked_params, microbatches)


def pipeline_loss_fn(mesh: Mesh, stage_fn: Callable, loss_tail: Callable,
                     *, axis: str = "stage"):
    """Differentiable pipeline loss: mean over microbatches of
    ``loss_tail(last_stage_out, labels_mb)``.  ``jax.grad`` of this runs
    the reverse pipeline (backward microbatches traverse the same
    ppermute channels in reverse)."""
    def fn(stacked_params, microbatches, labels):
        S = mesh.shape[axis]
        M = microbatches.shape[0]
        pipe = spmd_pipeline(stage_fn, S, M, axis)

        def body(params, xs, ys):
            outs = pipe(params, xs)                    # [M, mb, ...]
            return loss_tail(outs, ys)

        shmapped = jax.shard_map(
            body, mesh=mesh, in_specs=(P(axis), P(), P()),
            out_specs=P(), check_vma=False)
        return shmapped(stacked_params, microbatches, labels)
    return fn


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def stack_stage_params(per_stage: list) -> Any:
    """Stack per-stage parameter pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def shard_stage_params(mesh: Mesh, stacked: Any, axis: str = "stage") -> Any:
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), stacked)
