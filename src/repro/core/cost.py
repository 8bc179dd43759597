"""Cost probes: FLOPs / bytes / collective estimates per compiled unit.

Two consumers share this module (one memoized code path, per the QoR
loop's "measure cheap, measure once" rule):

* the **floorplanner** (:mod:`repro.core.floorplan`) prices every
  :class:`~repro.core.synth.StepTask` firing so the min-cut/load-balance
  objective has real per-task weights instead of a hash of the task
  name — :func:`task_cost` / :func:`phase_cost`;
* the **perf_iter benchmark** (``benchmarks/perf_iter.py``) measures
  whole training/decode step builds — :func:`probe_compiled`, the
  refactored body of its old private ``meas`` helper.

Both paths are memoized in the compile cache's JSON store
(``memo_get``/``memo_put``): a probe key folds in the *probed
function's own structural digest* plus its binding specs, so editing one
task definition dirties exactly one cost cell — every untouched cell is
a digest lookup, in this process (dict) and across processes (disk).

Step-task probes lower the single-firing body (the same
``_phase_probe`` trace the whole-graph program inlines) and read XLA's
``cost_analysis`` from the *lowered* module — no backend compile, so
pricing a 100-task graph costs milliseconds per distinct cell.
``probe_compiled`` runs the full ``lower().compile()`` pipeline because
its callers need optimized-HLO collective traffic and memory analysis.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import numpy as np

from .compile_cache import (_stable_repr, default_cache, instance_key,
                            structural_digest)
from .synth import (_ChanRef, _MMapRef, _PortRef, _canon_dtype, _chan_specs,
                    _mmap_specs, _phase_probe, _state_spec)

COST_SCHEMA = "cost1"

# Per-chip peaks keyed by ``device_kind``: the floorplan objective and
# perf_iter's fit-corrected terms convert raw counters into seconds with
# these, so "compute seconds" and "cut-traffic seconds" are commensurable.
V5E = "TPU v5 lite"
HW = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    V5E: {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 1600e9 / 8,
          "hbm_capacity": 16e9},
    # nominal, for tests on the CPU backend only: not a measured machine
    "cpu": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
            "hbm_capacity": 16e9},
}


def hw_peaks(device_kind: Optional[str] = None) -> dict:
    """The :data:`HW` row for ``device_kind`` (default: the first visible
    device).  A kind with no row is an error, never another chip's
    numbers."""
    kind = device_kind or jax.devices()[0].device_kind
    try:
        return HW[kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {kind!r}; add its "
                       f"published peaks to repro.core.cost.HW") from None


# in-process cost cells (the disk memo's L1): probe key -> result dict
_CELLS: dict[str, dict] = {}


def clear_cost_cells() -> None:
    """Drop the in-process cost-cell cache (tests)."""
    _CELLS.clear()


def _extract_compiled(compiled) -> dict:
    from ..launch.dryrun import collective_bytes   # lazy: launch is heavy
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"]),
            "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0))}


def probe_compiled(fn: Callable, args: tuple = (), kwargs=None, *,
                   mesh=None, in_shardings=None, out_shardings=None,
                   donate_argnums=None, memo_key: Optional[str] = None,
                   cache: Any = None) -> dict:
    """``jit(fn).lower(*args).compile()`` and return its cost split.

    Returns ``{"flops", "bytes", "coll", "arg_bytes", "temp_bytes"}``
    (optimized-HLO counters; ``coll`` is the collective traffic parsed
    from the compiled module).  With ``memo_key`` set the result is
    memoized in ``cache`` (default: the process compile cache;
    ``cache=False`` disables memoization) — a hit never touches XLA.
    """
    cc = default_cache() if cache is None else (cache or None)
    if memo_key is not None and cc is not None:
        hit = cc.memo_get(memo_key)
        if hit is not None:
            return hit
    jit_kw = {}
    if in_shardings is not None:
        jit_kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        jit_kw["out_shardings"] = out_shardings
    if donate_argnums is not None:
        jit_kw["donate_argnums"] = donate_argnums
    if mesh is not None:
        with mesh:
            compiled = jax.jit(fn, **jit_kw).lower(
                *args, **(kwargs or {})).compile()
    else:
        compiled = jax.jit(fn, **jit_kw).lower(
            *args, **(kwargs or {})).compile()
    out = _extract_compiled(compiled)
    if memo_key is not None and cc is not None:
        cc.memo_put(memo_key, out)
    return out


# ---------------------------------------------------------------------------
# step-task probes (the floorplanner's price list)
# ---------------------------------------------------------------------------

def _template_sig(plan, t: Any) -> Any:
    """Stable signature of one bound argument template: everything that
    shapes the lowered firing body *except* the phase function itself
    (which the probe key hashes separately via its structural digest)."""
    if isinstance(t, _ChanRef):
        c = plan.channels[t.ci]
        return ("chan", c.capacity, str(_canon_dtype(c.dtype)),
                tuple(c.shape))
    if isinstance(t, _MMapRef):
        m = plan.mmaps[t.mi]
        return ("mmap", tuple(m.shape), str(m.dtype))
    if isinstance(t, _PortRef):
        p = plan.ports[t.pi]
        return ("port", tuple(p.shape), str(p.dtype), p.latency, p.depth)
    if isinstance(t, (list, tuple)):
        return ("seq",) + tuple(_template_sig(plan, x) for x in t)
    return ("const", _stable_repr(t))


def phase_key(plan, tp, ph) -> str:
    """The cost cell's content address: phase-function digest + binding
    specs + ring impl + toolchain.  Depends on nothing outside this one
    task's definition and its port shapes, so editing another task — or
    re-wiring an unrelated corner of the graph — leaves this cell warm.
    """
    sig = (tuple(_template_sig(plan, t) for t in tp.t_args),
           tuple(sorted((k, _template_sig(plan, t))
                        for k, t in tp.t_kwargs.items())))
    state = _stable_repr(jax.tree.map(
        lambda x: (tuple(x.shape), str(x.dtype)), _state_spec(tp.state0)))
    return instance_key(
        ph.fn, (), {},
        extra=("step_cost", COST_SCHEMA, plan.ring_impl, ph.label,
               sig, state))


def phase_cost(plan, tp, ph, *, cache: Any = None) -> dict:
    """Per-firing ``{"flops", "bytes", "coll"}`` for one phase of one
    task plan — lowered-module counters, memoized under
    :func:`phase_key`."""
    key = phase_key(plan, tp, ph)
    hit = _CELLS.get(key)
    if hit is not None:
        return hit
    cc = default_cache() if cache is None else (cache or None)
    if cc is not None:
        hit = cc.memo_get(key)
        if hit is not None:
            _CELLS[key] = hit
            return hit
    probe = _phase_probe(plan, tp, ph.fn, rec=None)
    low = jax.jit(probe).lower(_state_spec(tp.state0),
                               _chan_specs(plan, tp),
                               _mmap_specs(plan, tp))
    cost = low.cost_analysis() or low.compile().cost_analysis() or {}
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0)),
           # a single step firing is device-local by construction; the
           # interconnect traffic it *causes* is priced per channel by
           # the floorplanner, not here
           "coll": 0.0}
    _CELLS[key] = out
    if cc is not None:
        cc.memo_put(key, out)
    return out


def task_cost(plan, tp, *, cache: Any = None, hw: Optional[dict] = None
              ) -> dict:
    """Whole-budget cost of one task instance: per-phase firing cost x
    firing count, plus the roofline-converted ``seconds`` the floorplan
    objective balances."""
    hw = hw or hw_peaks()
    tot = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    per_phase = []
    for ph in tp.phases:
        c = phase_cost(plan, tp, ph, cache=cache)
        per_phase.append({"label": ph.label, "count": ph.count, **c})
        for k in tot:
            tot[k] += c[k] * ph.count
    seconds = (tot["flops"] / hw["peak_flops"]
               + tot["bytes"] / hw["hbm_bw"]
               + tot["coll"] / hw["ici_bw"])
    return {**tot, "seconds": seconds, "phases": per_phase}


def graph_cost_salt(plan) -> str:
    """Digest of every task's phase-function digests — a cheap way for
    placement artifacts to notice a task edit without re-probing."""
    import hashlib
    h = hashlib.sha256()
    for tp in plan.tasks:
        for ph in tp.phases:
            h.update(structural_digest(ph.fn).encode())
    return h.hexdigest()
