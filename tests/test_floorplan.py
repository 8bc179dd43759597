"""Mesh floorplanner (repro.core.floorplan) + partitioned lowering.

Fast tests (tier-1) drive the optimizer with synthetic cost models so
its choices are assertable without touching XLA, and cover the refusal
diagnostics and the content-addressing of placement artifacts.  Bit-
parity against the single-device program and the zero-recompile reuse
contract compile real programs and are marked slow — they run in the CI
partition-parity job under a forced 8-device host platform.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import repro
from repro import StepTask, SynthesisError, channel, mmap

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from repro.core.compile_cache import CompileCache  # noqa: E402
from repro.core.cost import phase_key  # noqa: E402
from repro.core.floorplan import (Placement, channel_endpoints,  # noqa: E402
                                  channel_traffic, placement_key,
                                  plan_placement)
from repro.core.synth import elaborate_step_graph  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def relay_pipeline(n_tokens=32, stages=2, burst=8, capacity=16, bias=0):
    """Step-form Source -> stages x Relay -> Sink; ``bias`` edits the
    relay body (cost-cell dirtying tests)."""
    fires = n_tokens // burst

    def source_step(k, out):
        out.write_burst(k * burst + jnp.arange(burst, dtype=jnp.int32))
        return k + 1

    def relay_step(state, inp, out):
        out.write_burst(inp.read_burst(burst) + bias)
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
        chans = [channel(capacity, f"c{i}", dtype=np.int32, shape=())
                 for i in range(stages + 1)]
        t = repro.task().invoke(Source, chans[0], name="Source")
        for s in range(stages):
            t = t.invoke(Relay, chans[s], chans[s + 1], name=f"Relay{s}")
        t.invoke(Sink, chans[stages], res, name="Sink")

    return Top, (res,), buf


def _plan(stages=2, **kw):
    top, args, _ = relay_pipeline(stages=stages, **kw)
    plan, graph, _ = elaborate_step_graph(top, *args)
    return plan, graph


def _flat_cost(plan, tp):
    return 1.0


# ---------------------------------------------------------------------------
# the optimizer (synthetic costs: no XLA)
# ---------------------------------------------------------------------------

def test_placement_is_deterministic():
    plan, graph = _plan(stages=4)
    a = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost)
    b = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost)
    assert a.owners == b.owners
    assert a.objective == b.objective
    assert a.source == "partitioned"


def test_placement_balances_flat_costs():
    """Six unit-cost tasks on two devices: the greedy + refine passes
    must land a 3/3 split (max load == half the total)."""
    plan, graph = _plan(stages=4)
    pl = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost)
    loads = pl.objective["loads_s"]
    assert sorted(loads) == [3.0, 3.0]
    assert pl.objective["max_load_s"] == 3.0


def test_heavy_task_isolated():
    """One task worth more than everything else combined gets a device
    to itself."""
    plan, graph = _plan(stages=3)

    def cost(plan, tp):
        return 100.0 if tp.inst.name == "Relay1" else 1.0

    pl = plan_placement(plan, graph, 2, cache=False, cost_fn=cost)
    heavy = dict(zip(pl.task_names, pl.owners))["Relay1"]
    others = [d for n, d in zip(pl.task_names, pl.owners) if n != "Relay1"]
    assert all(d != heavy for d in others)


def test_single_device_placement_has_no_cuts():
    plan, graph = _plan(stages=2)
    pl = plan_placement(plan, graph, 1, cache=False, cost_fn=_flat_cost)
    assert set(pl.owners) == {0}
    assert pl.objective["cut_bytes"] == 0
    assert pl.objective["cut_channels"] == []


def test_overrides_pin_tasks():
    plan, graph = _plan(stages=2)
    pl = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost,
                        overrides={"Source": 1, "Sink": 1})
    byname = dict(zip(pl.task_names, pl.owners))
    assert byname["Source"] == 1 and byname["Sink"] == 1


def test_override_unknown_task_refuses_with_names():
    plan, graph = _plan(stages=1)
    with pytest.raises(SynthesisError, match="Relayz.*known instances"):
        plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost,
                       overrides={"Relayz": 0})


def test_override_device_out_of_range_refuses():
    plan, graph = _plan(stages=1)
    with pytest.raises(SynthesisError, match="'Source' to device 5"):
        plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost,
                       overrides={"Source": 5})


def test_channel_traffic_counts_full_run_bytes():
    plan, _ = _plan(stages=1, n_tokens=32, burst=8)
    traffic = channel_traffic(plan)
    ep = channel_endpoints(plan)
    # every pipeline channel moves all 32 int32 tokens over the run
    assert all(t == 32 * 4 for t in traffic)
    assert all(p >= 0 and c >= 0 for p, c in ep)


# ---------------------------------------------------------------------------
# content addressing + memoization
# ---------------------------------------------------------------------------

def test_hw_peaks_keyed_by_device_kind():
    """Peaks come from the device's own row; the CPU row is nominal and
    an unknown kind is an error, never another chip's numbers."""
    from repro.core.cost import HW, V5E, hw_peaks
    assert hw_peaks(V5E)["peak_flops"] == 197e12
    assert hw_peaks(V5E)["hbm_bw"] == 819e9
    assert hw_peaks() is HW[jax.devices()[0].device_kind]
    with pytest.raises(KeyError, match="TPU v9"):
        hw_peaks("TPU v9")


def test_placement_key_sensitivity():
    plan, graph = _plan(stages=2)
    h = graph.structural_hash()
    base = placement_key(h, 2)
    assert base == placement_key(h, 2)
    assert base != placement_key(h, 4)
    assert base != placement_key(h, 2, {"Source": 1})
    assert placement_key(h, 2, {"Source": 1}) \
        != placement_key(h, 2, {"Source": 0})
    assert base != placement_key(h + "x", 2)
    from repro.core.cost import HW, V5E
    assert base != placement_key(h, 2, hw=HW[V5E])
    assert base.startswith("place_")


def test_placement_memo_round_trip(tmp_path):
    plan, graph = _plan(stages=3)
    cc = CompileCache(root=tmp_path)
    a = plan_placement(plan, graph, 2, cache=cc, cost_fn=_flat_cost)
    assert a.source == "partitioned"
    b = plan_placement(plan, graph, 2, cache=cc, cost_fn=_flat_cost)
    assert b.source == "memo"
    assert b.owners == a.owners
    assert b.objective == a.objective


def test_cost_cell_key_dirties_only_edited_task():
    """Editing one task's body changes that task's cost cell address and
    nobody else's — the incremental-pricing contract."""
    plan_a, _ = _plan(stages=2, bias=0)
    plan_b, _ = _plan(stages=2, bias=1)
    keys_a = {tp.inst.name: phase_key(plan_a, tp, tp.phases[0])
              for tp in plan_a.tasks}
    keys_b = {tp.inst.name: phase_key(plan_b, tp, tp.phases[0])
              for tp in plan_b.tasks}
    assert keys_a["Source"] == keys_b["Source"]
    assert keys_a["Sink"] == keys_b["Sink"]
    assert keys_a["Relay0"] != keys_b["Relay0"]
    assert keys_a["Relay1"] != keys_b["Relay1"]


def test_to_dot_colors_devices_and_cuts():
    plan, graph = _plan(stages=2)
    pl = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost)
    dot = graph.to_dot(placement=pl)
    assert "fillcolor" in dot and "dev0" in dot and "dev1" in dot
    assert ("color=red" in dot) == (len(pl.objective["cut_channels"]) > 0)
    assert "fillcolor" not in graph.to_dot()


# ---------------------------------------------------------------------------
# refusal diagnostics (never reach XLA)
# ---------------------------------------------------------------------------

def test_partitioned_ports_refuse_naming_port_and_task():
    """async_mmap latency queues have no cut protocol yet; the refusal
    must name the port AND the tasks bound to it."""
    from repro.core import async_mmap

    data = np.arange(8, dtype=np.int32)
    port = async_mmap(data.copy(), latency=2, depth=2, name="mem")
    buf = np.zeros(8, np.int32)
    res = mmap(buf, "res")

    def warm(k, port, res):
        port.read_addr.write(k)
        return k + 1

    def step(k, port, res):
        res.write_burst(k - 2, port.read_data.read()[None])
        port.read_addr.write(k)
        return k + 1

    def flush(k, port, res):
        res.write_burst(k - 2, port.read_data.read()[None])
        return k + 1

    Fetch = StepTask(step, steps=6, init=jnp.int32(0), warmup=warm,
                     n_warmup=2, flush=flush, n_flush=2, name="Fetch")

    def Top(port, res):
        repro.task().invoke(Fetch, port, res)

    with pytest.raises(SynthesisError, match="mem.*Fetch"):
        repro.ENGINES["compiled"](mesh=1, cache=False).run(Top, port, res)


def test_non_1d_mesh_refuses():
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("a", "b"))
    top, args, _ = relay_pipeline(stages=1)
    with pytest.raises(SynthesisError, match="1-D mesh"):
        repro.ENGINES["compiled"](mesh=mesh, cache=False).run(top, *args)


def test_mesh_wider_than_visible_devices_refuses():
    from repro.distributed.sharding import device_mesh
    n = jax.device_count()
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        device_mesh(n + 1)


def test_placement_reuse_mismatch_refuses():
    plan, graph = _plan(stages=2)
    pl = plan_placement(plan, graph, 2, cache=False, cost_fn=_flat_cost)
    wrong = Placement(n_devices=pl.n_devices + 1, owners=pl.owners,
                      task_names=pl.task_names, objective=pl.objective)
    top, args, _ = relay_pipeline(stages=2)
    with pytest.raises(SynthesisError, match="placement reuse mismatch"):
        repro.ENGINES["compiled"](mesh=1, cache=False,
                                  placement=wrong).run(top, *args)


# ---------------------------------------------------------------------------
# bit-parity with the single-device program (slow; multi-device CI job)
# ---------------------------------------------------------------------------

def _gemm_bytes(engine_kwargs):
    from repro.apps import gemm
    top, args, check = gemm.build_step(P=2, n=4, K=2)
    eng = repro.ENGINES["compiled"](**engine_kwargs)
    rep = eng.run(top, *args)
    assert rep.ok, rep.error
    assert check()[0]
    got = np.concatenate([np.asarray(m.data) for m in args[2]])
    return got.tobytes(), eng


def _page_rank_bytes(engine_kwargs):
    from repro.apps import page_rank
    top, args, check = page_rank.build_step(n_vertices=16, n_edges=48,
                                            n_pe=2, n_iters=4)
    eng = repro.ENGINES["compiled"](**engine_kwargs)
    rep = eng.run(top, *args)
    assert rep.ok, rep.error
    assert check()[0]
    return np.asarray(args[1].data).tobytes(), eng


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [2, 4])
def test_gemm_partitioned_bit_identical(n_dev):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    golden, _ = _gemm_bytes({})
    got, eng = _gemm_bytes({"mesh": n_dev})
    assert got == golden
    assert eng.placement_used.n_devices == n_dev
    assert len(set(eng.placement_used.owners)) > 1


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [2, 4])
def test_page_rank_partitioned_bit_identical(n_dev):
    """The feedback-loop graph (cyclic dataflow) survives partitioning:
    cut channels inside the cycle still deliver bit-identical ranks."""
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    golden, _ = _page_rank_bytes({})
    got, eng = _page_rank_bytes({"mesh": n_dev})
    assert got == golden
    assert eng.partition_source in ("partitioned", "memo")


@pytest.mark.slow
def test_manual_placement_bit_identical_and_keyed_apart():
    """A manual override produces the same answer over a different cut,
    and its compiled program caches under a different key."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    golden, _ = _gemm_bytes({})
    auto, eng_a = _gemm_bytes({"mesh": 2})
    manual, eng_m = _gemm_bytes(
        {"mesh": 2, "placement": {"PE0_0": 0, "PE1_1": 1}})
    assert auto == golden and manual == golden
    byname = dict(zip(eng_m.placement_used.task_names,
                      eng_m.placement_used.owners))
    assert byname["PE0_0"] == 0 and byname["PE1_1"] == 1
    if eng_a.placement_used.owners != eng_m.placement_used.owners:
        assert eng_a.compile_key != eng_m.compile_key


# ---------------------------------------------------------------------------
# cross-process reuse: zero re-partition, zero XLA compiles (slow)
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r})
    import numpy as np
    import repro
    from repro.core.compile_cache import CompileCache
    from repro.core.floorplan import placement_key
    from repro.core.synth import elaborate_step_graph
    from repro.apps import gemm

    cc = CompileCache(root={root!r})
    top, args, check = gemm.build_step(P=2, n=4, K=2)
    eng = repro.ENGINES["compiled"](mesh=2, cache=cc)
    rep = eng.run(top, *args)
    assert rep.ok and check()[0]
    top, args, _ = gemm.build_step(P=2, n=4, K=2)
    plan, graph, _ = elaborate_step_graph(top, *args)
    key = placement_key(graph.structural_hash(), 2)
    art = json.dumps(cc.memo_get(key), sort_keys=True)
    print("PSOURCE", eng.partition_source)
    print("CSOURCE", eng.compile_source)
    print("CKEY", eng.compile_key)
    print("ART", art)
""")


@pytest.mark.slow
def test_second_process_zero_repartition_zero_compiles(tmp_path):
    """Process 1 floorplans + compiles; process 2 must read both back
    from the content-addressed store (placement source == memo, compile
    source == disk) and see a byte-identical placement artifact."""
    import os
    prog = _CHILD.format(src=SRC, root=str(tmp_path))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=600,
                           env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append({ln.split(" ", 1)[0]: ln.split(" ", 1)[1]
                     for ln in r.stdout.strip().splitlines()
                     if " " in ln})
    assert outs[0]["PSOURCE"] == "partitioned"
    assert outs[0]["CSOURCE"] == "compiled"
    assert outs[1]["PSOURCE"] == "memo"          # zero re-partitioning
    assert outs[1]["CSOURCE"] == "disk"          # zero XLA compiles
    assert outs[0]["CKEY"] == outs[1]["CKEY"]
    assert outs[0]["ART"] == outs[1]["ART"]      # byte-identical artifact
    assert json.loads(outs[0]["ART"])["n_devices"] == 2
