"""What a compiled run brings back from the device, and that it is enough.

The partitioned program's outputs are stacked over the mesh, one row per
device; ``CompiledEngine`` fetches only the writer's shard of each written
mmap and one row of the replicated counters, and leaves read-only mmaps on
the device.  ``writeback_bytes`` counts what came back.  These tests hold
the partitioned runs byte-identical to the one-device program, with the
same firings and channel token counts (both programs run one sweep), and
the counter to the written mmaps' bytes, on either path.  Channel
``max_occupancy`` is the one count that differs: on a mesh it is sampled
at sweep ends.
"""

import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import repro  # noqa: E402
from repro.apps import gemm  # noqa: E402
from repro.core.compile_cache import CompileCache  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")

# The partitioned path needs up to four devices, which a CPU backend gives
# only to a process that starts with the flag: every run happens in one
# child, and each case below reads its share of the child's answer.
_CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r})
    import jax
    import numpy as np
    from jax.sharding import Mesh
    import repro
    from repro.apps import gemm, page_rank
    from repro.core import graph as graph_mod, synth
    from repro.core.compile_cache import CompileCache
    from repro.ft.recovery import _abstract_schedule

    cc = CompileCache(root={cc!r})

    # the plan and the per-task firing counts each run hands its stats
    seen = {{}}
    fill_stats = synth.CompiledEngine._fill_stats

    def spy(self, plan, fires, maxocc):
        seen.update(plan=plan, fires=[int(f) for f in fires])
        return fill_stats(self, plan, fires, maxocc)

    synth.CompiledEngine._fill_stats = spy

    # every definition digest a Graph computes
    digests = []
    structural_digest = graph_mod.structural_digest

    def counting(fn):
        digests.append(fn)
        return structural_digest(fn)

    graph_mod.structural_digest = counting

    # each channel's largest size at a sweep boundary of the abstract
    # schedule, whose cuts are the fires after each sweep
    def sweep_end_max(plan):
        best = [0] * len(plan.channels)
        for cut in _abstract_schedule(plan)[0]:
            size = [0] * len(plan.channels)
            for tp, f in zip(plan.tasks, cut):
                start = 0
                for ph in tp.phases:
                    k = min(max(f - start, 0), ph.count)
                    start += ph.count
                    for ci, w in ph.writes.items():
                        size[ci] += w * k
                    for ci, r in ph.reads.items():
                        size[ci] -= r * k
            best = [max(b, n) for b, n in zip(best, size)]
        return best

    def build(app):
        if app == "gemm":
            top, args, check = gemm.build_step(P=2, n=4, K=2)
            return top, args, check, list(args[2]), [args[0], args[1]]
        top, args, check = page_rank.build_step(
            n_vertices=16, n_edges=48, n_pe=2, n_iters=4)
        r0, out, deg, edges, plans = args
        return top, args, check, [out], [r0, deg, *edges, *plans]

    def run(app, **kw):
        top, args, check, written, read_only = build(app)
        before = [np.array(m.data, copy=True) for m in read_only]
        eng = repro.ENGINES["compiled"](cache=cc, **kw)
        digests.clear()
        rep = eng.run(top, *args)
        n_digest_calls = len(digests)
        placed = {{}}
        if eng.placement_used is not None:
            placed = dict(zip(eng.placement_used.task_names,
                              map(int, eng.placement_used.owners)))
        plan = seen["plan"]
        return {{
            "tasks": [tp.inst.name for tp in plan.tasks],
            "fires": seen["fires"],
            "channels": [c.name for c in plan.channels],
            "totals": [[c.total_read, c.total_written]
                       for c in plan.channels],
            "maxocc": [c.max_occupancy for c in plan.channels],
            "sweep_end_max": sweep_end_max(plan),
            "ok": bool(rep.ok and check()[0]),
            "out": b"".join(np.asarray(m.data).tobytes()
                            for m in written).hex(),
            "read_only_same": all(
                np.asarray(m.data).tobytes() == b.tobytes()
                for m, b in zip(read_only, before)),
            "written_nbytes": int(sum(np.asarray(m.data).nbytes
                                      for m in written)),
            "writeback_bytes": int(eng.writeback_bytes),
            "sweeps": int(eng.n_sweeps),
            "placed": placed,
            "partition_source": eng.partition_source,
            "n_digests": eng.n_digests,
            "digest_calls": n_digest_calls,
            "definitions": len(definitions(app))}}

    def definitions(app):
        top, args = build(app)[:2]
        return synth.elaborate_step_graph(top, *args)[1].definitions

    devs = jax.devices()
    got = {{
        "gemm-1": run("gemm"),
        "gemm-mesh2": run("gemm", mesh=2, placement={{"Collector1": 1}}),
        "gemm-mesh4": run("gemm", mesh=4, placement={{"Collector0": 3,
                                                       "Collector1": 1}}),
        # the same invocation again: the floorplanner's memo hits
        "gemm-mesh4-again": run("gemm", mesh=4, placement={{
            "Collector0": 3, "Collector1": 1}}),
        # a mesh whose device order is not the shards' row order
        "gemm-mesh4-reversed": run(
            "gemm", mesh=Mesh(np.asarray(devs[:4][::-1]), ("dev",)),
            placement={{"Collector0": 2, "Collector1": 1}}),
        "page_rank-1": run("page_rank"),
        "page_rank-mesh2": run("page_rank", mesh=2),
    }}
    print(json.dumps(got))
""")

PINS = {"gemm-mesh2": {"Collector1": 1},
        "gemm-mesh4": {"Collector0": 3, "Collector1": 1},
        "gemm-mesh4-reversed": {"Collector0": 2, "Collector1": 1},
        "page_rank-mesh2": {}}


def _plan_order(run: dict) -> tuple:
    """A run's tasks and channels in plan order, by name less the
    instance number some names carry (``Ctrl#51``)."""
    return tuple([re.sub(r"#\d+$", "", n) for n in run[k]]
                 for k in ("tasks", "channels"))


@pytest.fixture(scope="module")
def child_runs(tmp_path_factory):
    prog = _CHILD.format(src=SRC,
                         cc=str(tmp_path_factory.mktemp("wb") / "cc"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(PINS))
def test_partitioned_writeback_matches_one_device(child_runs, case):
    """Written mmaps come back byte-identical to the one-device program
    from the writer's shard (a pinned writer off row 0 would read a row
    of zeros from the wrong one), read-only mmaps are untouched, and the
    bytes fetched are the written mmaps' bytes, as on one device."""
    app = case.split("-")[0]
    one, got = child_runs[f"{app}-1"], child_runs[case]
    assert one["ok"] and got["ok"]
    assert got["out"] == one["out"]
    assert one["read_only_same"] and got["read_only_same"]
    assert got["writeback_bytes"] == got["written_nbytes"] \
        == one["writeback_bytes"] > 0
    assert got["sweeps"] == one["sweeps"] > 0
    # one sweep on both paths: the same firings, the same tokens on every
    # channel, in the same plan order
    assert _plan_order(got) == _plan_order(one)
    assert got["fires"] == one["fires"] and sum(one["fires"]) > 0
    assert got["totals"] == one["totals"]
    for task, dev in PINS[case].items():
        assert got["placed"][task] == dev
    assert len(set(got["placed"].values())) > 1


@pytest.mark.parametrize("case", sorted(PINS))
def test_partitioned_max_occupancy_is_sweep_granular(child_runs, case):
    """A mesh samples each channel's occupancy at sweep end, after the
    sizes are resynchronised: never above the one-device highwater, which
    is sampled after every firing, and equal to the largest sweep-end
    size of the abstract schedule."""
    app = case.split("-")[0]
    one, got = child_runs[f"{app}-1"], child_runs[case]
    assert _plan_order(got) == _plan_order(one)
    for name, mesh, single, boundary in zip(
            got["channels"], got["maxocc"], one["maxocc"],
            got["sweep_end_max"]):
        assert mesh <= single, name
        assert mesh == boundary, name
    assert got["sweep_end_max"] == one["sweep_end_max"]
    assert max(got["maxocc"]) > 0


@pytest.mark.parametrize("case", sorted(PINS) + ["gemm-mesh4-again"])
def test_partitioned_run_digests_each_definition_once(child_runs, case):
    """Lowering, validation and placement read one digest table and one
    graph hash: a run on a mesh digests each task definition once, as a
    run on one device does, and the floorplanner's memo hit adds none."""
    app = case.split("-")[0]
    one, got = child_runs[f"{app}-1"], child_runs[case]
    assert got["ok"]
    assert got["n_digests"] == got["digest_calls"] == got["definitions"] \
        == one["n_digests"] == one["digest_calls"] > 1
    if case == "gemm-mesh4-again":
        assert got["partition_source"] == "memo"
        assert got["placed"] == child_runs["gemm-mesh4"]["placed"]


def test_single_device_writeback_bytes_counts_written_mmaps(tmp_path):
    top, args, _ = gemm.build_step(P=2, n=4, K=2)
    eng = repro.ENGINES["compiled"](cache=CompileCache(root=tmp_path))
    assert eng.writeback_bytes == 0
    assert eng.run(top, *args).ok
    assert eng.writeback_bytes == sum(m.data.nbytes for m in args[2])


def test_single_device_writeback_bytes_counts_written_ports(tmp_path):
    """An ``async_mmap`` write port's data comes back like an mmap's."""
    top, args, check = gemm.build_step_async(P=2, n=4, K=2)
    eng = repro.ENGINES["compiled"](cache=CompileCache(root=tmp_path))
    assert eng.run(top, *args).ok and check()[0]
    _, _, c_ports = args        # A's ports are read-only, B is never written
    assert eng.writeback_bytes == sum(p.data.nbytes for p in c_ports) > 0
