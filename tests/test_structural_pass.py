"""One structural pass per invocation: a ``Graph`` digests each task
definition once, and ``definitions``, ``structural_hash`` and the
floorplanner's memo read that one table and that one hash.

The table changes no value: the graph hash, every definition hash and the
keys built on them (lowering memo, executable, placement) equal those of a
graph that digests afresh at every read, as each pass did before.  It lives
on one ``Graph``, so an in-place edit of a captured array between two
invocations is still seen.
"""

from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

import repro  # noqa: E402
from repro import StepTask, channel, mmap  # noqa: E402
from repro.apps import gemm, page_rank  # noqa: E402
from repro.core import floorplan, graph as graph_mod, synth  # noqa: E402
from repro.core.compile_cache import CompileCache, structural_digest  # noqa: E402
from repro.core.context import clear_context  # noqa: E402
from repro.core.graph import Graph  # noqa: E402

APPS = {
    "gemm": lambda: gemm.build_step(P=2, n=4, K=2),
    "page_rank": lambda: page_rank.build_step(
        n_vertices=16, n_edges=48, n_pe=2, n_iters=3),
}


def _flat_cost(plan, tp):
    return 1.0


@pytest.fixture
def digest_calls(monkeypatch):
    """Counts every definition digest a ``Graph`` computes."""
    calls = []

    def counting(fn):
        calls.append(fn)
        return structural_digest(fn)

    monkeypatch.setattr(graph_mod, "structural_digest", counting)
    return calls


def _fresh_digests(monkeypatch):
    """Make every ``Graph`` digest afresh at each read, as each pass did
    before the table: the reference the table's values must equal."""
    monkeypatch.setattr(Graph, "_digest",
                        lambda self, fn: structural_digest(fn))


def _elaborate(app, **engine_kw):
    top, args, _ = APPS[app]()
    eng = repro.ENGINES["compiled"](**engine_kw)
    try:
        plan, graph, _ = eng._elaborate(top, *args)
    finally:
        clear_context()
    return plan, graph


@pytest.mark.parametrize("app", sorted(APPS))
def test_one_run_digests_each_definition_once(tmp_path, digest_calls, app):
    cc = CompileCache(root=tmp_path)
    for _ in range(2):              # a traced lowering, then a memo hit
        digest_calls.clear()
        top, args, check = APPS[app]()
        eng = repro.ENGINES["compiled"](cache=cc)
        assert eng.run(top, *args).ok and check()[0]
        assert len(digest_calls) == eng.n_digests
        assert len({id(fn) for fn in digest_calls}) == eng.n_digests
    assert eng.lower_source == "memory"
    _, graph = _elaborate(app, cache=cc)
    assert eng.n_digests == len(graph.definitions) > 1


@pytest.mark.parametrize("app", sorted(APPS))
def test_graph_hash_and_definitions_share_one_pass(digest_calls, app):
    _, graph = _elaborate(app, cache=False)     # hashed and validated
    n = len(digest_calls)
    assert n == graph.n_digests == len(graph.definitions)
    h = graph.structural_hash()
    assert graph.structural_hash() == h
    assert [d.defn_hash for d in graph.definitions] == \
        [d.defn_hash for d in graph.definitions]
    assert len(digest_calls) == n


def test_placement_reads_the_stored_hash(tmp_path, digest_calls,
                                         monkeypatch):
    plan, graph = _elaborate("gemm", cache=False)
    keys = []
    real_key = floorplan.placement_key
    monkeypatch.setattr(floorplan, "placement_key",
                        lambda h, *a, **k: keys.append(h) or
                        real_key(h, *a, **k))
    # the graph hash is a SHA-256 pass over every instance: none may run
    monkeypatch.setattr(graph_mod, "hashlib", SimpleNamespace(
        sha256=lambda: pytest.fail("graph hashed again")))
    digest_calls.clear()
    cc = CompileCache(root=tmp_path)
    sources = [floorplan.plan_placement(plan, graph, 2, cache=cc,
                                        cost_fn=_flat_cost).source
               for _ in range(2)]
    assert sources == ["partitioned", "memo"]
    assert keys == [plan.structural_hash] * 2
    assert digest_calls == []


@pytest.mark.parametrize("app", sorted(APPS))
def test_values_equal_fresh_digests(tmp_path, monkeypatch, app):
    """The graph hash, each definition hash and the lowering, executable
    and placement keys are those of fresh digests at every read."""
    def values(root):
        plan, graph = _elaborate(app, cache=False)
        top, args, _ = APPS[app]()
        eng = repro.ENGINES["compiled"](cache=CompileCache(root=root))
        assert eng.run(top, *args).ok
        return (plan.structural_hash, graph.structural_hash(),
                sorted((d.name, d.defn_hash) for d in graph.definitions),
                synth._lowering_key(plan.structural_hash, plan.ring_impl),
                eng.compile_key,
                floorplan.placement_key(plan.structural_hash, 2))

    shared = values(tmp_path / "shared")
    assert shared[0] == shared[1]
    for name, h in shared[2]:
        assert len(h) == 64, name
    with monkeypatch.context() as mp:
        _fresh_digests(mp)
        fresh = values(tmp_path / "fresh")
    assert shared == fresh
    _, graph = _elaborate(app, cache=False)
    for d in graph.definitions:
        assert d.defn_hash == structural_digest(d.fn)


def test_in_place_edit_between_invocations_misses(tmp_path):
    """The QoR loop edits a captured array in place and invokes again:
    the next invocation's Graph digests the edited bytes, so the hash
    changes and the lowering memo misses."""
    off = np.zeros(2, np.int32)

    def src(k, out):
        out.write_burst(k * 2 + jnp.arange(2, dtype=jnp.int32)
                        + jnp.asarray(off))
        return k + 1

    def snk(k, inp, res):
        res.write_burst(k * 2, inp.read_burst(2))
        return k + 1

    Src = StepTask(src, steps=2, init=jnp.int32(0), name="Src")
    Snk = StepTask(snk, steps=2, init=jnp.int32(0), name="Snk")

    def Top(res):
        c = channel(4, "c", dtype=np.int32, shape=())
        repro.task().invoke(Src, c).invoke(Snk, c, res)

    cc = CompileCache(root=tmp_path)

    def invoke():
        res = mmap(np.zeros(4, np.int32), "res")
        eng = repro.ENGINES["compiled"](cache=cc)
        assert eng.run(Top, res).ok
        return eng, res.data.copy()

    first, out0 = invoke()
    again, out1 = invoke()
    off[:] = 5
    edited, out2 = invoke()
    assert [e.lower_source for e in (first, again, edited)] == \
        ["traced", "memory", "traced"]
    assert first.compile_key == again.compile_key != edited.compile_key
    assert np.array_equal(out0, out1)
    assert np.array_equal(out2, out0 + 5)
    assert edited.n_digests == first.n_digests == 3
