"""PageRank with Graphalytics semantics as a compiled task graph.

The input program is the step-function graph of ``repro.apps.page_rank``
(``build_step``), rebuilt here through the public API with its task
bodies copied unchanged: Ctrl broadcasts the rank vector to the scatter
PEs each iteration and combines their contributions, so the graph has a
cycle that the whole-graph ``lax.while_loop`` runs.  Each PE owns the
edges whose destination lies in its quarter of the vertices and gathers
them through a build-time plan padded to its partition's largest
in-degree.

The graph is generated vectorised from the seed (the app's
``build_step`` loops over edges in Python).  Every seed gets the same
shapes: each PE holds exactly ``E / PEs`` edges, and the in-degree
multiset of each partition comes from the traffic file's
``degree_sequence_seed``; the seed relabels the vertices within each
partition, draws every edge's source uniformly and shuffles the edge
order.  Between invocations ``refresh`` moves the source of a few seeded
edges per PE and updates the out-degree vector, which keeps every
partition's shapes and gather plan.
"""

from __future__ import annotations

import numpy as np


def _program(V: int, n_pe: int, n_iters: int, damping: float):
    """The app's step graph for ``V`` vertices over ``n_pe`` PEs."""
    import jax
    import jax.numpy as jnp
    from repro.core import MMap, StepTask, channel, task

    n_vertices = V
    DAMPING = damping

    def scatter_step(state, edges: MMap, plan: MMap, deg: MMap, ranks_in,
                     upd_out):
        r = ranks_in.read()
        e = jnp.asarray(edges.read_burst(0, len(edges)))
        idx = jnp.asarray(plan.read_burst(0, n_vertices))
        degv = jnp.asarray(deg.read_burst(0, n_vertices))
        w = r[e[:, 0]] / degv[e[:, 0]]
        wext = jnp.concatenate([w, jnp.zeros(1, jnp.float32)])
        contrib = wext[idx[:, 0]]
        for k in range(1, idx.shape[1]):        # static, fixed-order sum
            contrib = contrib + wext[idx[:, k]]
        upd_out.write(contrib)
        return state

    _mix = jax.jit(lambda total: ((1 - DAMPING) / n_vertices +
                                  DAMPING * total).astype(jnp.float32))

    def _combine(upd_ins):
        total = upd_ins[0].read()
        for ci in upd_ins[1:]:
            total = total + ci.read()
        return _mix(total)

    def ctrl_warmup(r, ranks0: MMap, out: MMap, rank_outs, upd_ins):
        r = jnp.asarray(ranks0.read_burst(0, n_vertices))
        for o in rank_outs:
            o.write(r)
        return r

    def ctrl_step(r, ranks0: MMap, out: MMap, rank_outs, upd_ins):
        r = _combine(upd_ins)
        for o in rank_outs:
            o.write(r)
        return r

    def ctrl_flush(r, ranks0: MMap, out: MMap, rank_outs, upd_ins):
        r = _combine(upd_ins)
        out.write_burst(0, r)
        return r

    ScatterS = StepTask(scatter_step, steps=n_iters, name="Scatter")
    CtrlS = StepTask(ctrl_step, steps=n_iters - 1, warmup=ctrl_warmup,
                     flush=ctrl_flush,
                     init=jnp.zeros(n_vertices, jnp.float32), name="Ctrl")

    def Top(r0m: MMap, outm: MMap, degm: MMap, eports, plans):
        vec = dict(dtype=np.float32, shape=(n_vertices,))
        rank_ch = [channel(1, f"rank{p}", **vec) for p in range(n_pe)]
        upd_ch = [channel(1, f"upd{p}", **vec) for p in range(n_pe)]
        t = task()
        for p in range(n_pe):
            t = t.invoke(ScatterS, eports[p], plans[p], degm, rank_ch[p],
                         upd_ch[p], name=f"Scatter{p}")
        t.invoke(CtrlS, r0m, outm, rank_ch, upd_ch)

    return Top


def _uniform_graph(V: int, E: int, n_pe: int, degree_seed: int,
                   rng: np.random.Generator):
    """Per-PE ``(edges[E/PEs, 2], plan[V, width])`` of a uniform random
    graph whose destinations split evenly over the PEs' partitions."""
    part, ep = V // n_pe, E // n_pe
    fixed = np.random.default_rng(degree_seed)
    edges, plans = [], []
    for p in range(n_pe):
        indeg = np.bincount(fixed.integers(0, part, ep), minlength=part)
        deg_v = np.empty_like(indeg)
        deg_v[rng.permutation(part)] = indeg          # relabel vertices
        start = np.cumsum(deg_v) - deg_v
        dst_sorted = np.repeat(np.arange(p * part, (p + 1) * part), deg_v)
        order = rng.permutation(ep)                   # stored edge order
        pos = np.empty(ep, np.int64)
        pos[order] = np.arange(ep)
        e = np.empty((ep, 2), np.int32)
        e[:, 0] = rng.integers(0, V, ep)
        e[:, 1] = dst_sorted[order]
        width = int(indeg.max())
        k = np.arange(width)
        slot = start[:, None] + k[None, :]
        live = k[None, :] < deg_v[:, None]
        plan = np.full((V, width), ep, np.int32)      # ep: the zero weight
        plan[p * part:(p + 1) * part] = np.where(
            live, pos[np.minimum(slot, ep - 1)], ep)
        edges.append(e)
        plans.append(plan)
    return edges, plans


def _refresh_edges(inputs: dict, seed: int, i: int, per_pe: int) -> None:
    """Invocation ``i``'s refresh: a few seeded edges of each PE take a
    new uniform source; the out-degree counts and vector follow."""
    rng = np.random.default_rng([seed, i])
    counts, deg = inputs["counts"], inputs["deg"]
    V = len(deg)
    for e in inputs["edges"]:
        at = np.unique(rng.integers(0, len(e), per_pe))
        new = rng.integers(0, V, len(at)).astype(np.int32)
        old = e[at, 0].copy()
        e[at, 0] = new
        np.subtract.at(counts, old, 1)
        np.add.at(counts, new, 1)
        touched = np.concatenate([old, new])
        deg[touched] = np.maximum(counts[touched], 1)


class Graph:
    """One seeded instance of the cell: its mmaps, the graph the window
    invokes, and what the check needs to replay any invocation's inputs."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import mmap
        if traffic["graph"] != "uniform":
            raise ValueError(f"page_rank: no generator for graph "
                             f"{traffic['graph']!r}")
        self.V = 1 << int(cfg["scale"])
        self.E = int(cfg["edge_factor"]) * self.V
        self.n_pe = int(cfg["scatter_pes"])
        self.iters = int(cfg["iterations"])
        self.damping = float(cfg["damping"])
        self.seed = seed
        self.per_pe = int(traffic["refresh_edges"])
        self.engine_kw = {"mesh": cfg["mesh"]} if cfg.get("mesh") else {}
        rng = np.random.default_rng(seed)
        self.edges, self.plans = _uniform_graph(
            self.V, self.E, self.n_pe, int(traffic["degree_sequence_seed"]),
            rng)
        src = np.concatenate([e[:, 0] for e in self.edges])
        self.counts = np.bincount(src, minlength=self.V).astype(np.int64)
        self.deg = np.maximum(self.counts, 1).astype(np.float32)
        self.r0 = np.full(self.V, 1.0 / self.V, np.float32)
        self.ranks = np.zeros(self.V, np.float32)
        self._pristine = {k: np.copy(v) for k, v in self._live().items()
                          if k != "edges"}
        self._pristine["edges"] = [e.copy() for e in self.edges]
        self.args = (mmap(self.r0, "ranks0"), mmap(self.ranks, "ranks"),
                     mmap(self.deg, "out_deg"),
                     [mmap(e, f"edges{p}") for p, e in enumerate(self.edges)],
                     [mmap(g, f"gather{p}") for p, g in enumerate(self.plans)])
        self.top = self.make_top()

    def _live(self) -> dict:
        return {"edges": self.edges, "counts": self.counts, "deg": self.deg,
                "r0": self.r0}

    def make_top(self):
        """A freshly built graph: new task definitions, same shapes."""
        return _program(self.V, self.n_pe, self.iters, self.damping)

    def refresh(self, i: int) -> None:
        _refresh_edges(self._live(), self.seed, i, self.per_pe)

    def output(self) -> np.ndarray:
        return self.ranks.copy()

    def inputs_at(self, i: int) -> dict:
        """The inputs invocation ``i`` of the window saw."""
        inputs = {k: np.copy(v) for k, v in self._pristine.items()
                  if k != "edges"}
        inputs["edges"] = [e.copy() for e in self._pristine["edges"]]
        for j in range(i + 1):
            _refresh_edges(inputs, self.seed, j, self.per_pe)
        inputs.update(V=self.V, iters=self.iters, damping=self.damping)
        return inputs

    def work(self) -> dict:
        """Algorithmic work of one invocation, counted from the shapes.

        The bytes bound: per iteration every edge row (two int32) is read
        once, one rank and one out-degree are read per edge, and the rank
        vector is read and written once.  FLOPs (a divide and an add per
        edge) are far below it.  Channel bytes: each iteration Ctrl
        pushes one rank vector to every PE and every PE pushes one
        contribution vector, each written once and read once."""
        V, E, it, n_pe = self.V, self.E, self.iters, self.n_pe
        return {"flops": 2 * E * it, "flops_peak": "bf16",
                "bytes": it * (E * 8 + E * 4 + E * 4 + V * 8),
                "channel_bytes": 2 * (2 * n_pe * it) * V * 4}


def build(cfg: dict, traffic: dict, seed: int) -> Graph:
    return Graph(cfg, traffic, seed)


def _power_iteration(inputs: dict, round_to=None) -> np.ndarray:
    V, d = inputs["V"], inputs["damping"]
    src = np.concatenate([e[:, 0] for e in inputs["edges"]]).astype(np.int64)
    dst = np.concatenate([e[:, 1] for e in inputs["edges"]]).astype(np.int64)
    out_deg = np.maximum(np.bincount(src, minlength=V), 1).astype(np.float64)
    keep = (lambda x: x) if round_to is None else \
        (lambda x: x.astype(round_to).astype(np.float64))
    r = keep(inputs["r0"].astype(np.float64))
    for _ in range(inputs["iters"]):
        w = keep(r[src] / out_deg[src])
        r = keep((1 - d) / V + d * np.bincount(dst, weights=w, minlength=V))
    return r


def reference(inputs: dict) -> np.ndarray:
    """Graphalytics PageRank (no dangling redistribution) in float64."""
    return _power_iteration(inputs)


def control(inputs: dict) -> np.ndarray:
    """The reference one precision step below float32: the rank vector
    and every per-edge weight held in bfloat16, sums exact."""
    import ml_dtypes
    return _power_iteration(inputs, round_to=ml_dtypes.bfloat16)


def compare(out: np.ndarray, ref: np.ndarray) -> dict:
    """``max_rel_err``: the largest error relative to its vertex's
    reference rank; ``rel_l1_err``: the L1 norm of the error over the
    reference's."""
    err = np.abs(out.astype(np.float64) - ref)
    return {"max_rel_err": float(np.max(err / ref)),
            "rel_l1_err": float(np.sum(err) / np.sum(ref))}
