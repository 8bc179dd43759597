"""PolySA output-stationary systolic GEMM (arXiv:2009.11389, section 4.1).

The input program is the step-function graph of ``repro.apps.gemm``,
rebuilt here through the public API with its task bodies copied
unchanged: a compiler benchmark owns its input programs, so a change to
the app does not move this configuration.  A ``P x P`` array of PEs
computes ``C = A @ B`` for ``A: (P*n, K*n)`` and ``B: (K*n, P*n)``; A
blocks stream left to right, B blocks top to bottom, and each row's
collector writes its ``(n, P*n)`` slice of C.

Sizes: ``P`` and the precision come from the configuration file, the
block size ``n`` and block count ``K`` from the traffic file.  Between
invocations ``refresh`` rewrites a few seeded rows of A and B in place,
so every invocation computes a product it has not computed before.
"""

from __future__ import annotations

import numpy as np


def _program(P: int, n: int, K: int):
    """The app's step graph for a ``P x P`` array of ``(n, n)`` blocks."""
    import jax
    import jax.numpy as jnp
    from repro.core import MMap, StepTask, channel, task

    def afeeder_step(k, a: MMap, out, i: int):
        rows = jnp.asarray(a.read_burst(i * n, n))      # (n, K*n), static i
        out.write(jax.lax.dynamic_slice_in_dim(rows, k * n, n, axis=1))
        return k + 1

    def bfeeder_step(k, b: MMap, out, j: int):
        rows = jnp.asarray(b.read_burst(k * n, n))      # (n, P*n), dynamic k
        out.write(rows[:, j * n:(j + 1) * n])
        return k + 1

    _mac = jax.jit(lambda acc, a, b: acc + a @ b)

    def pe_step(acc, a_in, b_in, a_out, b_out, c_out):
        a = a_in.read()
        b = b_in.read()
        if a_out is not None:
            a_out.write(a)
        if b_out is not None:
            b_out.write(b)
        return _mac(acc, a, b)

    def pe_flush(acc, a_in, b_in, a_out, b_out, c_out):
        c_out.write(acc)
        return acc

    def collector_step(state, c_row: MMap, c_ins, i: int):
        for j, ch in enumerate(c_ins):
            c_row[:, j * n:(j + 1) * n] = ch.read()
        return state

    AFeederS = StepTask(afeeder_step, steps=K, init=jnp.int32(0),
                        name="AFeeder")
    BFeederS = StepTask(bfeeder_step, steps=K, init=jnp.int32(0),
                        name="BFeeder")
    PES = StepTask(pe_step, steps=K, flush=pe_flush,
                   init=jnp.zeros((n, n), jnp.float32), name="PE")
    CollectorS = StepTask(collector_step, steps=1, name="Collector")

    def Top(a: MMap, b: MMap, c_views):
        blk = dict(dtype=np.float32, shape=(n, n))
        a_ch = [[channel(2, f"a{i}_{j}", **blk) for j in range(P)]
                for i in range(P)]
        b_ch = [[channel(2, f"b{i}_{j}", **blk) for j in range(P)]
                for i in range(P)]
        c_ch = [[channel(1, f"c{i}_{j}", **blk) for j in range(P)]
                for i in range(P)]
        t = task()
        for i in range(P):
            t = t.invoke(AFeederS, a, a_ch[i][0], i, name=f"AFeeder{i}")
            t = t.invoke(BFeederS, b, b_ch[0][i], i, name=f"BFeeder{i}")
        for i in range(P):
            for j in range(P):
                t = t.invoke(
                    PES, a_ch[i][j], b_ch[i][j],
                    a_ch[i][j + 1] if j + 1 < P else None,
                    b_ch[i + 1][j] if i + 1 < P else None,
                    c_ch[i][j], name=f"PE{i}_{j}")
        for i in range(P):
            t = t.invoke(CollectorS, c_views[i], c_ch[i], i,
                         name=f"Collector{i}")

    return Top


def _refresh_rows(inputs: dict, seed: int, i: int, rows: int) -> None:
    """Invocation ``i``'s refresh: ``rows`` seeded rows of A and of B
    take fresh standard normal values, in place."""
    rng = np.random.default_rng([seed, i])
    for name in ("A", "B"):
        m = inputs[name]
        at = rng.integers(0, m.shape[0], rows)
        m[at] = rng.standard_normal((rows, m.shape[1]), np.float32)


class Graph:
    """One seeded instance of the cell: its mmaps, the graph the window
    invokes, and what the check needs to replay any invocation's inputs."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import mmap
        self.P, self.n, self.K = int(cfg["P"]), int(traffic["n"]), \
            int(traffic["K"])
        self.seed = seed
        self.rows = int(traffic["refresh_rows"])
        self.rounding = _default_rounding()
        self.engine_kw = {"mesh": cfg["mesh"]} if cfg.get("mesh") else {}
        P, n, K = self.P, self.n, self.K
        rng = np.random.default_rng(seed)
        self.A = rng.standard_normal((P * n, K * n), np.float32)
        self.B = rng.standard_normal((K * n, P * n), np.float32)
        self.C = np.zeros((P * n, P * n), np.float32)
        self._pristine = {"A": self.A.copy(), "B": self.B.copy()}
        c_rows = [mmap(self.C[i * n:(i + 1) * n, :], f"C{i}")
                  for i in range(P)]
        self.args = (mmap(self.A, "A"), mmap(self.B, "B"), c_rows)
        self.top = self.make_top()

    def make_top(self):
        """A freshly built graph: new task definitions, same shapes."""
        return _program(self.P, self.n, self.K)

    def refresh(self, i: int) -> None:
        _refresh_rows({"A": self.A, "B": self.B}, self.seed, i, self.rows)

    def output(self) -> np.ndarray:
        return self.C.copy()

    def inputs_at(self, i: int) -> dict:
        """The inputs invocation ``i`` of the window saw."""
        inputs = {k: v.copy() for k, v in self._pristine.items()}
        for j in range(i + 1):
            _refresh_rows(inputs, self.seed, j, self.rows)
        inputs["rounding"] = self.rounding
        return inputs

    def work(self) -> dict:
        """Algorithmic work of one invocation, counted from the shapes.

        FLOPs of the product at the bf16 peak (DEFAULT precision is one
        bf16 pass); compulsory bytes read A and B and write C once in
        f32; channel bytes are each ring token written once and read
        once: P feeders and P*(P-1) forwarding PEs push K blocks on each
        of the A and B sides, and P*P PEs push one C block."""
        P, n, K = self.P, self.n, self.K
        m, k = P * n, K * n
        tokens = 2 * P * P * K + P * P
        return {"flops": 2 * m * m * k, "flops_peak": "bf16",
                "bytes": 4 * (m * k + k * m + m * m),
                "channel_bytes": 2 * tokens * n * n * 4}


def build(cfg: dict, traffic: dict, seed: int) -> Graph:
    return Graph(cfg, traffic, seed)


def _default_rounding() -> str:
    """What XLA's DEFAULT precision does to f32 matmul operands on this
    platform: a TPU multiplies them rounded to bfloat16, a CPU as they
    are."""
    import jax
    return "bfloat16" if jax.default_backend() == "tpu" else "float32"


def _round(x: np.ndarray, dtype: str) -> np.ndarray:
    import ml_dtypes
    return x.astype(getattr(ml_dtypes, dtype, dtype)).astype(np.float64)


def reference(inputs: dict) -> np.ndarray:
    """``A @ B`` at the configuration's precision, in float64 numpy, in
    row blocks: the operands rounded to nearest even as DEFAULT
    precision rounds them (bfloat16 on a TPU, which accumulates in
    float32), the products summed exactly."""
    A = _round(inputs["A"], inputs["rounding"])
    B = _round(inputs["B"], inputs["rounding"])
    out = np.empty((A.shape[0], B.shape[1]), np.float64)
    step = 1024
    for r in range(0, A.shape[0], step):
        out[r:r + step] = A[r:r + step] @ B
    return out


def control(inputs: dict) -> np.ndarray:
    """The reference one precision step below the configuration's: the
    float32 output written in bfloat16 (accumulation exact), which would
    halve the bytes of every C token and of the writeback."""
    return _round(reference(inputs), "bfloat16")


def compare(out: np.ndarray, ref: np.ndarray) -> dict:
    """``rel_rms_err``: the Frobenius norm of the error over the
    reference's; ``max_err``: the largest error over the reference's
    root mean square."""
    err = out.astype(np.float64) - ref
    rms = float(np.sqrt(np.mean(ref * ref)))
    return {"rel_rms_err": float(np.sqrt(np.mean(err * err))) / rms,
            "max_err": float(np.max(np.abs(err))) / rms}
