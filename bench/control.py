"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 3

For each seed of ``--seeds``: the program's reading of every compared
number, as a benchmark run takes it (set-up, a window of ``--seconds``
at the cell's own load, the worst of the sampled invocations against the
float64 reference).  For each seed of ``--control-seeds``: the control's
reading, the configuration's reference computed one precision step below
the configuration's (``control`` in the configuration's module), on the
inputs of the window's last invocation.  One process reads every seed;
one JSON line per reading, then a summary line with the largest program
reading and the smallest control reading of each number.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def readings(name: str, seeds: list, control_seeds: list, seconds: float,
             require_tpu: bool = True, sizes: dict = None):
    """Yield ``{"seed", "kind": "program"|"control", numbers...}``."""
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    cs = run.cell_spec(bench, name)
    for part, over in (sizes or {}).items():
        getattr(cs, part).update(over)
    run.use_checkout()
    from repro.core.compile_cache import enable_persistent_cache
    run.device_info(cs.chips, require_tpu)
    enable_persistent_cache()
    watch = run.CompileWatch()
    mod = run.load_module(cs.module)
    for seed in sorted(set(seeds) | set(control_seeds)):
        g = mod.build(cs.cfg, cs.traffic, seed)
        for _ in range(run.WARMUP):
            t0 = time.perf_counter()
            run.invoke(g)
        w = run.window(g, seconds, seed, time.perf_counter() - t0, watch,
                       lambda _: contextlib.nullcontext())
        if seed in seeds:
            ok, rows = run.check(mod, g, w.outs, cs.traffic["limits"])
            yield {"seed": seed, "kind": "program", "invocations": w.n,
                   "failed": w.failed, "correct": ok,
                   **{k: r["value"] for k, r in rows.items()}}
        if seed in control_seeds:
            inputs = g.inputs_at(w.n - 1)
            got = mod.compare(mod.control(inputs), mod.reference(inputs))
            yield {"seed": seed, "kind": "control", **got}
        del g, w


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    lo, up = {}, {}
    try:
        for r in readings(args.workload, seeds, cseeds, args.seconds):
            print(json.dumps(r), flush=True)
            nums = {k: v for k, v in r.items() if isinstance(v, float)}
            for k, v in nums.items():
                if r["kind"] == "program":
                    lo[k] = max(lo.get(k, 0.0), v)
                else:
                    up[k] = min(up.get(k, float("inf")), v)
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"workload": args.workload, "program_max": lo,
                      "control_min": up}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
