"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 200 --batch 8 --seq 128

Integrates every substrate layer: config registry, data pipeline, sharded
init, jit'd train step (scan-over-layers = the paper's compile-once
insight), AdamW(+ZeRO-1 state sharding), checkpoint/restart
(``--resume`` is implied — the driver *always* restores the latest complete
checkpoint if one exists, so preempted jobs just re-run the same command),
preemption guard, straggler detection and optional int8 gradient
compression.

The default is the full published config (``--reduced`` for a tiny
same-family one); ``chip_smoke.py`` runs three full-width qwen3-0.6b
steps on one TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ckpt import CheckpointManager
from ..configs import get_config
from ..core.compile_cache import enable_persistent_cache
from ..data import make_pipeline
from ..distributed import sharding as shd
from ..ft import PreemptionGuard, StragglerDetector, resume_or_init
from ..models import lm
from ..optim import AdamWConfig, adamw_init, adamw_update, opt_state_specs
from .mesh import make_host_mesh
from .steps import make_train_step


def train(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas attention/SSD kernels (interpret on CPU)")
    ap.add_argument("--metrics", default=None,
                    help="write JSONL metrics to this path")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.with_reduced()
    print(f"[train] arch={cfg.name} family={cfg.family} "
          f"params={cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")

    mesh = make_host_mesh(args.model_parallel)
    pol = shd.for_mesh(mesh)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 1))

    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shd.param_specs(cfg, mesh, pol))
    oshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          opt_state_specs(cfg, mesh, pol))

    data = make_pipeline(cfg.vocab, args.seq, args.batch, seed=args.seed)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    guard = PreemptionGuard()
    straggler = StragglerDetector()

    # ---- init or resume --------------------------------------------------
    # resume_or_init goes through digest-verified restore_latest: a
    # checkpoint corrupted after publish (torn file, bad digest) is
    # skipped and the scan falls back to the previous good step, so a
    # kill-and-rerun always lands on sound state (tests/test_launch.py)
    aparams = lm.abstract_params(cfg)
    aopt = jax.eval_shape(partial(adamw_init, c=opt), aparams)

    def _init():
        with mesh:
            params = jax.jit(
                partial(lm.init_params, cfg),
                out_shardings=pshard)(jax.random.key(args.seed))
            opt_state = jax.jit(partial(adamw_init, c=opt),
                                out_shardings=oshard)(params)
        return params, opt_state

    start, params, opt_state, extra = resume_or_init(
        mgr, _init, aparams, aopt,
        param_shardings=pshard, opt_shardings=oshard)
    if start > 0:
        data.load_state_dict(extra.get("data", {"step": start}))
        print(f"[train] resumed from checkpoint step {start}")

    step_fn = make_train_step(cfg, opt, use_kernel=args.use_kernel)
    bspec = shd.batch_spec(cfg, mesh, args.batch, pol)
    bshard = {k: NamedSharding(mesh, v) for k, v in bspec.items()}
    jitted = jax.jit(step_fn,
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))

    metrics_f = open(args.metrics, "a") if args.metrics else None
    losses = []
    t_run = time.perf_counter()
    step = start
    if start >= args.steps:
        print(f"[train] checkpoint already at step {start} >= "
              f"--steps {args.steps}; nothing to do")
        return 0
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = {k: jax.device_put(v, bshard[k])
                 for k, v in data.next_batch().items()}
        params, opt_state, m = jitted(params, opt_state, batch)
        loss = float(m["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        slow = straggler.observe(dt)
        if (step + 1) % args.log_every == 0 or step == start:
            print(f"[train] step {step+1:5d} loss {loss:.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.3f}"
                  f" {dt*1e3:.0f}ms{'  [straggler]' if slow else ''}")
        if metrics_f:
            metrics_f.write(json.dumps(
                {"step": step + 1, "loss": loss, "dt": dt}) + "\n")
        if (step + 1) % args.ckpt_every == 0 or guard.requested:
            mgr.save(step + 1, params, opt_state,
                     extra={"data": data.state_dict()}, blocking=False)
        if guard.requested:
            mgr.wait()
            print(f"[train] preempted at step {step+1}; checkpoint saved")
            return 0

    mgr.save(step + 1, params, opt_state,
             extra={"data": data.state_dict()})
    wall = time.perf_counter() - t_run
    tok_s = (args.steps - start) * args.batch * args.seq / max(wall, 1e-9)
    print(f"[train] done: {args.steps - start} steps in {wall:.1f}s "
          f"({tok_s:,.0f} tok/s); loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if metrics_f:
        metrics_f.close()
    if len(losses) >= 20 and not (np.mean(losses[-5:]) <
                                  np.mean(losses[:5])):
        print("[train] WARNING: loss did not decrease")
        return 1
    return 0


if __name__ == "__main__":
    enable_persistent_cache()
    sys.exit(train())
