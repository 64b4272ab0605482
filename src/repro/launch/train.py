"""End-to-end training driver with fault tolerance.

Runnable at CPU scale (smoke configs) and structured for the production
mesh: sharded jit step, atomic checkpoints + auto-resume, heartbeat files
for the cluster monitor, straggler detection, simulated-failure injection
for restart testing.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt --resume
"""

from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.metadata_index import MetadataIndex
from repro.data.tokens import TokenPipeline
from repro.dist import checkpoint as ckpt
from repro.dist.sharding import (batch_shardings, opt_shardings,
                                 param_shardings, zero_pad_for)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_cli_mesh
from repro.models import transformer
from repro.models.common import ShardingCtx
from repro.optim import OptConfig, init_opt_state
from repro.train import train_step


class Heartbeat:
    """Per-host liveness + progress file for the cluster monitor.

    A real deployment points this at shared storage; the monitor restarts
    hosts whose heartbeat goes stale and triggers elastic re-entry."""

    def __init__(self, path, host_id=0):
        self.path = path
        self.host_id = host_id

    def beat(self, step, status="ok", **kv):
        rec = {"host": self.host_id, "step": step, "t": time.time(),
               "status": status, **kv}
        tmp = f"{self.path}.tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, self.path)


class StragglerMonitor:
    """Flags steps slower than ``factor`` x the running median.

    On TPU pods the mitigation is to exclude the slow host at the next
    checkpoint boundary (elastic re-entry with n-1 hosts); here we record
    the event so the launcher can act."""

    def __init__(self, factor=3.0, warmup=5):
        self.durations = []
        self.factor = factor
        self.warmup = warmup
        self.events = []

    def observe(self, step, dt):
        self.durations.append(dt)
        if len(self.durations) <= self.warmup:
            return False
        med = float(np.median(self.durations[-50:]))
        if dt > self.factor * med:
            self.events.append({"step": step, "dt": dt, "median": med})
            return True
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default=None, help="data,model (default: all devices data-parallel)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--heartbeat", default=None)
    ap.add_argument("--simulate-failure-at", type=int, default=0,
                    help="crash at this step (restart/fault-tolerance test)")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = replace(cfg, remat=True)

    mesh = make_cli_mesh(args.mesh)
    opt_cfg = OptConfig(lr=args.lr, total_steps=max(args.steps, 10),
                        warmup_steps=max(2, args.steps // 20))

    with ShardingCtx(mesh):
        p_sh = param_shardings(mesh, cfg)
        o_sh = opt_shardings(mesh, cfg)
        b_sh = batch_shardings(mesh, cfg, "train")
        b_sh.pop("patches", None)
        b_sh.pop("mrope_positions", None)

        params = jax.jit(
            lambda k: transformer.init_params(k, cfg),
            out_shardings=p_sh)(jax.random.PRNGKey(0))
        # ZeRO-1 flat moments: pad to the data-axis size so every leaf
        # shards (dist/sharding.py opt_shardings)
        opt_state = jax.jit(
            partial(init_opt_state, zero_pad=zero_pad_for(mesh)),
            out_shardings=o_sh)(params)

        step_fn = jax.jit(
            partial(train_step, cfg=cfg, opt_cfg=opt_cfg,
                    microbatches=args.microbatches),
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1))

        pipeline = TokenPipeline(cfg.vocab_size, args.batch, args.seq)
        meta_index = MetadataIndex()
        start_step = 0

        if args.resume and args.ckpt_dir and ckpt.available_steps(args.ckpt_dir):
            state_like = {"params": params, "opt": opt_state}
            restored, start_step, extra = ckpt.restore(
                args.ckpt_dir, state_like,
                shardings={"params": p_sh, "opt": o_sh})
            params, opt_state = restored["params"], restored["opt"]
            if "pipeline" in extra:
                pipeline.restore(extra["pipeline"])
            print(f"[train] resumed from step {start_step}", flush=True)
            if start_step >= args.steps:
                # restart of an already-finished run (cluster monitors do
                # this); exit cleanly instead of entering an empty loop
                print(f"[train] already at step {start_step} >= --steps "
                      f"{args.steps}; nothing to do", flush=True)
                return []

        hb = Heartbeat(args.heartbeat) if args.heartbeat else None
        straggler = StragglerMonitor()
        metrics_log = []
        t_start = time.time()

        for step in range(start_step, args.steps):
            if args.simulate_failure_at and step == args.simulate_failure_at:
                print(f"[train] simulating failure at step {step}", flush=True)
                os._exit(42)
            t0 = time.time()
            batch_np, meta = pipeline.next_batch()
            meta_index.add_batch(meta)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            loss = float(m["loss"])
            dt = time.time() - t0
            if straggler.observe(step, dt):
                print(f"[train] straggler step {step}: {dt:.2f}s", flush=True)
            if hb:
                hb.beat(step, loss=loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {float(m['grad_norm']):.3f}  {dt*1e3:.0f} ms",
                      flush=True)
            metrics_log.append({"step": step, "loss": loss, "dt": dt})
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(
                    args.ckpt_dir, step + 1,
                    {"params": params, "opt": opt_state},
                    extra={"pipeline": pipeline.snapshot()})

        ckpt.wait_pending()
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps,
                      {"params": params, "opt": opt_state},
                      extra={"pipeline": pipeline.snapshot()})

        # data-plane bitmap index demo: curation query over trained batches
        # (add_batch sealed segments incrementally; no monolithic build)
        rows, scanned = meta_index.query(where={"domain": 3})
        elapsed = time.time() - t_start
        print(f"[train] done in {elapsed:.1f}s; metadata index "
              f"{meta_index.size_words()} words; domain=3 -> {len(rows)} rows "
              f"({scanned} compressed words scanned)", flush=True)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump({"metrics": metrics_log,
                           "stragglers": straggler.events}, f)
        first, last = metrics_log[0]["loss"], metrics_log[-1]["loss"]
        print(f"[train] loss {first:.4f} -> {last:.4f}", flush=True)
        return metrics_log


if __name__ == "__main__":
    main()
