"""Fault-tolerant training launcher.

Drives the pjit train step with: auto-resume from the latest checkpoint,
async atomic checkpointing every N steps, a step-time watchdog (straggler
detection), deterministic resumable data sharding, and a failure-injection
flag that kills the process at a chosen step to exercise the restart path
(tests/test_fault_tolerance.py runs this end-to-end).

Usage:
  python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --steps 50 --ckpt-every 10 --ckpt-dir /tmp/run1
  # kill it at any point, rerun the same command: it resumes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint.ckpt import CheckpointManager
from repro.core.yoco_linear import YocoConfig
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_mod
from repro.models.model import ModelRuntime
from repro.optim import adamw
from repro.runtime import train_step as TS


class StepWatchdog:
    """Flags straggling steps (> ``factor`` x the median of recent steps).
    On a real cluster this feeds the controller that evicts the slow host;
    here it logs and counts (the mechanism under test)."""

    def __init__(self, factor: float = 3.0, window: int = 20):
        self.times = []
        self.factor = factor
        self.window = window
        self.straggler_events = 0

    def observe(self, dt: float) -> bool:
        import statistics
        slow = (len(self.times) >= 5
                and dt > self.factor * statistics.median(
                    self.times[-self.window:]))
        self.times.append(dt)
        if slow:
            self.straggler_events += 1
        return slow


def train(arch: str, *, smoke: bool = True, steps: int = 50,
          global_batch: int = 8, seq_len: int = 64, lr: float = 1e-3,
          grad_accum: int = 1, ckpt_every: int = 10,
          ckpt_dir: str = '/tmp/repro_ckpt', mode: str = 'bf16',
          simulate_failure_at: int = -1, log_every: int = 10,
          seed: int = 0, quiet: bool = False) -> dict:
    cfg = configs.get(arch, smoke=smoke)
    yoco = YocoConfig(mode=mode)
    opt_cfg = adamw.OptConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                              total_steps=steps, grad_accum=grad_accum)
    dc = synthetic.for_arch(cfg, seed=1234 + seed, global_batch=global_batch,
                            seq_len=seq_len)

    params = model_mod.init_params(jax.random.key(seed), cfg)
    opt_state = adamw.init(params, opt_cfg)
    step_fn = jax.jit(TS.make_train_step(cfg, yoco, opt_cfg=opt_cfg),
                      donate_argnums=(0, 1))

    mgr = CheckpointManager(ckpt_dir, keep=3)
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        (params, opt_state), manifest = mgr.restore((params, opt_state))
        start = manifest['step']
        if not quiet:
            print(f'[resume] restored step {start} from {ckpt_dir}')

    wd = StepWatchdog()
    history = []
    for step in range(start, steps):
        batch = synthetic.make_batch(dc, step)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics['loss'])
        dt = time.time() - t0
        slow = wd.observe(dt)
        history.append(loss)
        if not quiet and (step % log_every == 0 or step == steps - 1):
            print(f'step {step:5d} loss {loss:.4f} '
                  f'gnorm {float(metrics["grad_norm"]):.3f} '
                  f'lr {float(metrics["lr"]):.2e} {dt*1e3:.0f} ms'
                  + (' [STRAGGLER]' if slow else ''))
        if simulate_failure_at == step:
            mgr.wait()                        # die BEFORE this step's save —
            print(f'[failure-sim] dying at step {step}', flush=True)
            os._exit(17)                      # hard kill mid-interval
        if ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state),
                     extra=dict(loss=loss, arch=arch))
    mgr.wait()
    mgr.save(steps, (params, opt_state), extra=dict(loss=history[-1],
                                                    arch=arch))
    mgr.wait()
    return dict(final_loss=history[-1], first_loss=history[0],
                steps_run=len(history), straggler_events=wd.straggler_events,
                history=history)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='stablelm-1.6b')
    ap.add_argument('--smoke', action='store_true', default=True)
    ap.add_argument('--full', dest='smoke', action='store_false')
    ap.add_argument('--steps', type=int, default=50)
    ap.add_argument('--global-batch', type=int, default=8)
    ap.add_argument('--seq-len', type=int, default=64)
    ap.add_argument('--lr', type=float, default=1e-3)
    ap.add_argument('--grad-accum', type=int, default=1)
    ap.add_argument('--ckpt-every', type=int, default=10)
    ap.add_argument('--ckpt-dir', default='/tmp/repro_ckpt')
    ap.add_argument('--mode', default='bf16',
                    choices=['bf16', 'qat', 'w8a8', 'analog_sim'])
    ap.add_argument('--simulate-failure-at', type=int, default=-1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                global_batch=args.global_batch, seq_len=args.seq_len,
                lr=args.lr, grad_accum=args.grad_accum,
                ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                mode=args.mode,
                simulate_failure_at=args.simulate_failure_at)
    print(json.dumps({k: v for k, v in out.items() if k != 'history'}))


if __name__ == '__main__':
    main()
