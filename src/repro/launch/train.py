"""Training launcher: uBFT-coordinated, checkpoint/restart fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \\
      --steps 50 --ckpt-dir /tmp/ckpt [--resume] [--byzantine 2]

Runs 2f+1 replicated trainers on the in-process harness: every step id and
data range is agreed through uBFT consensus, gradients/params are
fingerprint-attested (a Byzantine replica is flagged), and checkpoint cuts
are consensus-ordered before being written.  ``--resume`` restarts from the
latest attested checkpoint — kill the process mid-run and relaunch to see
fault tolerance end-to-end.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import init_params, params_count
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.steps import make_train_step
from repro.runtime.trainer import ReplicatedTrainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--byzantine", type=int, default=None,
                    help="index of a replica to corrupt (demo detection)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch))
    opt_cfg = AdamWConfig(lr=args.lr)

    start_step = 0
    if args.resume:
        try:
            start_step, params0, opt0 = load_checkpoint(args.ckpt_dir)
            print(f"[resume] from attested checkpoint @ step {start_step}")
        except FileNotFoundError:
            params0 = init_params(cfg, jax.random.PRNGKey(0))
            opt0 = adamw_init(params0, opt_cfg)
    else:
        params0 = init_params(cfg, jax.random.PRNGKey(0))
        opt0 = adamw_init(params0, opt_cfg)

    # three independent training replicas (each its own copy of the state)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg=opt_cfg))
    replicas = [{"params": params0, "opt": opt0} for _ in range(3)]
    losses = []

    def train_one(idx: int, step: int, data_epoch: int):
        b = pipe.global_batch(start_step + step)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        r = replicas[idx]
        r["params"], r["opt"], m = step_fn(r["params"], r["opt"], batch)
        if idx == 0:
            losses.append(float(m["loss"]))
        return int(m["grad_fp"]), int(m["param_fp"]), {"loss": float(m["loss"])}

    rt = ReplicatedTrainer.build(train_one)
    t0 = time.time()
    done = 0
    while done < args.steps:
        n = min(args.ckpt_every, args.steps - done)
        recs = rt.run_steps(n, byzantine_replica=args.byzantine)
        done += n
        step = start_step + done
        fp = save_checkpoint(args.ckpt_dir, step,
                             replicas[0]["params"], replicas[0]["opt"])
        rt.agree_checkpoint(step, fp)
        flagged = recs[-1]["flagged"]
        print(f"[step {step}] loss={losses[-1]:.4f} "
              f"ckpt_fp={fp} flagged={flagged} "
              f"({(time.time() - t0) / done:.2f}s/step)")
    print(f"params={params_count(replicas[0]['params'])} "
          f"final_loss={losses[-1]:.4f} "
          f"coordinator_checkpoints={rt.coordinator_state.checkpoints}")


if __name__ == "__main__":
    main()
