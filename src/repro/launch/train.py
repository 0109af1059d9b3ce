"""Training launcher: ``--arch`` selects any assigned architecture.

On this CPU container the launcher executes REDUCED configs end-to-end
(real steps, checkpoints, resume); on a TPU fleet the same entry point
runs the full config — the step builders in repro/configs are identical,
only the mesh and scale change.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 50 [--ckpt-dir /tmp/ck] [--resume]
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED, get_arch
from repro.dist.sharding import default_rules
from repro.launch.mesh import make_local_mesh
from repro.train.loop import TrainLoopConfig, Trainer
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def lm_trainer(arch, args, mesh, rules):
    from repro.data.loader import LMDataConfig, SyntheticLMStream
    from repro.models import transformer as T

    cfg = arch.smoke_cfg if not args.full else arch.cfg
    params = T.init_params(cfg, jax.random.key(args.seed))
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(T.lm_loss)(params, batch, cfg, rules)
        params, opt_state, metrics = adamw_update(ocfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics}

    stream = SyntheticLMStream(
        LMDataConfig(vocab=cfg.vocab, batch=args.batch, seq_len=args.seq))
    return Trainer(
        jax.jit(step_fn), params, init_opt_state(params), stream,
        TrainLoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                        log_every=max(1, args.steps // 10),
                        ckpt_dir=args.ckpt_dir),
        to_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ASSIGNED)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="full published config (TPU-scale; not for CPU)")
    args = ap.parse_args()

    arch = get_arch(args.arch)
    mesh = make_local_mesh()
    rules = default_rules(mesh)

    if arch.family == "lm":
        trainer = lm_trainer(arch, args, mesh, rules)
        if args.resume and trainer.try_resume():
            print(f"resumed from step {trainer.step}")
        with mesh:
            out = trainer.run()
        for h in out["history"]:
            print(f"step {h['step']:>5}  loss {h['loss']:.4f}  "
                  f"{h['sec_per_step']*1e3:7.1f} ms")
        print(f"final loss {out['final_loss']:.4f}")
        return

    # GNN / recsys: run the arch's training smoke path N times as a demo
    # loop (their full-scale steps are exercised by the dry-run).
    print(f"[{args.arch}] family={arch.family}: running reduced train steps")
    out = arch.smoke_run()
    print(f"one-step diagnostics: {out}")


if __name__ == "__main__":
    main()
