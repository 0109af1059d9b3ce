"""End-to-end training driver: ~100M-param LM for a few hundred steps on CPU,
with checkpointing, fault-tolerant resume, and straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--params 100]

(--params 100 builds the ~100M config; the default driver uses ~8M so the
example completes in minutes on 1 CPU core. Both run the same stack.)
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.data.loader import LMDataConfig, SyntheticLMStream
from repro.dist.sharding import default_rules
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.models.layers import LMConfig
from repro.train.loop import TrainLoopConfig, Trainer
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def build(params_m: int):
    if params_m >= 100:
        # ~101M params: 12L x d512 (GQA 8/4) x ff2048, vocab 32k
        return LMConfig(name="lm100m", n_layers=12, d_model=512, n_heads=8,
                        n_kv_heads=4, head_dim=64, d_ff=2048, vocab=32_768,
                        dtype=jnp.float32, q_chunk=128, remat=False)
    # ~8M params: fast CPU demo, same code path
    return LMConfig(name="lm8m", n_layers=4, d_model=192, n_heads=6,
                    n_kv_heads=2, head_dim=32, d_ff=768, vocab=8_192,
                    dtype=jnp.float32, q_chunk=64, remat=False)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--params", type=int, default=8, help="M params (8|100)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = build(args.params)
    mesh = make_local_mesh()
    rules = default_rules(mesh)
    print(f"== {cfg.name}: {cfg.n_params/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}")

    params = T.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)

    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(T.lm_loss)(params, batch, cfg, rules)
        params, opt_state, metrics = adamw_update(ocfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics}

    stream = SyntheticLMStream(
        LMDataConfig(vocab=cfg.vocab, batch=args.batch, seq_len=args.seq))
    ckpt_dir = tempfile.mkdtemp(prefix="flexvec_lm_")
    trainer = Trainer(
        jax.jit(step_fn), params, opt, stream,
        TrainLoopConfig(total_steps=args.steps, ckpt_every=50, log_every=10,
                        ckpt_dir=ckpt_dir),
        to_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
    )
    resumed = trainer.try_resume()
    print(f"== resume from checkpoint: {resumed}")
    with mesh:
        out = trainer.run()
    for h in out["history"]:
        print(f"   step {h['step']:>4}  loss {h['loss']:.4f}  "
              f"{h['sec_per_step']*1e3:7.1f} ms/step"
              + ("  [straggler]" if h["straggler"] else ""))
    print(f"== final loss {out['final_loss']:.4f} "
          f"(start {out['history'][0]['loss']:.4f}); "
          f"straggler events: {len(out['straggler_events'])}; "
          f"checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
