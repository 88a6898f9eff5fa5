"""Training driver: train a small LM on the synthetic next-token stream with
the full production loop — sharded (if >1 device), checkpointed, straggler-
monitored, crash-restartable.

  PYTHONPATH=src python examples/train_lm.py --steps 200
  PYTHONPATH=src python examples/train_lm.py --steps 400   # resumes at 200
"""
import argparse
import os

import jax
import jax.numpy as jnp

from repro.data.lm_pipeline import Prefetcher, synthetic_lm_batches
from repro.models.transformer import TransformerConfig, init, loss_fn
from repro.runtime import configure_compile_cache
from repro.training.fault_tolerance import StragglerDetector, resume_or_init
from repro.training.optimizer import adamw, cosine_schedule
from repro.training.train_loop import (Trainer, TrainerConfig, init_state,
                                       make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    configure_compile_cache()

    # ~10M params — sized so a few hundred CPU steps visibly learn the
    # synthetic Markov stream; the same loop drives the pod-scale configs
    cfg = TransformerConfig(name="lm-10m", n_layers=4, d_model=256, n_heads=8,
                            n_kv_heads=4, d_ff=688, vocab_size=512,
                            dtype="float32", attn_impl="naive")
    opt = adamw(cosine_schedule(3e-3, warmup=20, total=args.steps),
                weight_decay=0.01)

    def fresh():
        params = init(jax.random.PRNGKey(0), cfg)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"init {n/1e6:.1f}M params")
        return init_state(params, opt)

    state, start = resume_or_init(args.ckpt, fresh)
    if start:
        print(f"resumed from checkpoint at step {start}")

    step_fn = make_train_step(lambda p, b: loss_fn(p, cfg, b), opt, donate=False)
    data = Prefetcher(synthetic_lm_batches(cfg.vocab_size, args.batch, args.seq,
                                           start_step=start))
    det = StragglerDetector()
    trainer = Trainer(TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                                    ckpt_every=50, log_every=10),
                      step_fn, state, data, straggler_detector=det)
    trainer.run()
    if det.events:
        print(f"straggler events: {[(s, f'{t:.2f}s') for s, t, _ in det.events]}")
    first, last = trainer.history[0]["loss"], trainer.history[-1]["loss"]
    print(f"\nloss {first:.3f} -> {last:.3f} over {args.steps - start} steps "
          f"(mean step {det.mean_step_s*1e3:.0f} ms)")


if __name__ == "__main__":
    main()
