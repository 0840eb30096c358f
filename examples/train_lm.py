"""Train an LM with the full production loop (checkpoint/restart included).

Default: a reduced xlstm config for a fast CPU demo. ``--full-100m`` trains a
~100M-parameter tinyllama-family config for a few hundred steps (hours on
this CPU; the code path is identical to the TPU deployment).

    PYTHONPATH=src python examples/train_lm.py --steps 30
    PYTHONPATH=src python examples/train_lm.py --full-100m --steps 300
"""
import argparse
import dataclasses

import jax.numpy as jnp

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.launch.train import train
from repro.models.model import ModelApi


def hundred_m_config():
    base = configs.get_config("tinyllama-1.1b")
    return dataclasses.replace(
        base, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
        superlayer_repeat=12, n_layers=12, head_dim=64, vocab_size=32000,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        grad_accum=1, remat=False).validate()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    cfg = hundred_m_config() if args.full_100m else configs.get_reduced("xlstm-125m")
    print(f"training {cfg.name} ({ModelApi(cfg).param_count():,} params) "
          f"for {args.steps} steps")
    _, _, losses = train(cfg, args.steps, args.batch, args.seq, args.ckpt_dir,
                         ckpt_every=20, log_every=5)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
