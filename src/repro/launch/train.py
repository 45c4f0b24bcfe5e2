"""Training launcher: one process on one device (no mesh is built).

On a CPU (``JAX_PLATFORMS=cpu``) pass ``--reduced`` to run the identical
code path at smoke scale.

  python -m repro.launch.train --arch smollm-135m --reduced --steps 100
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.train.checkpoint import save_checkpoint
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.trainer import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--scan", action="store_true",
                    help="scanned-layer layout (production; default for >8 layers)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    use_scan = args.scan or cfg.num_layers > 8
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"layout={'scan' if use_scan else 'loop'} devices={jax.device_count()}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)
    params = model.init_params(jax.random.PRNGKey(0))
    if use_scan:
        params = model.to_scan(params)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, opt_cfg, remat=True, scan=use_scan))

    data = lm_batches(args.batch, args.seq_len, cfg.vocab_size, seed=0)
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, m = step(params, opt, batch)
        if (i + 1) % 10 == 0 or i == 0:
            print(f"step {i+1:5d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}  gnorm {float(m['grad_norm']):.2f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "step": args.steps})
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
