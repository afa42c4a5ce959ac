"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant training loop on the locally available devices
(tests/laptops use reduced configs; a real cluster launches one process per
host with the same entry point — the mesh derives from jax.device_count()).
"""

import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.data import DataConfig, SyntheticLMStream, make_global_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.launch.sharding import make_shard_hook
from repro.models import build_model
from repro.models.config import ShapeConfig
from repro.optim import AdamWConfig
from repro.train import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = make_host_mesh(args.data, args.model)
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    stream = SyntheticLMStream(cfg, shape, DataConfig())
    opt_cfg = AdamWConfig(
        base_lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
        total_steps=args.steps,
    )

    with jax.set_mesh(mesh):
        model = build_model(cfg, remat=True, shard=make_shard_hook(mesh))
        trainer = Trainer(
            model, opt_cfg,
            ckpt_dir=os.path.join(args.ckpt_dir, cfg.name),
            ckpt_every=args.ckpt_every, accum_steps=args.accum_steps,
            heartbeat=lambda step, dt: (
                print(f"  step {step}: {dt*1e3:.0f} ms") if step % 20 == 0
                else None
            ),
        )
        params, opt_state, start = trainer.init_or_restore(
            jax.random.PRNGKey(0)
        )
        if start:
            print(f"resumed from step {start}")

        from jax.sharding import PartitionSpec as P
        dp = dp_axes(mesh)

        def batches(step):
            return make_global_batch(stream.batch(step), mesh, P(dp))

        params, opt_state, hist = trainer.run(
            params, opt_state, batches, start, args.steps
        )
    for h in hist:
        print({k: round(v, 4) for k, v in h.items()})
    print(f"final loss: {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
