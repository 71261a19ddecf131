"""Production training launcher: ``--arch <id>`` on the production mesh.

On this CPU container it is exercised with ``--smoke`` (reduced config,
1-device mesh); on a pod the same script runs the full config — the mesh,
sharding rules, trainer, and checkpointing are identical code paths.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 20
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_test_mesh
from repro.models import init_params
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.train import (Trainer, TrainerConfig, TrainOptions,
                         make_train_step)
from repro.train import sharding as shd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + local mesh (CPU container)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)

    n_dev = len(jax.devices())
    if args.smoke or n_dev < 256:
        shape = (n_dev, 1)
    else:
        shape = (16, 16)
    mesh = make_test_mesh(shape)
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name} "
          f"({cfg.param_count()/1e6:.1f}M params)")

    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    opt_state = init_opt_state(params)
    p_specs, dropped = shd.param_specs(params, mesh)
    for d in dropped:
        print(f"[sharding] {d}")
    o_specs = {"m": p_specs, "v": p_specs, "count": P()}
    if args.zero1:
        o_specs = {"m": shd.zero1_specs(p_specs, params, mesh),
                   "v": shd.zero1_specs(p_specs, params, mesh), "count": P()}

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    batch0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    b_specs = shd.batch_specs(batch0, mesh)

    opts = TrainOptions(microbatches=args.microbatches, zero1=args.zero1)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    step = make_train_step(cfg, opt_cfg, opts)
    with mesh:
        jstep = jax.jit(step, in_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), (p_specs, o_specs, b_specs),
            is_leaf=lambda x: isinstance(x, P)))

        def init_state():
            p = init_params(key, cfg)
            return {"params": p, "opt": init_opt_state(p)}

        trainer = Trainer(
            TrainerConfig(total_steps=args.steps, checkpoint_every=25,
                          checkpoint_dir=args.ckpt_dir, log_every=10),
            jstep, data, init_state,
            to_device=lambda b: {k: jnp.asarray(v) for k, v in b.items()})
        trainer.run()
    h = trainer.metrics_history
    print(f"done: loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
