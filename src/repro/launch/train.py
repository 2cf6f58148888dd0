"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt /tmp/zebra_run

Assembles the full production path — mesh from live devices, sharded jit
train step (FSDP+TP+Zebra), counter-indexed data stream, fault-tolerant
supervisor with async checkpoints + auto-resume — and runs it. On this CPU
container use --reduced; on a real slice drop it and the exact same code
drives the full config (jax.distributed.initialize() is called when the
environment advertises multiple processes).
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..data import LMDatasetConfig, StreamingLoader, lm_batch
from ..distributed import sharding as shd
from ..ft import FTConfig, StepSupervisor
from ..models.lm import LM
from ..optim import adamw, warmup_cosine
from .cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import make_train_state_shape, make_train_step, train_state_specs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compress", default="bf16", choices=["none", "bf16", "int8"])
    ap.add_argument("--t-obj", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    cfg = cfg.replace(zebra_t_obj=args.t_obj)
    mesh = make_host_mesh(model=args.model_parallel)
    model = LM(cfg)
    opt = adamw(warmup_cosine(args.lr, max(args.steps // 10, 1), args.steps))

    state_shape, init_fn = make_train_state_shape(model, opt, args.compress)
    sspec = train_state_specs(state_shape, cfg, mesh)
    sshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), sspec,
                                    is_leaf=lambda x: isinstance(x, P))
    bshard = {"tokens": NamedSharding(mesh, shd.batch_spec(mesh, 2))}

    step_fn = jax.jit(make_train_step(model, opt, mesh, args.compress),
                      in_shardings=(sshard, bshard),
                      out_shardings=(sshard, None), donate_argnums=(0,))

    ds = LMDatasetConfig(vocab=cfg.vocab, seed=args.seed)
    loader = StreamingLoader(
        lambda b, s: {"tokens": lm_batch(ds, b, args.seq, s)},
        args.batch, host_id=jax.process_index(), n_hosts=jax.process_count())

    sup = StepSupervisor(FTConfig(ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every))

    def fresh():
        with mesh:
            return jax.jit(init_fn, out_shardings=sshard)(
                jax.random.PRNGKey(args.seed))
    state, start, extra = sup.resume_or_init(fresh)
    loader.restore(extra.get("loader_step", start))
    print(f"[train] {cfg.name} params={cfg.param_counts()['total']:,} "
          f"mesh={dict(mesh.shape)} start_step={start}")

    def log(step, m):
        if step % 10 == 0 or step <= 2:
            print(f"step {step:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                  f"zreg={m['zebra_reg']:.4f} zf={m['zero_frac']:.3f} "
                  f"gnorm={m['grad_norm']:.2f}", flush=True)

    state, step = sup.run(state, step_fn, loader, args.steps, start,
                          loader_state_fn=loader.state, on_metrics=log)
    if sup.straggler_events:
        print(f"[ft] {len(sup.straggler_events)} straggler step(s) flagged")
    print(f"[train] done at step {step}; checkpoints in {args.ckpt}")


if __name__ == "__main__":
    main()
