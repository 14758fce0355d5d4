"""CLI: train an assigned architecture (reduced or full config).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --seq-len 128 --batch 8 --algorithm vfpc --ckpt ckpt/
"""

from __future__ import annotations

import argparse

import jax

from repro.core.policy import ALGORITHMS
from repro.data.tokens import TokenPipeline
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train import TrainLoop, init_train_state, restore_elastic
from repro.train.loop import state_shardings
from repro import sharding
from repro.launch.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--algorithm", default="vfpc", choices=sorted(ALGORITHMS),
                    help="fused-phase width policy (paper technique)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="shard over all local devices")
    args = ap.parse_args()
    enable_compile_cache()

    model = build_model(args.arch, smoke=args.smoke)
    cfg = model.cfg
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.batch)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps, compress=args.compress_grads)
    mesh = rules = None
    if args.mesh:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
        rules = sharding.make_rules()

    state = None
    if args.ckpt:
        tmpl = jax.eval_shape(
            lambda k: init_train_state(model, opt, k), jax.random.PRNGKey(0))
        if mesh is not None:
            state, step = restore_elastic(args.ckpt, model, opt, mesh, rules, tmpl)
        else:
            from repro.train import load_checkpoint
            tree, step = load_checkpoint(args.ckpt, template=tmpl)
            state = jax.device_put(tree) if tree is not None else None
        if state is not None:
            print(f"resumed from step {step}")
    if state is None:
        state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh, rules)

    loop = TrainLoop(model, pipe, opt, algorithm=args.algorithm,
                     mesh=mesh, rules=rules, checkpoint_dir=args.ckpt)
    state, records = loop.run(state, args.steps)
    for r in records:
        print(f"phase {r.phase_idx:3d} npass={r.npass} steps={r.steps} "
              f"loss={r.mean_loss:.4f} {r.elapsed:.2f}s")
    print(f"final loss {records[-1].mean_loss:.4f} over {len(records)} phases "
          f"({sum(r.npass for r in records)} steps)")


if __name__ == "__main__":
    main()
