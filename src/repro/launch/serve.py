"""CLI: serve a model with paper-policy multi-step decode fusion.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 8 --prompt-len 16 --max-new 64 --algorithm optimized_vfpc
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core.policy import ALGORITHMS
from repro.launch.cache import enable_compile_cache
from repro.launch.cliopts import add_policy_args, policy_kwargs_from_args
from repro.models import build_model
from repro.serving import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--algorithm", default="optimized_vfpc",
                    choices=sorted(ALGORITHMS))
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    add_policy_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    model = build_model(args.arch, smoke=args.smoke)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(1, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)

    eng = ServeEngine(model, params,
                      cache_len=args.prompt_len + args.max_new + 8,
                      algorithm=args.algorithm,
                      policy_kwargs=policy_kwargs_from_args(
                          args, args.algorithm),
                      latency_budget_ms=args.latency_budget_ms)
    toks, records = eng.generate(prompts, max_new_tokens=args.max_new,
                                 eos_id=args.eos_id)
    total_t = sum(r.elapsed for r in records)
    total_tok = sum(r.tokens_emitted for r in records)
    print(f"algorithm={args.algorithm} dispatches={len(records)} "
          f"tokens={total_tok} wasted={sum(r.wasted_tokens for r in records)} "
          f"decode_time={total_t:.3f}s ({total_tok/max(total_t,1e-9):.1f} tok/s)")
    for r in records:
        print(f"  phase {r.phase_idx:3d} npass={r.npass:2d} "
              f"active={r.active_before} {r.elapsed*1e3:.1f} ms")
    print("first row tokens:", toks[0][:24].tolist())


if __name__ == "__main__":
    main()
