"""Training driver: ``--arch <id>`` end-to-end LM training.

On CPU this runs reduced configs (``--reduced``, default) — the same code
path pjit-compiles for the production mesh on TPU (``--mesh prod``).

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.model_factory import materialize_batch
from repro.training import (AdamW, SyntheticLMDataset, cosine_schedule,
                            make_train_step, save_checkpoint)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=args.layers, d_model=args.d_model)
    model = build_model(cfg)

    key = jax.random.key(args.seed)
    params = model.init(key)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"({cfg.arch_type}, {cfg.num_layers}L d={cfg.d_model})")

    opt = AdamW(learning_rate=cosine_schedule(args.lr, args.steps // 10, args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt, microbatches=args.microbatches))

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    it = iter(ds)
    extras_key = jax.random.key(args.seed + 1)

    losses = []
    t0 = time.monotonic()
    for step in range(args.steps):
        batch = dict(next(it))
        # modality stubs (VLM patches / audio frames) ride along
        mat = materialize_batch(cfg, args.batch, args.seq, "train", extras_key)
        for k, v in mat.items():
            if k != "tokens":
                batch[k] = v
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.monotonic() - t0
            tok_s = (step + 1) * args.batch * args.seq / dt
            print(f"step {step:5d} loss {loss:.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} tok/s {tok_s:.0f}")
        assert np.isfinite(loss), f"loss diverged at step {step}"

    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, opt_state, args.steps,
                        {"arch": cfg.name})
        print(f"checkpoint -> {args.checkpoint}")
    result = {"first_loss": losses[0], "last_loss": losses[-1],
              "min_loss": min(losses)}
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(improved {losses[0]-losses[-1]:.4f})")
    return result


if __name__ == "__main__":
    main()
