"""Training driver.

Runs real steps on whatever devices exist (CPU smoke configs here; the
same code path drives a pod — the mesh shape is the only difference).
Supports checkpoint/restart (``--ckpt-dir``): on start it resumes from the
latest complete checkpoint, and the deterministic data pipeline replays
from the restored step, so a killed run continues bit-exact.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import ckpt as ckpt_lib
from repro.configs import get, get_smoke
from repro.launch.compile_cache import use_compile_cache
from repro.data import SyntheticTokens
from repro.launch.mesh import make_mesh, mesh_shape_dict
from repro.models.config import ShapeConfig, input_specs
from repro.models.model import build_model
from repro.optim import wsd_schedule
from repro.parallel.sharding import make_rules
from repro.parallel.steps import init_train_state, make_train_step


def train(
    arch: str,
    steps: int = 20,
    batch: int = 8,
    seq: int = 128,
    smoke: bool = True,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    lr: float = 3e-4,
    mesh=None,
    log_every: int = 10,
    data_seed: int = 0,
    compress_grads: bool = False,
    total_steps: int | None = None,
    microbatches: int = 1,
    log_fn=print,
) -> dict:
    cfg = get_smoke(arch) if smoke else get(arch)
    model = build_model(cfg)
    shape = ShapeConfig("custom", seq, batch, "train")
    total_steps = total_steps or steps  # LR schedule horizon (for restarts)

    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n, 1), ("data", "model"))
    rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)

    bundle = make_train_step(
        model, rules, mesh, shape,
        lr_schedule=wsd_schedule(lr, warmup=min(20, total_steps // 10 + 1),
                                 total=total_steps),
        compress_grads=compress_grads,
        microbatches=microbatches,
    )
    with mesh:
        step_fn = jax.jit(
            bundle.fn,
            in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        )

        start_step = 0
        state = None
        if ckpt_dir is not None:
            latest = ckpt_lib.latest_step(ckpt_dir)
            if latest is not None:
                like = jax.eval_shape(
                    lambda: init_train_state(model, jax.random.key(0))
                )
                state, meta = ckpt_lib.restore_checkpoint(ckpt_dir, like)
                if "ef" in dict(bundle.in_shardings[0]) and "ef" not in state:
                    pass
                start_step = meta["step"]
                log_fn(f"[train] resumed from step {start_step}")
        if state is None:
            state = init_train_state(model, jax.random.key(0))
            if compress_grads:
                from repro.parallel.compression import ef_init
                state["ef"] = ef_init(state["params"])

        source = SyntheticTokens(cfg.padded_vocab(), seq, batch,
                                 seed=data_seed)
        losses = []
        t0 = time.time()
        for i in range(start_step, steps):
            np_batch = source.batch(i)
            jb = {k: jnp.asarray(v) for k, v in np_batch.items()}
            if cfg.is_encoder_decoder:
                jb["frames"] = jnp.zeros(
                    (batch, cfg.encoder_frames, cfg.d_model), jnp.bfloat16
                )
            state, metrics = step_fn(state, jb)
            loss = float(metrics["loss"])
            losses.append(loss)
            if (i + 1) % log_every == 0 or i == steps - 1:
                log_fn(f"[train] step {i+1:5d} loss={loss:.4f} "
                       f"gnorm={float(metrics['grad_norm']):.3f} "
                       f"({(time.time()-t0)/max(i+1-start_step,1)*1e3:.0f} ms/step)")
            if ckpt_dir is not None and (i + 1) % ckpt_every == 0:
                ckpt_lib.save_checkpoint(ckpt_dir, i + 1, state)
        if ckpt_dir is not None:
            ckpt_lib.save_checkpoint(ckpt_dir, steps, state)
    return {
        "losses": losses,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "steps": steps,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (pod-scale!) not the smoke")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()
    out = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        smoke=not args.full, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, lr=args.lr,
        compress_grads=args.compress_grads, microbatches=args.microbatches,
    )
    print(f"[train] done: loss {out['first_loss']:.3f} -> "
          f"{out['last_loss']:.3f} over {out['steps']} steps")


if __name__ == "__main__":
    main()
