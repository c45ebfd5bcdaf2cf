"""Serving driver: batched prefill + decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --batch 4 --prompt-len 64 --gen 32

Each call builds its model and lowers and compiles its three programs
(weight init, prefill, decode) ahead of time, then runs them.  Every stage
is a ``repro.serve.*`` span (``repro.core.spans``) carrying the mesh's
chips, so a profiler trace names what the host did while the chips idled.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get, get_smoke
from repro.core.spans import chip_ids, span
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, mesh_shape_dict
from repro.models.config import ShapeConfig
from repro.models.model import build_model
from repro.parallel.sharding import make_rules
from repro.parallel.steps import make_decode_step, make_prefill_step


def _compile(program: str, fn, chips: str, *args):
    """``fn`` traced and lowered for ``args``, then compiled (or loaded
    from the persistent cache), each stage under its own span."""
    with span("repro.serve.lower", chips=chips, program=program):
        lowered = fn.lower(*args)
    with span("repro.serve.compile", chips=chips, program=program):
        return lowered.compile()


def serve(
    arch: str,
    batch: int = 4,
    prompt_len: int = 64,
    gen: int = 16,
    smoke: bool = True,
    mesh=None,
    temperature: float = 0.0,
    seed: int = 0,
    log_fn=print,
) -> dict:
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n, 1), ("data", "model"))
    chips = chip_ids(mesh.devices.flat)
    with span("repro.serve", chips=chips, batch=batch, prompt=prompt_len,
              gen=gen), mesh:
        with span("repro.serve.build", chips=chips):
            cfg = get_smoke(arch) if smoke else get(arch)
            model = build_model(cfg)
            rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)
            pre = make_prefill_step(
                model, rules, mesh,
                ShapeConfig("serve", prompt_len, batch, "prefill"))
            dec = make_decode_step(
                model, rules, mesh,
                ShapeConfig("serve", prompt_len, batch, "decode"))
            rng = np.random.default_rng(seed)
            prompts = rng.integers(
                0, cfg.vocab_size, size=(batch, prompt_len)
            ).astype(np.int32)
            batch_in = {"tokens": jnp.asarray(prompts)}
            if cfg.is_encoder_decoder:
                batch_in["frames"] = jnp.zeros(
                    (batch, cfg.encoder_frames, cfg.d_model), jnp.bfloat16
                )
            init_key = jax.random.key(0)
            # weights are made in place on the mesh: an eager init would
            # put every job's full copy on the first device first
            init_fn = jax.jit(model.init, out_shardings=pre.in_shardings[0])
            prefill_fn = jax.jit(pre.fn, in_shardings=pre.in_shardings,
                                 out_shardings=pre.out_shardings)
            decode_fn = jax.jit(dec.fn, in_shardings=dec.in_shardings,
                                out_shardings=dec.out_shardings,
                                donate_argnums=dec.donate_argnums)

        init = _compile("init", init_fn, chips, init_key)
        prefill = _compile("prefill", prefill_fn, chips, init.out_info,
                           batch_in)
        decode = _compile("decode", decode_fn, chips, init.out_info,
                          prefill.out_info[1],
                          jax.ShapeDtypeStruct((batch, 1), jnp.int32))

        with span("repro.serve.init", chips=chips):
            params = jax.block_until_ready(init(init_key))
        with span("repro.serve.prefill", chips=chips):
            t0 = time.perf_counter()
            logits, cache = jax.block_until_ready(prefill(params, batch_in))
            prefill_s = time.perf_counter() - t0

        key = jax.random.key(seed)

        def sample(lg, key):
            if temperature <= 0:
                return jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, lg[:, -1].astype(jnp.float32) / temperature, axis=-1
            ).astype(jnp.int32)

        key, sub = jax.random.split(key)
        token = sample(logits, sub)[:, None]
        generated = [np.asarray(token)]
        t1 = time.perf_counter()
        for step in range(gen - 1):
            with span("repro.serve.decode_step", chips=chips, step=step):
                logits, cache = decode(params, cache, token)
                key, sub = jax.random.split(key)
                token = sample(logits, sub)[:, None]
                generated.append(np.asarray(token))
        decode_s = time.perf_counter() - t1
    tokens = np.concatenate(generated, axis=1)
    log_fn(f"[serve] prefill {prompt_len}tok×{batch} in {prefill_s*1e3:.0f}ms; "
           f"decode {gen-1} steps in {decode_s*1e3:.0f}ms")
    return {
        "tokens": tokens,
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "device_ids": sorted(d.id for d in logits.sharding.device_set),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
          gen=args.gen, smoke=not args.full)


if __name__ == "__main__":
    main()
