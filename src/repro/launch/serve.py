"""Serving driver: batched prefill + decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --batch 4 --prompt-len 64 --gen 32

A call serves one batch with three programs compiled ahead of time:
weight init, prefill and decode.  The programs are kept for the life of
the process, keyed on what they depend on: init on the model's
configuration and the mesh (its devices in order, shape, axis names and
axis types), prefill and decode on ``(batch, prompt_len)`` too.  A call
builds, lowers and compiles (or loads from the persistent cache) only the
programs not kept yet; every call still makes its weights, prefills and
decodes on the chips.  Every stage is a ``repro.serve.*`` span
(``repro.core.spans``) carrying the mesh's chips, and the ``repro.serve``
span names the kept programs it used (``cached="init prefill decode"``;
empty when it built all three, which reads back as no stat), so a
profiler trace names what the host did while the chips idled.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import threading
import time
from typing import Any

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


@dataclasses.dataclass
class _Slot:
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    program: Any = None


class _Programs:
    """Compiled programs by key, each built once per process.  A build
    holds only its own key's lock: builds for other meshes or shapes go on
    beside it, and a second caller of the same key waits for the first
    one's program."""

    def __init__(self):
        self._lock = threading.Lock()
        self._slots: dict = {}

    def get(self, key, build):
        """``(program, kept)``: the kept program for ``key``, else the
        one ``build()`` returns, kept from then on."""
        with self._lock:
            slot = self._slots.setdefault(key, _Slot())
        with slot.lock:
            if slot.program is not None:
                return slot.program, True
            slot.program = build()
            return slot.program, False

    def clear(self) -> None:
        with self._lock:
            self._slots.clear()


_PROGRAMS = _Programs()


def clear_programs() -> None:
    """Forget every kept program: the next call builds all it needs."""
    _PROGRAMS.clear()


def _mesh_key(mesh) -> tuple:
    """What a program depends on of ``mesh``: a new ``Mesh`` over the same
    devices, in the same layout, has the same key."""
    return (tuple(d.id for d in mesh.devices.flat), mesh.devices.shape,
            mesh.axis_names, mesh.axis_types)


def _jitted(cfg, mesh, batch: int, prompt_len: int, chips: str) -> dict:
    """The model's three programs for one shape on ``mesh``, jitted with
    their shardings, by name."""
    with span("repro.serve.build", chips=chips):
        model = build_model(cfg)
        rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)
        pre = make_prefill_step(
            model, rules, mesh,
            ShapeConfig("serve", prompt_len, batch, "prefill"))
        dec = make_decode_step(
            model, rules, mesh,
            ShapeConfig("serve", prompt_len, batch, "decode"))
        return {
            # weights are made in place on the mesh: an eager init would
            # put every job's full copy on the first device first
            "init": jax.jit(model.init, out_shardings=pre.in_shardings[0]),
            "prefill": jax.jit(pre.fn, in_shardings=pre.in_shardings,
                               out_shardings=pre.out_shardings),
            "decode": jax.jit(dec.fn, in_shardings=dec.in_shardings,
                              out_shardings=dec.out_shardings,
                              donate_argnums=dec.donate_argnums),
        }


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
    cfg = get_smoke(arch) if smoke else get(arch)
    on_mesh = (cfg, _mesh_key(mesh))
    with span("repro.serve", chips=chips, batch=batch, prompt=prompt_len,
              gen=gen) as job, mesh:
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
        jitted = functools.cache(
            functools.partial(_jitted, cfg, mesh, batch, prompt_len, chips))
        kept = []

        def program(name: str, key: tuple, *args):
            out, hit = _PROGRAMS.get(
                (name, *key),
                lambda: _compile(name, jitted()[name], chips, *args))
            if hit:
                kept.append(name)
            return out

        init = program("init", on_mesh, init_key)
        prefill = program("prefill", (*on_mesh, batch, prompt_len),
                          init.out_info, batch_in)
        decode = program("decode", (*on_mesh, batch, prompt_len),
                         init.out_info, prefill.out_info[1],
                         jax.ShapeDtypeStruct((batch, 1), jnp.int32))
        job.set_metadata(cached=" ".join(kept))

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
