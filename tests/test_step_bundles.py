"""Step bundles lower and run on a 1-device mesh for smoke configs
(the production-mesh equivalents are covered by the 512-device dry-run)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SMOKES
from repro.launch.mesh import make_mesh, mesh_shape_dict
from repro.models.config import ShapeConfig, input_specs
from repro.models.model import build_model
from repro.parallel.sharding import make_rules
from repro.parallel.steps import (
    init_train_state,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)


@pytest.mark.parametrize("name", ["qwen2-moe-a2.7b", "gemma3-12b",
                                  "zamba2-2.7b", "whisper-small"])
def test_train_bundle_runs(name):
    cfg = SMOKES[name]
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)
    shape = ShapeConfig("t", 32, 2, "train")
    bundle = make_train_step(model, rules, mesh, shape)
    with mesh:
        fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
        state = init_train_state(model, jax.random.key(0))
        batch = {
            "tokens": jnp.ones((2, 32), jnp.int32),
            "labels": jnp.ones((2, 32), jnp.int32),
        }
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.zeros(
                (2, cfg.encoder_frames, cfg.d_model), jnp.bfloat16
            )
        state, metrics = fn(state, batch)
        assert float(metrics["loss"]) > 0
        assert int(metrics["step"]) == 1
        state, metrics = fn(state, batch)
        assert int(metrics["step"]) == 2


@pytest.mark.parametrize("name", ["qwen2.5-3b", "xlstm-350m"])
def test_prefill_decode_bundles_run(name):
    cfg = SMOKES[name]
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)
    shape_p = ShapeConfig("p", 32, 2, "prefill")
    shape_d = ShapeConfig("d", 32, 2, "decode")
    pre = make_prefill_step(model, rules, mesh, shape_p)
    dec = make_decode_step(model, rules, mesh, shape_d)
    with mesh:
        params = model.init(jax.random.key(0))
        pfn = jax.jit(pre.fn, in_shardings=pre.in_shardings,
                      out_shardings=pre.out_shardings)
        logits, cache = pfn(params, {"tokens": jnp.ones((2, 32), jnp.int32)})
        dfn = jax.jit(dec.fn, in_shardings=dec.in_shardings,
                      out_shardings=dec.out_shardings,
                      donate_argnums=dec.donate_argnums)
        logits2, cache2 = dfn(params, cache, jnp.ones((2, 1), jnp.int32))
        assert logits2.shape == (2, 1, cfg.padded_vocab())


def test_microbatched_train_step_matches_full_batch():
    cfg = SMOKES["gemma-2b"]
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False)
    shape = ShapeConfig("t", 32, 8, "train")
    batch = {
        "tokens": jax.random.randint(jax.random.key(0), (8, 32), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                     cfg.vocab_size),
    }
    losses = {}
    with mesh:
        for mb in (1, 4):
            b = make_train_step(model, rules, mesh, shape, microbatches=mb)
            fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                         out_shardings=b.out_shardings)
            state = init_train_state(model, jax.random.key(0))
            state, metrics = fn(state, batch)
            losses[mb] = float(metrics["loss"])
    assert losses[1] == pytest.approx(losses[4], rel=1e-2)
