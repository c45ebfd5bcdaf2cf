"""Compile the vectorized evaluator's device programs for one TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jax, compiles for
a described v5e chip and raises whatever the chip's compiler would raise
(unsupported types, layouts that do not tile, programs that do not fit).
The shapes are the real ones of a full-family batch of 2000 tasks on an
A100: a chunk of 512 candidates over 2048-long size rows.

The topology is described inside a fixture, never while a module is
imported, so every test worker collects the same tests and only the one
that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_spec import A100
from repro.core.family_eval import (
    _chains_program,
    _phase_a_program,
    _spec_eval_arrays,
)

C, L = 512, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_phase_a_program_compiles_for_v5e(one_chip):
    sa = _spec_eval_arrays(A100)
    with jax.enable_x64(True):
        run = _phase_a_program(sa, C, L)
        compiled = run.lower(
            _shape((C, sa.n_sizes, L), jnp.float64, one_chip),
            _shape((C, sa.n_sizes), jnp.int32, one_chip),
        ).compile()
    nid, chain_durs, chain_len = compiled.out_info
    assert nid.shape == (L + sa.n_nodes, C) and nid.dtype == np.int32
    assert chain_durs.shape == (C, sa.n_nodes, L)
    assert chain_durs.dtype == np.float64
    assert chain_len.shape == (C, sa.n_nodes)
    assert "tpu_custom_call" not in compiled.as_text()


def test_chain_walk_compiles_for_v5e(one_chip):
    N = len(A100.nodes)
    with jax.enable_x64(True):
        walk = _chains_program(A100, C, L)
        compiled = walk.lower(
            _shape((C, N, L), jnp.float64, one_chip),
            _shape((C, N), jnp.int32, one_chip),
        ).compile()
    out = compiled.out_info
    assert out.shape == (C,) and out.dtype == np.float64
    assert "tpu_custom_call" not in compiled.as_text()
