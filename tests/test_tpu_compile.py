"""Compile-only checks of the main path's device programs for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot —
block shapes Mosaic refuses, unsupported vector layouts, programs that do
not fit the chip's memory — without a chip.  Nothing runs; results are
checked by the interpret-mode parity tests.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def uncached():
    """A described chip's executables cannot be read back from the
    persistent cache, so keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _abstract(tree, sharding):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= V5E_HBM_BYTES, total
    return total


@pytest.mark.parametrize("batch", [256, 1024])
@pytest.mark.parametrize("name", ["inception_v3", "bert_base"])
def test_level_kernel_compiles_for_v5e(name, batch, one_chip, uncached):
    """The level scorer — kernel and its XLA wrapper — at paper sizes."""
    import jax
    import jax.numpy as jnp
    from repro.core import paper_platform
    from repro.core.sim import get_backend
    from repro.core.sim.level import _simulate_level
    from repro.graphs import PAPER_BENCHMARKS

    g = PAPER_BENCHMARKS[name]()
    prep = get_backend("level").prepare(g, paper_platform())
    placements = jax.ShapeDtypeStruct((batch, g.num_nodes), jnp.int32,
                                      sharding=one_chip)
    compiled = jax.jit(_simulate_level, static_argnames=("interpret",)).lower(
        _abstract(prep.arrays, one_chip), _abstract(prep.levels, one_chip),
        placements, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_scan_rollout_window_compiles_for_v5e(one_chip, uncached):
    """One fused ``scan``-engine rollout window of the search, Inception-v3,
    64 chains — the program every search episode starts with."""
    import jax
    import jax.numpy as jnp
    from repro.core import HSDAG, HSDAGConfig, paper_platform
    from repro.core.costmodel import sim_arrays
    from repro.core.features import (batch_graph_arrays, extract_features,
                                     shared_feature_config)
    from repro.core.sim import get_backend
    from repro.core.sim.rollout import GraphOperands, build_window_fns
    from repro.graphs import inception_v3

    g = inception_v3()
    chains, window = 64, 10
    cfg = HSDAGConfig(batch_chains=chains, update_timestep=window)
    arrays = extract_features(g, shared_feature_config([g]))
    agent = HSDAG(cfg)
    params = agent.init(jax.random.PRNGKey(0), arrays)
    gb = batch_graph_arrays([arrays])
    sim = jax.tree.map(lambda a: np.asarray(a)[None],
                       sim_arrays(g, paper_platform()))
    ops = GraphOperands(gb.x, gb.adj, gb.edges, gb.node_mask, gb.edge_mask,
                        sim=sim)
    rollout, _, _ = build_window_fns(agent._step, cfg, fused=True,
                                     backend=get_backend("scan"))
    z = jax.ShapeDtypeStruct((1, chains, g.num_nodes, cfg.hidden_channel),
                             jnp.float32, sharding=one_chip)
    rngs = jax.ShapeDtypeStruct((1, chains, 2), jnp.uint32,
                                sharding=one_chip)
    compiled = jax.jit(rollout, static_argnames=("num_steps", "start_first")
                       ).lower(_abstract(ops, one_chip),
                               _abstract(params, one_chip), z, rngs,
                               num_steps=window, start_first=False).compile()
    assert _fits(compiled) > 0
