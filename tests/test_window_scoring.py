"""A fused rollout window scores all of its placements in one simulator walk
after the step scan.

The rewards and latencies it returns are, bit for bit, what the backend's
host entry ``simulate_batch`` gives for the same placements, and the
population window's chain-best records are those folded from the
host-scored latencies.  In the window's jaxpr the simulator's node scan
appears once, outside the body of the step scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HSDAG, HSDAGConfig, extract_features, paper_platform
from repro.core.features import batch_graph_arrays, shared_feature_config
from repro.core.costmodel import sim_arrays_batch
from repro.core.sim import DynamicRolloutEngine, RewardPipeline, get_backend
from repro.core.train.curriculum import _operands
from repro.core.train.loop import make_chain_rngs
from repro.core.train.population import PopulationConfig
from repro.graphs import build_corpus

PLAT = paper_platform()
B = 3
SCAN = get_backend("scan")


def _agent(graphs, T):
    fc = shared_feature_config(graphs)
    arrays = [extract_features(g, fc) for g in graphs]
    agent = HSDAG(HSDAGConfig(num_devices=2, hidden_channel=16,
                              batch_chains=B, update_timestep=T))
    agent.init(jax.random.PRNGKey(0), arrays[0])
    return agent, arrays


def _single(T, start_first):
    """RolloutEngine (the search path) over one graph, G=1."""
    graph = list(build_corpus(
        "synthetic:family=mixed:count=1:size=12:seed=0"))[0]
    agent, (arrays,) = _agent([graph], T)
    engine = agent._engine_single(
        arrays, RewardPipeline.from_platform(graph, PLAT, "scan"))
    x0 = jnp.asarray(arrays.x)
    z = jnp.broadcast_to(x0, (1, B) + x0.shape)
    rngs = make_chain_rngs(jax.random.PRNGKey(1), 1, B)
    out = engine.rollout_window(agent.params, z, rngs, num_steps=T,
                                start_first=True)
    if not start_first:
        out = engine.rollout_window(agent.params, out[0], out[1],
                                    num_steps=T, start_first=False)
    return [graph], out[3], out[5], out[6], None


def _padded_ops(T, population=None):
    graphs = list(build_corpus(
        "synthetic:family=mixed:count=3:size=12:seed=1"))[:2]
    assert graphs[0].num_nodes != graphs[1].num_nodes
    agent, arrays = _agent(graphs, T)
    v_max = max(g.num_nodes for g in graphs)
    e_max = max(a.edges.shape[0] for a in arrays)
    ga = batch_graph_arrays(arrays, v_max=v_max, e_max=e_max)
    sb = sim_arrays_batch(graphs, PLAT, v_max=v_max)
    ops = _operands(ga, jax.tree.map(jnp.asarray, sb.arrays))
    engine = DynamicRolloutEngine(agent._step, agent.cfg, backend=SCAN,
                                  population=population)
    z = jnp.broadcast_to(ops.x0[:, None], (2, B) + ops.x0.shape[1:])
    rngs = make_chain_rngs(jax.random.PRNGKey(1), 2, B)
    return graphs, agent, engine, ops, z, rngs, v_max


def _dynamic(T):
    """DynamicRolloutEngine on a padded batch of two graphs."""
    graphs, agent, engine, ops, z, rngs, _ = _padded_ops(T)
    out = engine.rollout_window(ops, agent.params, z, rngs, num_steps=T,
                                start_first=True)
    return graphs, out[3], out[5], out[6], None


def _population(T):
    """The population window, whose chain bests fold in-jit."""
    graphs, agent, engine, ops, z, rngs, v_max = _padded_ops(
        T, PopulationConfig(cull_every=2, greedy_restart_every=2))
    pop = engine.init_population(jax.random.PRNGKey(7), num_graphs=2,
                                 num_chains=B, num_nodes=v_max)
    # One window first, so the records folded next are not the initial ones.
    out = engine.rollout_window_pop(ops, agent.params, z, rngs, pop,
                                    num_steps=T, start_first=True)
    pop_in = out[2]
    out = engine.rollout_window_pop(ops, agent.params, out[0], out[1],
                                    pop_in, num_steps=T, start_first=False)
    return (graphs, out[4], out[6], out[7],
            lambda latencies: (pop_in, out[2],
                               engine.update_population(pop_in, out[4],
                                                        latencies)))


CASES = {
    "rollout-first-T1": lambda: _single(1, True),
    "rollout-carried-T1": lambda: _single(1, False),
    "rollout-first-T4": lambda: _single(4, True),
    "rollout-carried-T4": lambda: _single(4, False),
    "dynamic-padded-2graphs": lambda: _dynamic(3),
    "population-padded-2graphs": lambda: _population(3),
}


@pytest.mark.parametrize("case", list(CASES))
def test_window_scores_equal_simulate_batch_bitwise(case):
    graphs, fine, rewards, latencies, bests = CASES[case]()
    fine, rewards, latencies = map(np.asarray, (fine, rewards, latencies))
    T, G = fine.shape[:2]
    assert rewards.shape == latencies.shape == (T, G, B)
    assert rewards.dtype == latencies.dtype == np.float32
    host_lat = np.empty_like(latencies)
    for g, graph in enumerate(graphs):
        n = graph.num_nodes
        res = SCAN.simulate_batch(SCAN.prepare(graph, PLAT),
                                  fine[:, g, :, :n].reshape(T * B, n))
        np.testing.assert_array_equal(rewards[:, g],
                                      np.reshape(res.reward, (T, B)))
        np.testing.assert_array_equal(latencies[:, g],
                                      np.reshape(res.latency, (T, B)))
        host_lat[:, g] = np.reshape(res.latency, (T, B))
    assert np.all(latencies > 0)
    if bests is not None:
        before, fused, host = bests(host_lat)
        for field in ("best_latency", "best_fine"):
            np.testing.assert_array_equal(np.asarray(getattr(fused, field)),
                                          np.asarray(getattr(host, field)))
        # The window did improve on some record, so the fold was exercised.
        assert np.any(np.asarray(fused.best_latency)
                      < np.asarray(before.best_latency))


def _scan_depths(jaxpr, length, depth=0):
    """Nesting depth (in enclosing scans and loops) of each ``scan`` of the
    given length in ``jaxpr`` and every jaxpr inside it."""
    found = []
    for eqn in jaxpr.eqns:
        loop = eqn.primitive.name in ("scan", "while")
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append(depth)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _scan_depths(inner, length, depth + loop)
    return found


@pytest.mark.parametrize("engine_kind", ["rollout", "dynamic"])
@pytest.mark.parametrize("start_first", [True, False])
def test_simulator_walks_once_outside_the_step_scan(engine_kind,
                                                    start_first):
    T = 4
    graph = list(build_corpus(
        "synthetic:family=mixed:count=1:size=12:seed=0"))[0]
    assert graph.num_nodes not in (T, T - 1)
    agent, (arrays,) = _agent([graph], T)
    x0 = jnp.asarray(arrays.x)
    if start_first:
        z = jnp.broadcast_to(x0, (1, B) + x0.shape)
    else:
        z = jnp.zeros((1, B, x0.shape[0], agent.cfg.hidden_channel))
    rngs = make_chain_rngs(jax.random.PRNGKey(1), 1, B)
    if engine_kind == "rollout":
        engine = agent._engine_single(
            arrays, RewardPipeline.from_platform(graph, PLAT, "scan"))
        jaxpr = jax.make_jaxpr(lambda p, z_, r: engine._window[0](
            p, z_, r, num_steps=T, start_first=start_first))(
                agent.params, z, rngs)
    else:
        ga = batch_graph_arrays([arrays])
        ops = _operands(ga, jax.tree.map(
            jnp.asarray, sim_arrays_batch([graph], PLAT).arrays))
        engine = DynamicRolloutEngine(agent._step, agent.cfg, backend=SCAN)
        jaxpr = jax.make_jaxpr(lambda o, p, z_, r: engine._built[0](
            o, p, z_, r, num_steps=T, start_first=start_first))(
                ops, agent.params, z, rngs)
    # The one scan over the graph's nodes is the simulator's, at top level.
    assert _scan_depths(jaxpr.jaxpr, graph.num_nodes) == [0]
    # And the step scan is there, so the walk is not inside it.
    assert _scan_depths(jaxpr.jaxpr, T - start_first) == [0]
