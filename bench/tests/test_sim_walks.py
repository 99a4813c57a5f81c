"""``sim_walks.search`` read from the CPU trace of the search loop that
``test_program_trace.py`` records, against a count of the simulator's node
scans in the rollout program itself."""
import numpy as np
import pytest

from bench import common, trace
from bench.tests.helpers import TINY_GRAPH
from bench.tests.test_program_trace import B, T, WINDOWS, Recorded

NAME = "sim_walks.search"


def jaxpr_walks(recorded) -> int:
    """Walks of the simulator per carried window, from the rollout
    program's jaxpr: each scan in the ``repro.sim`` scope, times the trip
    counts of the scans around it."""
    import jax
    import jax.numpy as jnp
    from repro.core import paper_platform
    from repro.core.sim import RewardPipeline
    from repro.graphs import PAPER_BENCHMARKS

    graph = PAPER_BENCHMARKS[TINY_GRAPH["builder"]](**TINY_GRAPH["args"])
    engine = recorded.agent._engine_single(
        recorded.arrays, RewardPipeline.from_platform(graph, paper_platform(),
                                                      "scan"))
    params, z, _ = recorded.windows[0]
    rngs = jnp.zeros((1, B, 2), jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda p, z_, r: engine.rollout_window(
        p, z_, r, num_steps=T, start_first=False))(params, z, rngs)

    def walks(jx, trips):
        total = 0
        for eqn in jx.eqns:
            inner = trips
            if eqn.primitive.name == "scan":
                if "repro.sim" in str(eqn.source_info.name_stack):
                    total += trips
                    continue
                inner = trips * eqn.params["length"]
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        total += walks(sub, inner)
        return total

    return walks(jaxpr.jaxpr, 1)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    cache = tmp_path_factory.mktemp("cache")
    rec = Recorded(str(cache / "trace" / "cpu.tiny.1"))
    rec.cache = str(cache)
    return rec


@pytest.fixture
def ctx(recorded, monkeypatch):
    monkeypatch.setattr(common, "CACHE", recorded.cache)
    return {"windows": WINDOWS}


def test_sim_walks_reads_the_cpu_trace(ctx):
    value = common.metric_reader(NAME)(ctx)
    assert value is not None and np.isfinite(value)


def test_sim_walks_equal_an_independent_count(recorded, ctx):
    got = common.metric_reader(NAME)(ctx)
    assert got == jaxpr_walks(recorded) >= 1


def test_sim_walks_reads_nothing_where_the_program_names_nothing(
        tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    tracer = trace.Tracer(str(tmp_path / "trace" / "plain.1"))
    tracer.start()
    f(x).block_until_ready()
    tracer.stop(windows=1)
    monkeypatch.setattr(common, "CACHE", str(tmp_path))
    assert common.metric_reader(NAME)({"windows": 1}) is None
