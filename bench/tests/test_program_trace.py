"""The program's scopes and spans read from a profiler trace recorded on the
CPU over two windows of the program's search loop on a small graph."""
import os
import sys

import numpy as np
import pytest

from bench import common, program_trace, trace
from bench.tests.helpers import REPO, TINY_GRAPH

sys.path.insert(0, os.path.join(REPO, "src"))

B, T, WINDOWS = 2, 4, 2
METRICS = ("encoder_ms.search", "gpn_ms.search", "sim_ms.search",
           "gpn_rounds.search", "update_idle_ms.search",
           "dispatch_idle_ms.search")


class Recorded:
    """Two windows of rollout, replay and Adam step, traced as the benchmark
    traces its windows, with what each rollout step started from."""

    def __init__(self, directory):
        import jax
        import jax.numpy as jnp
        from repro.core import HSDAG, HSDAGConfig, paper_platform
        from repro.core.features import extract_features
        from repro.core.sim import RewardPipeline
        from repro.graphs import PAPER_BENCHMARKS

        graph = PAPER_BENCHMARKS[TINY_GRAPH["builder"]](**TINY_GRAPH["args"])
        self.arrays = extract_features(graph)
        self.agent = HSDAG(HSDAGConfig(batch_chains=B, update_timestep=T))
        self.agent.init(jax.random.PRNGKey(0), self.arrays)
        engine = self.agent._engine_single(
            self.arrays, RewardPipeline.from_platform(graph, paper_platform(),
                                                      "scan"))
        x0 = jnp.asarray(self.arrays.x)
        z = jnp.broadcast_to(x0, (1, B) + x0.shape)
        rngs = jax.random.split(jax.random.PRNGKey(1), B)[None]
        self.windows = []
        tracer = trace.Tracer(directory)
        for w in range(WINDOWS + 1):
            if w == 1:                  # the first window compiles
                tracer.start()
            params = self.agent.params
            out = engine.rollout_window(params, z, rngs, num_steps=T,
                                        start_first=w == 0)
            weights = jnp.linspace(-1.0, 1.0, T * B).reshape(T, 1, B)
            grads = engine.window_grads(params, z, out[2], weights,
                                        num_steps=T, start_first=w == 0)
            self.agent.apply_grads(grads)
            if w:
                self.windows.append((params, z, out[2]))
            z, rngs = out[0], out[1]
        jax.block_until_ready(self.agent.params)
        tracer.stop(windows=WINDOWS)

    def retained(self):
        """(steps, B, E) retained edges of the traced rollout steps, from
        the program's own step vmapped over the chains as the rollout
        does."""
        import jax
        import jax.numpy as jnp
        a = self.arrays
        x0, adj, edges = (jnp.asarray(a.x), jnp.asarray(a.adj),
                          jnp.asarray(a.edges))

        @jax.jit
        def step(params, z, keys):
            def one(z1, k1):
                out = self.agent._step(params, z1, x0, adj, edges, k1,
                                       first=False, train=True)
                return out.z_next, out.parse.retained
            return jax.vmap(one)(z, keys)

        out = []
        for params, z, keys in self.windows:
            z = z[0]
            for t in range(T):
                z, kept = step(params, z, keys[t, 0])
                out.append(np.asarray(kept))
        return np.stack(out)


def numpy_passes(edges, kept, num_nodes) -> int:
    """Jacobi min-label passes over the kept edges until a pass changes
    nothing, that pass included."""
    src, dst = edges[kept, 0], edges[kept, 1]
    labels, passes = np.arange(num_nodes), 0
    while True:
        new = labels.copy()
        np.minimum.at(new, dst, labels[src])
        np.minimum.at(new, src, labels[dst])
        passes += 1
        if np.array_equal(new, labels):
            return passes
        labels = new


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


@pytest.fixture
def pt(ctx):
    return program_trace.load(ctx)


def test_reader_finds_the_one_run_directory(pt, ctx, tmp_path, monkeypatch):
    assert pt is not None and program_trace.load(ctx) is pt
    monkeypatch.setattr(common, "CACHE", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "a")
    os.makedirs(tmp_path / "trace" / "b")
    assert program_trace.load({"windows": 1}) is None


def test_scoped_layers_cover_the_window_programs(pt):
    leaf = pt.leaf()
    scoped = np.zeros_like(leaf)
    for scope in ("repro.encoder", "repro.gpn", "repro.sim", "repro.head"):
        scoped |= pt.leaf(scope=scope)
    total = pt.busy_ns(leaf)
    covered, rest = pt.busy_ns(scoped), pt.busy_ns(leaf & ~scoped)
    assert total > 0
    # The two parts make up the whole; on the CPU a few operations overlap.
    assert covered <= total and rest <= total <= covered + rest
    # What is left is the loop's own plumbing: scan bookkeeping, key
    # splits, the state update and the loss, and XLA's parameter copies.
    assert covered / total > 0.6
    unscoped = {pt.table[k]["op_name"] for k in np.unique(pt.kind[leaf &
                                                                  ~scoped])}
    assert not any("repro." in n for n in unscoped)


def test_gpn_rounds_equal_an_independent_count(recorded, ctx):
    got = common.metric_reader("gpn_rounds.search")(ctx)
    kept = recorded.retained()
    edges = np.asarray(recorded.arrays.edges)
    n = recorded.arrays.x.shape[0]
    # Under vmap the loop runs until the slowest chain is done.
    per_step = [max(numpy_passes(edges, kept[s, b], n) for b in range(B))
                for s in range(len(kept))]
    assert len(per_step) == WINDOWS * T
    assert got == np.mean(per_step)
    assert got > 1


def test_idle_parts_add_up_to_the_idle_time(pt, ctx):
    update = common.metric_reader("update_idle_ms.search")(ctx)
    dispatch = common.metric_reader("dispatch_idle_ms.search")(ctx)
    t0, t1 = pt.window
    ops = np.stack([pt.start, pt.end], axis=1).clip(t0, t1)
    idle = (t1 - t0) - trace.covered(ops)
    spans = trace.merge(np.concatenate(list(pt.spans.values())))
    # Idle time outside every repro.* span, gap by gap.
    outside = 0.0
    for g0, g1 in pt.idle():
        inside = sum(max(0.0, min(g1, s1) - max(g0, s0)) for s0, s1 in spans)
        outside += (g1 - g0) - inside
    assert update > 0 and dispatch >= 0
    parts = (update + dispatch) * 1e6 * WINDOWS + outside
    assert parts == pytest.approx(idle, rel=1e-9)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_the_cpu_trace(ctx, name):
    value = common.metric_reader(name)(ctx)
    assert value is not None and np.isfinite(value)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_nothing_where_the_program_names_nothing(
        name, tmp_path, monkeypatch):
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
    assert common.metric_reader(name)({"windows": 1}) is None
