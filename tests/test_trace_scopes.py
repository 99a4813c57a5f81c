"""The search window's named device scopes and host spans.

The window programs carry ``repro.*`` scopes in their compiled ``op_name``
metadata, the host steps write ``repro.*`` spans into a profiler trace, and
neither changes what a window computes.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_diamond
from repro.core import HSDAG, HSDAGConfig, paper_platform
from repro.core.features import extract_features
from repro.core.sim import RewardPipeline

B, T = 2, 3
SCOPES = ("repro.encoder", "repro.gpn", "repro.gpn_components", "repro.head",
          "repro.sim")
SPANS = ("repro.rollout", "repro.replay", "repro.optim.update")


def _token(scope):
    return re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")


class Window:
    """One agent and engine over the diamond graph, and a window's inputs
    after one warm-up window (so the traced one is the carried variant)."""

    def __init__(self):
        graph = make_diamond()
        arrays = extract_features(graph)
        self.agent = HSDAG(HSDAGConfig(batch_chains=B, update_timestep=T))
        self.agent.init(jax.random.PRNGKey(0), arrays)
        pipeline = RewardPipeline.from_platform(graph, paper_platform(),
                                                "scan")
        self.engine = self.agent._engine_single(arrays, pipeline)
        x0 = jnp.asarray(arrays.x)
        z = jnp.broadcast_to(x0, (1, B) + x0.shape)
        rngs = jax.random.split(jax.random.PRNGKey(1), B)[None]
        out = self.run(z, rngs, first=True)
        self.z, self.rngs = out["z"], out["rngs"]
        self.params = self.agent.params
        self.opt_state = self.agent._opt_state

    def run(self, z, rngs, *, first):
        """One window: rollout, replay gradient, optimizer step."""
        (z_next, rngs_next, keys, fines, _, _, latencies) = \
            self.engine.rollout_window(self.agent.params, z, rngs,
                                       num_steps=T, start_first=first)
        weights = jnp.linspace(-1.0, 1.0, T * B).reshape(T, 1, B)
        grads = self.engine.window_grads(self.agent.params, z, keys, weights,
                                         num_steps=T, start_first=first)
        self.agent.apply_grads(grads)
        jax.block_until_ready(self.agent.params)
        return dict(z=z_next, rngs=rngs_next, keys=keys, weights=weights,
                    fines=np.asarray(fines),
                    latencies=np.asarray(latencies),
                    grads=jax.tree.map(np.asarray, grads),
                    params=jax.tree.map(np.asarray, self.agent.params))

    def again(self):
        """The same carried window from the same parameters."""
        self.agent.params, self.agent._opt_state = self.params, self.opt_state
        return self.run(self.z, self.rngs, first=False)


@pytest.fixture(scope="module")
def window():
    return Window()


@pytest.fixture(scope="module")
def traced(window, tmp_path_factory):
    """(result without the profiler, result under it, trace file)."""
    plain = window.again()
    directory = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(directory)
    try:
        profiled = window.again()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0]
    return plain, profiled, path


@pytest.fixture(scope="module")
def compiled(window):
    w = window
    rollout = w.engine._window[0].lower(
        w.params, w.z, w.rngs, num_steps=T, start_first=False).compile()
    keys = jnp.zeros((T, 1, B, 2), jnp.uint32)
    weights = jnp.ones((T, 1, B))
    replay = w.engine._window[1].lower(
        w.params, w.z, keys, weights, num_steps=T,
        start_first=False).compile()
    return {"rollout": rollout.as_text(), "replay": replay.as_text()}


# The replay re-runs the policy with the rewards as constants, so it has no
# simulator in it.
@pytest.mark.parametrize("program,scope", [
    ("rollout", s) for s in SCOPES] + [
    ("replay", s) for s in SCOPES if s != "repro.sim"])
def test_compiled_window_programs_carry_scope(compiled, program, scope):
    op_names = re.findall(r'op_name="([^"]*)"', compiled[program])
    assert any(_token(scope).search(n) for n in op_names), (
        f"{scope} is in no op_name of the {program} program")


def test_components_scope_is_read_apart_from_the_gpn_scope(compiled):
    names = re.findall(r'op_name="([^"]*)"', compiled["rollout"])
    gpn_only = [n for n in names if _token("repro.gpn").search(n)
                and not _token("repro.gpn_components").search(n)]
    inner = [n for n in names if _token("repro.gpn_components").search(n)]
    assert gpn_only and inner
    assert all(_token("repro.gpn").search(n) for n in inner)


@pytest.mark.parametrize("span", SPANS)
def test_profiler_trace_holds_host_span(traced, span):
    from jax.profiler import ProfileData
    _, _, path = traced
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert span in names


@pytest.mark.parametrize("field", ["fines", "latencies", "grads", "params"])
def test_profiled_window_is_bitwise_the_plain_one(traced, field):
    plain, profiled, _ = traced
    a = jax.tree.leaves(plain[field])
    b = jax.tree.leaves(profiled[field])
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
