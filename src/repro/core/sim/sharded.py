"""ShardedRolloutEngine — the (G, B) window rollout over a 2-D device mesh.

:class:`~repro.core.sim.DynamicRolloutEngine` runs the whole (G, B) chain
grid on one device.  This engine ``shard_map``s the *same* raw window
functions (:func:`~repro.core.sim.rollout.build_window_fns`) over a
("graphs", "chains") mesh — graph slots tile one axis, REINFORCE chains the
other — turning the curriculum trainer into a fleet trainer:

* **rollout** is embarrassingly parallel per (g, b) chain: each shard runs
  the identical scan/vmap body on its tile, no collectives.
* **gradients** are computed per shard against the *global* chain-count
  denominator and ``psum``-reduced over both mesh axes in-mesh, so one
  optimizer step consumes exactly the unsharded mean gradient.
* **reward standardization** (the corpus trainer's per-graph reward norm)
  runs in-mesh too (:meth:`window_weights`): per-graph moments psum over
  the "chains" axis only — graphs never mix, matching the host math.

Parity contract (pinned by ``tests/test_sharded_rollout.py``): at mesh=1×1
every psum is an identity and the shard body is the dynamic engine's jaxpr,
so training is **bit-for-bit** equal to :class:`DynamicRolloutEngine`; at
any other factorization the only delta is the float32 in-mesh weights math
vs the host float64 path, bounded at ≤1e-5 on final parameters.

Sharding specs come from the logical-axis rule machinery in
``distributed/sharding.py`` (:data:`~repro.distributed.sharding
.ROLLOUT_RULES`), the same table-driven path the production mesh uses.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh

from ...distributed.sharding import ROLLOUT_RULES, AxisRules, logical_spec
from .rollout import GraphOperands, build_window_fns

__all__ = ["ShardedRolloutEngine", "make_rollout_mesh"]

_AXES = ("graphs", "chains")


def make_rollout_mesh(graph_shards: int, chain_shards: int) -> Mesh:
    """A ``graph_shards × chain_shards`` mesh named ("graphs", "chains").

    Uses the first ``graph_shards * chain_shards`` local devices; on a CPU
    host run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    these are virtual devices (the parity tests and ``table10_sharded.py``
    drive exactly that setup).
    """
    gs, bs = int(graph_shards), int(chain_shards)
    if gs < 1 or bs < 1:
        raise ValueError(f"mesh shape must be positive, got ({gs}, {bs})")
    need = gs * bs
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(
            f"mesh ({gs}, {bs}) needs {need} devices but only "
            f"{len(devs)} are visible — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before jax initializes")
    return Mesh(np.array(devs[:need]).reshape(gs, bs), _AXES)


class ShardedRolloutEngine:
    """Drop-in :class:`DynamicRolloutEngine` replacement over a mesh.

    Same public surface (``rollout_window`` / ``window_grads`` /
    ``greedy_decode`` / ``shape_keys_seen``) plus :meth:`window_weights`,
    the in-mesh per-graph reward-standardization + Eq.-14 step-weights
    kernel the fused update path uses.  The sampled graph batch must tile
    the mesh: G divisible by the "graphs" axis, B by the "chains" axis
    (validated per call with the offending sizes named).
    """

    def __init__(self, step_fn, cfg, *, backend=None,
                 mesh: Optional[Mesh] = None,
                 mesh_shape: Optional[Tuple[int, int]] = None,
                 rules: Optional[AxisRules] = None, population=None):
        if mesh is None:
            gs, bs = mesh_shape if mesh_shape is not None else (1, 1)
            mesh = make_rollout_mesh(gs, bs)
        missing = [a for a in _AXES if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"rollout mesh must carry axes {_AXES}, got "
                f"{mesh.axis_names} (missing {missing})")
        self.mesh = mesh
        self._gm = mesh.shape["graphs"]
        self._bm = mesh.shape["chains"]
        self._rules = dict(ROLLOUT_RULES, **(rules or {}))
        self._step = step_fn
        self._cfg = cfg
        self._backend = backend
        self._fused = backend is not None and backend.jit_fused
        self._fns = None
        self._population = population
        self._pop_fns = None
        self.shape_keys_seen = set()

    # -------------------------------------------------------------- specs
    def _spec(self, *axes, rank: int):
        """Logical leading axes + replicated tail → PartitionSpec."""
        lead = axes[:min(len(axes), rank)]
        return logical_spec(tuple(lead) + (None,) * (rank - len(lead)),
                            self._rules, self.mesh)

    def _tree_spec(self, tree, *lead):
        return jax.tree.map(
            lambda a: self._spec(*lead, rank=jnp.ndim(a)), tree)

    def _check_tiling(self, G: int, B: Optional[int] = None) -> None:
        if G % self._gm:
            raise ValueError(
                f"graph batch G={G} does not tile the mesh 'graphs' axis "
                f"({self._gm}) — pick graphs_per_episode divisible by it")
        if B is not None and B % self._bm:
            raise ValueError(
                f"chain batch B={B} does not tile the mesh 'chains' axis "
                f"({self._bm}) — pick batch_chains divisible by it")

    # ----------------------------------------------------------- builders
    def _build(self):
        raw_rollout, raw_loss, raw_greedy = build_window_fns(
            self._step, self._cfg, fused=self._fused, backend=self._backend)
        mesh = self.mesh

        def _rollout(ops, params, z, rngs, num_steps: int,
                     start_first: bool):
            gb = lambda r: self._spec("graphs", "chains", rank=r)
            tgb = lambda r: self._spec(None, "graphs", "chains", rank=r)
            f = shard_map(
                lambda o, p, z_, r_: raw_rollout(o, p, z_, r_,
                                                 num_steps, start_first),
                mesh=mesh,
                in_specs=(self._tree_spec(ops, "graphs"),
                          self._tree_spec(params), gb(4), gb(3)),
                out_specs=(gb(4), gb(3), tgb(4), tgb(4), tgb(3), tgb(3),
                           tgb(3)),
                check_vma=False)
            return f(ops, params, z, rngs)

        def _grads(ops, params, z0, keys, weights, num_steps: int,
                   start_first: bool):
            # The global chain count: each shard's partial loss divides by
            # it, so psum over both axes reassembles the unsharded mean.
            denom = z0.shape[0] * z0.shape[1]

            def local(o, p, z_, k_, w_):
                g = jax.grad(lambda pp: raw_loss(
                    o, pp, z_, k_, w_, num_steps, start_first, denom))(p)
                return jax.lax.psum(g, _AXES)

            f = shard_map(
                local, mesh=mesh,
                in_specs=(self._tree_spec(ops, "graphs"),
                          self._tree_spec(params),
                          self._spec("graphs", "chains", rank=4),
                          self._spec(None, "graphs", "chains", rank=4),
                          self._spec(None, "graphs", "chains", rank=3)),
                out_specs=self._tree_spec(params),
                check_vma=False)
            return f(ops, params, z0, keys, weights)

        def _greedy(ops, params, keys):
            f = shard_map(
                raw_greedy, mesh=mesh,
                in_specs=(self._tree_spec(ops, "graphs"),
                          self._tree_spec(params),
                          self._spec("graphs", rank=2)),
                out_specs=(self._spec("graphs", rank=2),
                           self._spec("graphs", rank=1)),
                check_vma=False)
            return f(ops, params, keys)

        def _weights(rewards, gamma: float, reward_to_go: bool,
                     normalize: bool, reward_norm: str):
            """(T, G, B) rewards → (T, G, B) Eq.-14 replay weights, with
            the corpus trainer's per-graph standardization done in-mesh
            (float32 mirror of the host float64 path in
            ``EpisodeRunner``/``step_weights``)."""
            T, _, B_global = rewards.shape

            def local(r):
                if reward_norm == "pergraph":
                    cnt = jnp.float32(T * B_global)
                    mean = jax.lax.psum(
                        jnp.sum(r, axis=(0, 2), keepdims=True),
                        "chains") / cnt
                    var = jax.lax.psum(
                        jnp.sum((r - mean) ** 2, axis=(0, 2),
                                keepdims=True), "chains") / cnt
                    r = (r - mean) / (jnp.sqrt(var) + 1e-8)
                if reward_to_go:
                    def body(acc, r_t):
                        acc = r_t + gamma * acc
                        return acc, acc
                    _, w = jax.lax.scan(body, jnp.zeros_like(r[0]), r,
                                        reverse=True)
                else:
                    disc = gamma ** jnp.arange(T, dtype=jnp.float32)
                    w = disc[:, None, None] * r
                if normalize and T > 1:
                    std = jnp.std(w, axis=0, keepdims=True)
                    safe = jnp.where(std > 1e-12, std, 1.0)
                    w = jnp.where(std > 1e-12,
                                  (w - jnp.mean(w, axis=0, keepdims=True))
                                  / safe, w)
                return w

            tgb = self._spec(None, "graphs", "chains", rank=3)
            f = shard_map(local, mesh=mesh, in_specs=(tgb,),
                          out_specs=tgb, check_vma=False)
            return f(rewards)

        return (jax.jit(_rollout,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(_grads,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(_greedy),
                jax.jit(_weights,
                        static_argnames=("gamma", "reward_to_go",
                                         "normalize", "reward_norm")))

    def _build_pop(self):
        """Shard_map the population window bodies over the mesh.

        Chain-local pieces (tempered rollout, replay gradient, chain-best
        folding) shard exactly like the base path.  The PBT transition is
        the one genuinely collective step: each shard ``all_gather``s the
        complete (row-wise) best-latency/temperature rows over "chains",
        computes the *identical* cull/exchange decisions everywhere (the
        per-row randomness is keyed on global row ids, so every shard
        derives the same keys), slices its local columns back out, and
        reassembles the elite broadcast from one-hot masked partial sums
        ``psum``-ed over "chains" — the same sums the full-view body
        computes, so mesh=1×1 is bitwise the dynamic engine's pbt_step.
        """
        from ..train import population as popmod
        fns = build_window_fns(self._step, self._cfg, fused=self._fused,
                               backend=self._backend,
                               population=self._population)
        mesh = self.mesh
        popcfg = self._population
        gb = lambda r: self._spec("graphs", "chains", rank=r)
        tgb = lambda r: self._spec(None, "graphs", "chains", rank=r)
        pop_spec = popmod.ChainState(
            temperature=gb(2), best_latency=gb(2), best_fine=gb(3),
            rng=self._spec(rank=1))

        def _rollout(ops, params, z, rngs, pop, num_steps: int,
                     start_first: bool):
            f = shard_map(
                lambda o, p, z_, r_, pp: fns.rollout(o, p, z_, r_, pp,
                                                     num_steps,
                                                     start_first),
                mesh=mesh,
                in_specs=(self._tree_spec(ops, "graphs"),
                          self._tree_spec(params), gb(4), gb(3), pop_spec),
                out_specs=(gb(4), gb(3), pop_spec, tgb(4), tgb(4), tgb(3),
                           tgb(3), tgb(3)),
                check_vma=False)
            return f(ops, params, z, rngs, pop)

        def _grads(ops, params, z0, keys, weights, temps, num_steps: int,
                   start_first: bool):
            denom = z0.shape[0] * z0.shape[1]

            def local(o, p, z_, k_, w_, t_):
                g = jax.grad(lambda pp: fns.loss(
                    o, pp, z_, k_, w_, t_, num_steps, start_first,
                    denom))(p)
                return jax.lax.psum(g, _AXES)

            f = shard_map(
                local, mesh=mesh,
                in_specs=(self._tree_spec(ops, "graphs"),
                          self._tree_spec(params), gb(4), tgb(4), tgb(3),
                          gb(2)),
                out_specs=self._tree_spec(params),
                check_vma=False)
            return f(ops, params, z0, keys, weights, temps)

        def _pbt(ops, params, pop, z, use_greedy: bool):
            def local(o, p, pp, z_):
                Gl, Bl = pp.temperature.shape
                gidx = jax.lax.axis_index("graphs")
                bidx = jax.lax.axis_index("chains")
                row_ids = gidx * Gl + jnp.arange(Gl)
                cols = bidx * Bl + jnp.arange(Bl)
                lat_rows = jax.lax.all_gather(pp.best_latency, "chains",
                                              axis=1, tiled=True)
                temp_rows = jax.lax.all_gather(pp.temperature, "chains",
                                               axis=1, tiled=True)
                k_use, k_greedy, k_next = jax.random.split(pp.rng, 3)
                culled_g, inherit_g, new_temp_g, jstar = popmod.pbt_rows(
                    popcfg, k_use, lat_rows, temp_rows, row_ids)
                sl = lambda a: jax.lax.dynamic_slice_in_dim(
                    a, bidx * Bl, Bl, axis=1)
                culled, inherit = sl(culled_g), sl(inherit_g)
                new_temp = sl(new_temp_g)
                onehot = cols[None, :] == jstar[:, None]       # (Gl, Bl)
                lat_star = jax.lax.psum(
                    jnp.sum(jnp.where(onehot, pp.best_latency, 0.0),
                            axis=1), "chains")
                fine_star = jax.lax.psum(
                    jnp.sum(pp.best_fine * onehot[:, :, None], axis=1),
                    "chains")
                z_star = jax.lax.psum(
                    jnp.sum(z_ * onehot[:, :, None, None].astype(z_.dtype),
                            axis=1), "chains")
                if use_greedy:
                    gkeys = jax.vmap(jax.random.fold_in,
                                     in_axes=(None, 0))(k_greedy, row_ids)
                    z_src = fns.greedy_state(o, p, gkeys)
                else:
                    z_src = z_star
                new_z = jnp.where(culled[:, :, None, None], z_src[:, None],
                                  z_)
                new_pop = pp._replace(
                    temperature=new_temp,
                    best_latency=jnp.where(inherit, lat_star[:, None],
                                           pp.best_latency),
                    best_fine=jnp.where(inherit[:, :, None],
                                        fine_star[:, None], pp.best_fine),
                    rng=k_next)
                return new_pop, new_z

            f = shard_map(local, mesh=mesh,
                          in_specs=(self._tree_spec(ops, "graphs"),
                                    self._tree_spec(params), pop_spec,
                                    gb(4)),
                          out_specs=(pop_spec, gb(4)),
                          check_vma=False)
            return f(ops, params, pop, z)

        def _update(pop, fines, latencies):
            f = shard_map(fns.update_bests, mesh=mesh,
                          in_specs=(pop_spec, tgb(4), tgb(3)),
                          out_specs=pop_spec, check_vma=False)
            return f(pop, fines, latencies)

        return (jax.jit(_rollout,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(_grads,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(_pbt, static_argnames=("use_greedy",)),
                jax.jit(_update))

    @property
    def _built(self):
        if self._fns is None:
            self._fns = self._build()
        return self._fns

    @property
    def _pop_built(self):
        if self._pop_fns is None:
            if self._population is None:
                raise ValueError(
                    "population path requested but the engine was built "
                    "without population= (pass a PopulationConfig)")
            self._pop_fns = self._build_pop()
        return self._pop_fns

    def _note(self, ops: GraphOperands) -> None:
        self.shape_keys_seen.add(ops.shape_key())

    # --------------------------------------------------------- public API
    def rollout_window(self, ops: GraphOperands, params, z, rngs, *,
                       num_steps: int, start_first: bool):
        self._check_tiling(z.shape[0], z.shape[1])
        self._note(ops)
        return self._built[0](ops, params, z, rngs, num_steps=num_steps,
                              start_first=start_first)

    def window_grads(self, ops: GraphOperands, params, z0, keys, weights, *,
                     num_steps: int, start_first: bool):
        self._check_tiling(z0.shape[0], z0.shape[1])
        self._note(ops)
        return self._built[1](ops, params, z0, keys, weights,
                              num_steps=num_steps, start_first=start_first)

    def greedy_decode(self, ops: GraphOperands, params, keys):
        self._check_tiling(keys.shape[0])
        self._note(ops)
        return self._built[2](ops, params, keys)

    # ------------------------------------------------------- population API
    @property
    def population(self):
        return self._population

    def init_population(self, key, *, num_graphs: int, num_chains: int,
                        num_nodes: int, temperatures=None):
        from ..train import population as popmod
        self._check_tiling(num_graphs, num_chains)
        return popmod.init_chain_state(
            self._population, key, num_graphs=num_graphs,
            num_chains=num_chains, num_nodes=num_nodes,
            temperatures=temperatures)

    def rollout_window_pop(self, ops: GraphOperands, params, z, rngs, pop, *,
                           num_steps: int, start_first: bool):
        self._check_tiling(z.shape[0], z.shape[1])
        self._note(ops)
        return self._pop_built[0](ops, params, z, rngs, pop,
                                  num_steps=num_steps,
                                  start_first=start_first)

    def window_grads_pop(self, ops: GraphOperands, params, z0, keys, weights,
                         temps, *, num_steps: int, start_first: bool):
        self._check_tiling(z0.shape[0], z0.shape[1])
        self._note(ops)
        return self._pop_built[1](ops, params, z0, keys, weights, temps,
                                  num_steps=num_steps,
                                  start_first=start_first)

    def pbt_step(self, ops: GraphOperands, params, pop, z, *,
                 use_greedy: bool = False):
        self._check_tiling(z.shape[0], z.shape[1])
        self._note(ops)
        return self._pop_built[2](ops, params, pop, z,
                                  use_greedy=use_greedy)

    def update_population(self, pop, fines, latencies):
        return self._pop_built[3](pop, fines, latencies)

    def window_weights(self, rewards, *, gamma: float, reward_to_go: bool,
                       normalize: bool, reward_norm: str):
        rewards = jnp.asarray(rewards, dtype=jnp.float32)
        self._check_tiling(rewards.shape[1], rewards.shape[2])
        return self._built[3](rewards, gamma=float(gamma),
                              reward_to_go=bool(reward_to_go),
                              normalize=bool(normalize),
                              reward_norm=str(reward_norm))
