"""RolloutEngine — the one window-granular rollout/replay engine.

Before this layer ``core/hsdag.py`` carried three near-duplicate engine
paths (``_make_jitted`` / ``_make_batched`` / ``_make_multi``): the same
sample-score-replay closures, triplicated for the scalar, B-chain and
(G, B)-chain cases, with the reward source hardcoded in each.  The engine
collapses them:

* :meth:`rollout_window` / :meth:`window_grads` — the jitted (G, B)-chain
  window rollout and its differentiable Eq.-14 ``lax.scan`` replay.  The
  single-graph batched search runs the same code at G=1 (proven bitwise
  equal to the former dedicated path by the PR-2 equivalence suite), and
  rewards come from the :class:`~.pipeline.RewardPipeline` — in-jit for
  the ``scan`` backend (one simulator walk over the whole window's
  placements, after the step scan: :func:`fused_window_scores`),
  deferred to window scoring on the host otherwise.
* :meth:`rollout_step` / :meth:`window_grads_scalar` — the PR-1 scalar
  reference loop (one unbatched chain, Python-unrolled replay), kept
  verbatim as the ground-truth implementation the batched engines are
  pinned against (and the path ``place()`` decodes through).

Masks (``node_mask``/``edge_mask``) thread the padded multi-graph contract
exactly as before: dropped at trace time when the batch needs no padding, so
G=1 on an unpadded batch is the unmasked computation.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import export as jax_export

from .pipeline import RewardPipeline

__all__ = ["RolloutEngine", "DynamicRolloutEngine", "GraphOperands",
           "PopulationWindowFns", "split_multi_keys", "build_window_fns"]


def split_multi_keys(rngs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-chain key split over a (G, B, 2) key batch."""
    both = jax.vmap(jax.vmap(jax.random.split))(rngs)    # (G, B, 2, 2)
    return both[:, :, 0], both[:, :, 1]


def fused_window_scores(score, sim, fine):
    """A window's (T, G, B, V) placements → (rewards, latencies), (T, G, B).

    ``score(simg, placement)`` is a fused backend's per-placement hook on
    one graph's sim tree; ``sim`` carries a leading (G,) axis.  The hook is
    vmapped over each graph's T·B placements and that over the graphs, so a
    sequential simulator (the ``scan`` backend's node scan) walks the graph
    once per window, T·G·B lanes wide.  Scoring never feeds the step scan's
    carry, so the rollout scores after it rather than once per step.
    ``score=None`` (a deferred pipeline) gives zeros for the host to fill.
    """
    T, G, B, V = fine.shape
    if score is None:
        return (jnp.zeros((T, G, B), jnp.float32),) * 2
    flat = jnp.swapaxes(fine, 0, 1).reshape(G, T * B, V)
    rewards, latencies = jax.vmap(jax.vmap(score, in_axes=(None, 0)))(
        sim, flat)
    return tuple(jnp.swapaxes(a.reshape(G, T, B), 0, 1)
                 for a in (rewards, latencies))


class RolloutEngine:
    """Builds and caches the jitted rollout/replay functions for one
    (graph batch, config, reward pipeline) triple.  See module docstring.

    ``step_fn`` is the Alg.-1 iteration (``HSDAG._step``-shaped)::

        step_fn(params, z, x0, adj, edges, rng, *, first, train,
                greedy=False, node_mask=None, edge_mask=None) -> StepOutput
    """

    def __init__(self, step_fn, cfg, *, x0, adj, edges,
                 node_mask=None, edge_mask=None,
                 pipeline: Optional[RewardPipeline] = None,
                 population=None, dev_feats=None):
        self._step = step_fn
        self._cfg = cfg
        self._x0 = jnp.asarray(x0)                   # (G, V, d)
        self._adj = jnp.asarray(adj)                 # (G, V, V)
        self._edges = jnp.asarray(edges)             # (G, E, 2)
        self._use_masks = node_mask is not None
        self._nmask = jnp.asarray(node_mask) if self._use_masks else None
        self._emask = jnp.asarray(edge_mask) if self._use_masks else None
        self._pipeline = pipeline
        self._fused = pipeline is not None and pipeline.fused
        self._sim = (jax.tree.map(jnp.asarray, pipeline.sim_tree)
                     if self._fused else None)
        # head="device": the (D, F_dev) fleet feature table, a closure
        # constant shared by every graph/chain; None keeps the dense head's
        # traces untouched.  Capacity masking (SimArrays.fit_ok) applies
        # whenever dev_feats and a fused sim tree are both present.
        self._dev_feats = (jnp.asarray(dev_feats)
                           if dev_feats is not None else None)
        self._window_fns = None
        self._scalar_fns = None
        self._population = population
        self._pop_state = None

    # ----------------------------------------------------- (G, B) window path
    def _build_window_fns(self):
        # The layer scopes of these programs live only in their metadata,
        # which JAX leaves out of the persistent compile cache's key by
        # default: an executable compiled from the same arithmetic under
        # other names would come back with those names.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        cfg = self._cfg
        step = self._step
        x0, adj, edges = self._x0, self._adj, self._edges
        use_masks, nmask, emask = self._use_masks, self._nmask, self._emask
        fused, sim, pipeline = self._fused, self._sim, self._pipeline
        dvf = self._dev_feats
        # Capacity masking needs the per-graph fit_ok rows, which only the
        # fused sim tree carries; the replay below threads sim through the
        # loss under the same condition so the sampled and replayed
        # distributions coincide (the Eq.-14 exactness requirement).
        mask_sim = dvf is not None and sim is not None

        def _chain_sample(params, xg, ag, eg, nmg, emg, simg, z, key,
                          first: bool):
            amask = simg.fit_ok if (mask_sim and simg is not None) else None
            out = step(params, z, xg, ag, eg, key, first=first, train=True,
                       node_mask=nmg, edge_mask=emg, dev_feats=dvf,
                       action_mask=amask)
            return (out.policy.fine_placement, out.parse.num_groups,
                    out.z_next)

        def _vsample(params, z, keys, first: bool):
            """z (G, B, V, d), keys (G, B, 2) → per-(g, b) samples."""

            def per_graph(xg, ag, eg, nmg, emg, simg, z_b, k_b):
                return jax.vmap(lambda z1, k1: _chain_sample(
                    params, xg, ag, eg, nmg, emg, simg, z1, k1, first)
                )(z_b, k_b)

            # Masks and the sim pytree are optional per-graph operands;
            # branch at trace time so absent ones never enter the vmap.
            # Sampling reads the sim tree only for the capacity mask.
            if use_masks and mask_sim:
                return jax.vmap(per_graph)(x0, adj, edges, nmask, emask,
                                           sim, z, keys)
            if use_masks:
                return jax.vmap(
                    lambda xg, ag, eg, nmg, emg, z_b, k_b: per_graph(
                        xg, ag, eg, nmg, emg, None, z_b, k_b)
                )(x0, adj, edges, nmask, emask, z, keys)
            if mask_sim:
                return jax.vmap(
                    lambda xg, ag, eg, simg, z_b, k_b: per_graph(
                        xg, ag, eg, None, None, simg, z_b, k_b)
                )(x0, adj, edges, sim, z, keys)
            return jax.vmap(
                lambda xg, ag, eg, z_b, k_b: per_graph(
                    xg, ag, eg, None, None, None, z_b, k_b)
            )(x0, adj, edges, z, keys)

        def _rollout_window(params, z, rngs, num_steps: int,
                            start_first: bool):
            """→ (z_final, rngs_final, keys (T,G,B,2), fine (T,G,B,V),
                  ngroups (T,G,B), rewards (T,G,B), latencies (T,G,B))."""

            def body(carry, _):
                z_c, rngs_c = carry
                rngs_c, keys = split_multi_keys(rngs_c)
                fine, ngroups, z_next = _vsample(params, z_c, keys,
                                                 first=False)
                return (z_next, rngs_c), (keys, fine, ngroups)

            if start_first:
                rngs, keys0 = split_multi_keys(rngs)
                fine0, ng0, z = _vsample(params, z, keys0, first=True)
                (z, rngs), tail = jax.lax.scan(body, (z, rngs), None,
                                               length=num_steps - 1)
                head = (keys0, fine0, ng0)
                outs = tuple(jnp.concatenate([h[None], t], axis=0)
                             for h, t in zip(head, tail))
            else:
                (z, rngs), outs = jax.lax.scan(body, (z, rngs), None,
                                               length=num_steps)
            return (z, rngs) + outs + fused_window_scores(
                pipeline.step_score if fused else None, sim, outs[1])

        def _window_loss(params, z0, keys, weights, num_steps: int,
                         start_first: bool):
            """Differentiable lax.scan replay (Eq. 14) averaged over every
            (g, b) chain.  keys (T,G,B,2), weights (T,G,B)."""

            def _chain_loss(params_, xg, ag, eg, nmg, emg, simg, z1, k1, w1,
                            first: bool):
                amask = simg.fit_ok if (mask_sim and simg is not None) \
                    else None
                out = step(params_, z1, xg, ag, eg, k1, first=first,
                           train=True, node_mask=nmg, edge_mask=emg,
                           dev_feats=dvf, action_mask=amask)
                loss = -out.policy.logp * w1
                loss = loss - cfg.entropy_coef * out.policy.entropy
                return out.z_next, loss

            def _vloss(z_c, k_t, w_t, first: bool):
                def per_graph(xg, ag, eg, nmg, emg, simg, z_b, k_b, w_b):
                    return jax.vmap(
                        lambda z1, k1, w1: _chain_loss(
                            params, xg, ag, eg, nmg, emg, simg, z1, k1, w1,
                            first)
                    )(z_b, k_b, w_b)

                if use_masks and mask_sim:
                    return jax.vmap(per_graph)(x0, adj, edges, nmask, emask,
                                               sim, z_c, k_t, w_t)
                if use_masks:
                    return jax.vmap(
                        lambda xg, ag, eg, nmg, emg, z_b, k_b, w_b: per_graph(
                            xg, ag, eg, nmg, emg, None, z_b, k_b, w_b)
                    )(x0, adj, edges, nmask, emask, z_c, k_t, w_t)
                if mask_sim:
                    return jax.vmap(
                        lambda xg, ag, eg, simg, z_b, k_b, w_b: per_graph(
                            xg, ag, eg, None, None, simg, z_b, k_b, w_b)
                    )(x0, adj, edges, sim, z_c, k_t, w_t)
                return jax.vmap(
                    lambda xg, ag, eg, z_b, k_b, w_b: per_graph(
                        xg, ag, eg, None, None, None, z_b, k_b, w_b)
                )(x0, adj, edges, z_c, k_t, w_t)

            total = jnp.float32(0.0)
            z = z0
            if start_first:
                z, l0 = _vloss(z, keys[0], weights[0], first=True)
                total = total + jnp.sum(l0)
                keys, weights = keys[1:], weights[1:]

            def body(carry, xs):
                z_c, tot = carry
                k_t, w_t = xs
                z_c, l_t = _vloss(z_c, k_t, w_t, first=False)
                return (z_c, tot + jnp.sum(l_t)), None

            (z, total), _ = jax.lax.scan(body, (z, total), (keys, weights))
            nchains = z0.shape[0] * z0.shape[1]
            return total / nchains

        rollout_window = jax.jit(_rollout_window,
                                 static_argnames=("num_steps", "start_first"))
        grad_fn = jax.jit(jax.grad(_window_loss),
                          static_argnames=("num_steps", "start_first"))
        return rollout_window, grad_fn

    @property
    def _window(self):
        if self._window_fns is None:
            self._window_fns = self._build_window_fns()
        return self._window_fns

    # The host spans name the launch of each window program on the
    # profiler's clock (the first call's lazy build included); they return
    # before the device finishes and add no sync.
    def rollout_window(self, params, z, rngs, *, num_steps: int,
                       start_first: bool):
        with jax.profiler.TraceAnnotation("repro.rollout"):
            return self._window[0](params, z, rngs, num_steps=num_steps,
                                   start_first=start_first)

    def window_grads(self, params, z0, keys, weights, *, num_steps: int,
                     start_first: bool):
        with jax.profiler.TraceAnnotation("repro.replay"):
            return self._window[1](params, z0, keys, weights,
                                   num_steps=num_steps,
                                   start_first=start_first)

    # ------------------------------------------------------- population API
    # The pop path is implemented once, on the operand-style engine; the
    # static engine delegates through a fixed GraphOperands built from its
    # closure constants (all-true masks when it was constructed unmasked —
    # numerically identical by the padding contract).  The closure-constant
    # base path above is untouched, preserving the population=None pin.
    @property
    def _pop(self):
        if self._pop_state is None:
            if self._population is None:
                raise ValueError(
                    "population path requested but the engine was built "
                    "without population= (pass a PopulationConfig)")
            backend = (self._pipeline.backend
                       if self._pipeline is not None else None)
            eng = DynamicRolloutEngine(self._step, self._cfg,
                                       backend=backend,
                                       population=self._population)
            nmask = (self._nmask if self._use_masks else
                     jnp.ones(self._x0.shape[:2], dtype=bool))
            emask = (self._emask if self._use_masks else
                     jnp.ones(self._edges.shape[:2], dtype=bool))
            dvf = self._dev_feats
            if dvf is not None:
                # Operand trees carry a leading (G,) axis on every leaf
                # (the sharded mirror shards that axis over its "graphs"
                # mesh dim), so the shared fleet table is broadcast per
                # graph rather than passed rank-2.
                dvf = jnp.broadcast_to(dvf, (self._x0.shape[0],) + dvf.shape)
            ops = GraphOperands(self._x0, self._adj, self._edges,
                                nmask, emask, sim=self._sim, dev_feats=dvf)
            self._pop_state = (eng, ops)
        return self._pop_state

    @property
    def population(self):
        return self._population

    def init_population(self, key, *, num_chains: int, temperatures=None):
        eng, ops = self._pop
        return eng.init_population(
            key, num_graphs=self._x0.shape[0], num_chains=num_chains,
            num_nodes=self._x0.shape[1], temperatures=temperatures)

    def rollout_window_pop(self, params, z, rngs, pop, *, num_steps: int,
                           start_first: bool):
        eng, ops = self._pop
        return eng.rollout_window_pop(ops, params, z, rngs, pop,
                                      num_steps=num_steps,
                                      start_first=start_first)

    def window_grads_pop(self, params, z0, keys, weights, temps, *,
                         num_steps: int, start_first: bool):
        eng, ops = self._pop
        return eng.window_grads_pop(ops, params, z0, keys, weights, temps,
                                    num_steps=num_steps,
                                    start_first=start_first)

    def pbt_step(self, params, pop, z, *, use_greedy: bool = False):
        eng, ops = self._pop
        return eng.pbt_step(ops, params, pop, z, use_greedy=use_greedy)

    def update_population(self, pop, fines, latencies):
        eng, _ = self._pop
        return eng.update_population(pop, fines, latencies)

    # ------------------------------------------------- scalar reference path
    def _build_scalar_fns(self):
        import numpy as np
        cfg = self._cfg
        step = self._step
        # The scalar engine is single-graph by construction: graph slot 0.
        x0, adj, edges = self._x0[0], self._adj[0], self._edges[0]
        if self._use_masks:
            # Masks are concrete at build time — trim pad slots (e.g. the
            # phantom edge row batch_graph_arrays pads an edge-free graph
            # to) so the scalar path sees exactly the unpadded arrays.
            nm = np.asarray(self._nmask[0])
            em = np.asarray(self._emask[0])
            x0 = jnp.asarray(np.asarray(x0)[nm])
            adj = jnp.asarray(np.asarray(adj)[np.ix_(nm, nm)])
            edges = jnp.asarray(np.asarray(edges)[em])

        # head="device" threads the fleet table here too so place() can
        # greedy-decode through the scalar path; capacity masks don't —
        # the scalar loop predates SimArrays and stays the unmasked
        # reference (hsdag forbids engine="scalar" *training* for the
        # device head).
        dvf = self._dev_feats

        def _rollout_step(params, z, rng, first: bool, greedy: bool = False):
            out = step(params, z, x0, adj, edges, rng,
                       first=first, train=not greedy, greedy=greedy,
                       dev_feats=dvf)
            return (out.policy.fine_placement, out.policy.coarse_placement,
                    out.parse.num_groups, out.z_next)

        def _window_loss(params, z0, rngs, weights, num_steps: int,
                         start_first: bool):
            """Python-unrolled replay of a buffer window (Eq. 14) — the
            reference gradient the scanned replay is pinned against."""
            z = z0
            loss = jnp.float32(0.0)
            for i in range(num_steps):
                first = start_first and i == 0
                out = step(params, z, x0, adj, edges, rngs[i],
                           first=first, train=True, dev_feats=dvf)
                loss = loss - out.policy.logp * weights[i]
                loss = loss - cfg.entropy_coef * out.policy.entropy
                z = out.z_next
            return loss

        rollout_step = jax.jit(_rollout_step,
                               static_argnames=("first", "greedy"))
        grad_fn = jax.jit(jax.grad(_window_loss),
                          static_argnames=("num_steps", "start_first"))
        return rollout_step, grad_fn

    @property
    def _scalar(self):
        if self._scalar_fns is None:
            self._scalar_fns = self._build_scalar_fns()
        return self._scalar_fns

    def rollout_step(self, params, z, rng, *, first: bool,
                     greedy: bool = False):
        return self._scalar[0](params, z, rng, first=first, greedy=greedy)

    def window_grads_scalar(self, params, z0, rngs, weights, *,
                            num_steps: int, start_first: bool):
        return self._scalar[1](params, z0, rngs, weights,
                               num_steps=num_steps, start_first=start_first)


class GraphOperands(NamedTuple):
    """The per-episode graph batch a :class:`DynamicRolloutEngine` consumes.

    Every field is an array with a leading (G,) axis (``sim`` is a pytree of
    such arrays, or ``None`` for non-fused backends).  The engine's jitted
    functions take the whole tuple as a *traced operand*, so jax's jit cache
    keys on its shapes: a corpus bucketed into K shape classes compiles each
    function at most K times no matter how many graph subsets stream
    through.
    """

    x0: jnp.ndarray          # (G, V, d)
    adj: jnp.ndarray         # (G, V, V)
    edges: jnp.ndarray       # (G, E, 2)
    node_mask: jnp.ndarray   # (G, V) bool
    edge_mask: jnp.ndarray   # (G, E) bool
    sim: object = None       # SimArrays pytree with (G, ...) axes, or None
    dev_feats: object = None  # (G, D, F_dev) fleet table (head="device"),
    #                           broadcast per graph so the leading axis
    #                           matches the sharded "graphs" contract

    def shape_key(self) -> Tuple:
        """Shape/dtype signature — what the jit cache keys on."""
        return tuple((tuple(a.shape), str(a.dtype))
                     for a in jax.tree.leaves(self))


# AOT export (jax.export) serializes pytree structure by name; registering
# GraphOperands once here lets any process deserialize an exported decode
# whose signature carries the operand tuple.
jax_export.register_namedtuple_serialization(
    GraphOperands, serialized_name="repro.core.sim.GraphOperands")


class PopulationWindowFns(NamedTuple):
    """The raw population-search window closures ``build_window_fns``
    returns when a :class:`~repro.core.train.population.PopulationConfig`
    is passed.  Same sharing contract as the base triple: the dynamic
    engine jits them, the sharded engine shard_maps the same bodies."""

    rollout: object       # (ops, params, z, rngs, pop, T, first) → 8-tuple
    loss: object          # (ops, params, z0, keys, w, temps, T, first[, denom])
    greedy: object        # (ops, params, keys) → (fine, ngroups) per graph
    greedy_state: object  # (ops, params, keys) → (G, V, d) post-decode state
    pbt: object           # (ops, params, pop, z, use_greedy) → (pop, z)
    update_bests: object  # (pop, fines, latencies) → pop


def build_window_fns(step, cfg, *, fused: bool, backend, population=None):
    """The raw (unjitted) operand-style window functions.

    One builder, two consumers: :class:`DynamicRolloutEngine` jits these
    directly; :class:`~repro.core.sim.sharded.ShardedRolloutEngine`
    shard_maps the *same* bodies over a ("graphs", "chains") mesh.  Sharing
    the closures is what makes the mesh=1×1 bitwise-parity contract hold —
    both engines trace the identical per-shard computation.

    Returns ``(_rollout_window, _window_loss, _greedy)``.  ``_window_loss``
    takes an optional ``denom`` — the chain count to average over.  The
    dynamic engine leaves it ``None`` (local ``G*B``, the historical
    behaviour); a sharded caller passes the *global* chain count so the
    per-shard partial losses sum (via psum of their grads) to exactly the
    unsharded mean.

    With ``population=`` (a PopulationConfig) the return value is instead a
    :class:`PopulationWindowFns`: the same rollout/loss bodies with the
    per-chain sampling temperature threaded into every policy step (a
    :class:`~repro.core.train.population.ChainState` rides along as an
    operand, its per-chain best records updated in-jit when the pipeline is
    fused), plus the full-view PBT transition.  ``population=None`` leaves
    this function's output — closure for closure, jaxpr for jaxpr —
    exactly the PR-7 build.
    """

    def _graph_vmap(per_graph, ops, rest, *, with_sim, with_dev):
        """vmap ``per_graph(xg, ag, eg, nmg, emg, simg, dvg, *rest)`` over
        the graph axis, injecting ``None`` for the sim tree / fleet table
        when the operands don't carry them — absent ones never enter the
        trace, so dense/deferred builds keep their historical jaxprs."""
        base = (ops.x0, ops.adj, ops.edges, ops.node_mask, ops.edge_mask)
        if with_sim and with_dev:
            return jax.vmap(per_graph)(*base, ops.sim, ops.dev_feats, *rest)
        if with_sim:
            return jax.vmap(
                lambda xg, ag, eg, nmg, emg, simg, *r: per_graph(
                    xg, ag, eg, nmg, emg, simg, None, *r)
            )(*base, ops.sim, *rest)
        if with_dev:
            return jax.vmap(
                lambda xg, ag, eg, nmg, emg, dvg, *r: per_graph(
                    xg, ag, eg, nmg, emg, None, dvg, *r)
            )(*base, ops.dev_feats, *rest)
        return jax.vmap(
            lambda xg, ag, eg, nmg, emg, *r: per_graph(
                xg, ag, eg, nmg, emg, None, None, *r)
        )(*base, *rest)

    def _chain_sample(params, xg, ag, eg, nmg, emg, simg, dvg, z, key,
                      first: bool):
        # Capacity masking (fit_ok) rides only with the device head AND a
        # sim operand: dense fused runs must not see a mask (the pin), and
        # without sim there is nothing to mask against.
        amask = simg.fit_ok if (dvg is not None and simg is not None) \
            else None
        out = step(params, z, xg, ag, eg, key, first=first, train=True,
                   node_mask=nmg, edge_mask=emg, dev_feats=dvg,
                   action_mask=amask)
        return out.policy.fine_placement, out.parse.num_groups, out.z_next

    def _vsample(ops, params, z, keys, first: bool):
        def per_graph(xg, ag, eg, nmg, emg, simg, dvg, z_b, k_b):
            return jax.vmap(lambda z1, k1: _chain_sample(
                params, xg, ag, eg, nmg, emg, simg, dvg, z1, k1, first)
            )(z_b, k_b)

        return _graph_vmap(per_graph, ops, (z, keys),
                           with_sim=fused and ops.dev_feats is not None,
                           with_dev=ops.dev_feats is not None)

    def _rollout_window(ops, params, z, rngs, num_steps: int,
                        start_first: bool):
        def body(carry, _):
            z_c, rngs_c = carry
            rngs_c, keys = split_multi_keys(rngs_c)
            fine, ngroups, z_next = _vsample(ops, params, z_c, keys,
                                             first=False)
            return (z_next, rngs_c), (keys, fine, ngroups)

        if start_first:
            rngs, keys0 = split_multi_keys(rngs)
            fine0, ng0, z = _vsample(ops, params, z, keys0, first=True)
            (z, rngs), tail = jax.lax.scan(body, (z, rngs), None,
                                           length=num_steps - 1)
            head = (keys0, fine0, ng0)
            outs = tuple(jnp.concatenate([h[None], t], axis=0)
                         for h, t in zip(head, tail))
        else:
            (z, rngs), outs = jax.lax.scan(body, (z, rngs), None,
                                           length=num_steps)
        return (z, rngs) + outs + fused_window_scores(
            backend.score if fused else None, ops.sim, outs[1])

    def _window_loss(ops, params, z0, keys, weights, num_steps: int,
                     start_first: bool, denom=None):
        def _chain_loss(params_, xg, ag, eg, nmg, emg, simg, dvg, z1, k1, w1,
                        first: bool):
            # The replay must mask exactly as sampling did (Eq.-14
            # exactness), so the sim tree threads in under the same
            # device-head condition.
            amask = simg.fit_ok if (dvg is not None and simg is not None) \
                else None
            out = step(params_, z1, xg, ag, eg, k1, first=first,
                       train=True, node_mask=nmg, edge_mask=emg,
                       dev_feats=dvg, action_mask=amask)
            loss = -out.policy.logp * w1
            loss = loss - cfg.entropy_coef * out.policy.entropy
            return out.z_next, loss

        def _vloss(z_c, k_t, w_t, first: bool):
            def per_graph(xg, ag, eg, nmg, emg, simg, dvg, z_b, k_b, w_b):
                return jax.vmap(
                    lambda z1, k1, w1: _chain_loss(
                        params, xg, ag, eg, nmg, emg, simg, dvg, z1, k1,
                        w1, first)
                )(z_b, k_b, w_b)

            return _graph_vmap(
                per_graph, ops, (z_c, k_t, w_t),
                with_sim=fused and ops.dev_feats is not None,
                with_dev=ops.dev_feats is not None)

        total = jnp.float32(0.0)
        z = z0
        if start_first:
            z, l0 = _vloss(z, keys[0], weights[0], first=True)
            total = total + jnp.sum(l0)
            keys, weights = keys[1:], weights[1:]

        def body(carry, xs):
            z_c, tot = carry
            k_t, w_t = xs
            z_c, l_t = _vloss(z_c, k_t, w_t, first=False)
            return (z_c, tot + jnp.sum(l_t)), None

        (z, total), _ = jax.lax.scan(body, (z, total), (keys, weights))
        nchains = denom if denom is not None else z0.shape[0] * z0.shape[1]
        return total / nchains

    def _greedy(ops, params, keys):
        """One greedy decode per graph slot → (G, V) placements."""
        def per_graph(xg, ag, eg, nmg, emg, simg, dvg, k):
            amask = simg.fit_ok if (dvg is not None and simg is not None) \
                else None
            out = step(params, xg, xg, ag, eg, k,
                       first=True, train=False, greedy=True,
                       node_mask=nmg, edge_mask=emg, dev_feats=dvg,
                       action_mask=amask)
            return out.policy.fine_placement, out.parse.num_groups

        return _graph_vmap(
            per_graph, ops, (keys,),
            with_sim=ops.dev_feats is not None and ops.sim is not None,
            with_dev=ops.dev_feats is not None)

    if population is None:
        return _rollout_window, _window_loss, _greedy

    # ------------------------------------------------ population variants
    # Function-level import: core/train pulls in the curriculum stack
    # (which imports this module); by the time an engine is *built* both
    # packages are fully imported, so no cycle — and the population-free
    # path never touches core/train at all.
    from ..train import population as popmod

    def _chain_sample_pop(params, xg, ag, eg, nmg, emg, simg, dvg, z, key,
                          temp, first: bool):
        amask = simg.fit_ok if (dvg is not None and simg is not None) \
            else None
        out = step(params, z, xg, ag, eg, key, first=first, train=True,
                   node_mask=nmg, edge_mask=emg, temperature=temp,
                   dev_feats=dvg, action_mask=amask)
        return out.policy.fine_placement, out.parse.num_groups, out.z_next

    def _vsample_pop(ops, params, z, keys, temps, first: bool):
        def per_graph(xg, ag, eg, nmg, emg, simg, dvg, z_b, k_b, t_b):
            return jax.vmap(lambda z1, k1, t1: _chain_sample_pop(
                params, xg, ag, eg, nmg, emg, simg, dvg, z1, k1, t1, first)
            )(z_b, k_b, t_b)

        return _graph_vmap(per_graph, ops, (z, keys, temps),
                           with_sim=fused and ops.dev_feats is not None,
                           with_dev=ops.dev_feats is not None)

    def _rollout_window_pop(ops, params, z, rngs, pop, num_steps: int,
                            start_first: bool):
        """→ (z, rngs, pop, keys, fine, ngroups, rewards, latencies); the
        chain-best records fold in-jit when rewards are fused (host-scored
        paths call ``update_bests`` afterwards)."""
        temps = pop.temperature

        def body(carry, _):
            z_c, rngs_c = carry
            rngs_c, keys = split_multi_keys(rngs_c)
            fine, ngroups, z_next = _vsample_pop(ops, params, z_c, keys,
                                                 temps, first=False)
            return (z_next, rngs_c), (keys, fine, ngroups)

        if start_first:
            rngs, keys0 = split_multi_keys(rngs)
            fine0, ng0, z = _vsample_pop(ops, params, z, keys0, temps,
                                         first=True)
            (z, rngs), tail = jax.lax.scan(body, (z, rngs), None,
                                           length=num_steps - 1)
            head = (keys0, fine0, ng0)
            outs = tuple(jnp.concatenate([h[None], t], axis=0)
                         for h, t in zip(head, tail))
        else:
            (z, rngs), outs = jax.lax.scan(body, (z, rngs), None,
                                           length=num_steps)
        outs = outs + fused_window_scores(
            backend.score if fused else None, ops.sim, outs[1])
        if fused:
            pop = popmod.update_chain_bests(pop, outs[1], outs[4])
        return (z, rngs, pop) + outs

    def _window_loss_pop(ops, params, z0, keys, weights, temps,
                         num_steps: int, start_first: bool, denom=None):
        """The Eq.-14 replay with the *same* per-chain temperatures the
        sampling pass used — the tempered logp is the exact log-density of
        what was sampled, so the gradient stays unbiased."""

        def _chain_loss(params_, xg, ag, eg, nmg, emg, simg, dvg, z1, k1,
                        w1, t1, first: bool):
            amask = simg.fit_ok if (dvg is not None and simg is not None) \
                else None
            out = step(params_, z1, xg, ag, eg, k1, first=first,
                       train=True, node_mask=nmg, edge_mask=emg,
                       temperature=t1, dev_feats=dvg, action_mask=amask)
            loss = -out.policy.logp * w1
            loss = loss - cfg.entropy_coef * out.policy.entropy
            return out.z_next, loss

        def _vloss(z_c, k_t, w_t, first: bool):
            def per_graph(xg, ag, eg, nmg, emg, simg, dvg, z_b, k_b, w_b,
                          t_b):
                return jax.vmap(
                    lambda z1, k1, w1, t1: _chain_loss(
                        params, xg, ag, eg, nmg, emg, simg, dvg, z1, k1,
                        w1, t1, first)
                )(z_b, k_b, w_b, t_b)

            return _graph_vmap(
                per_graph, ops, (z_c, k_t, w_t, temps),
                with_sim=fused and ops.dev_feats is not None,
                with_dev=ops.dev_feats is not None)

        total = jnp.float32(0.0)
        z = z0
        if start_first:
            z, l0 = _vloss(z, keys[0], weights[0], first=True)
            total = total + jnp.sum(l0)
            keys, weights = keys[1:], weights[1:]

        def body(carry, xs):
            z_c, tot = carry
            k_t, w_t = xs
            z_c, l_t = _vloss(z_c, k_t, w_t, first=False)
            return (z_c, tot + jnp.sum(l_t)), None

        (z, total), _ = jax.lax.scan(body, (z, total), (keys, weights))
        nchains = denom if denom is not None else z0.shape[0] * z0.shape[1]
        return total / nchains

    def _greedy_state(ops, params, keys):
        """One greedy decode per graph slot → the post-decode recurrent
        state (G, V, d) — what a greedy restart re-seeds culled chains
        from."""
        def per_graph(xg, ag, eg, nmg, emg, simg, dvg, k):
            amask = simg.fit_ok if (dvg is not None and simg is not None) \
                else None
            out = step(params, xg, xg, ag, eg, k,
                       first=True, train=False, greedy=True,
                       node_mask=nmg, edge_mask=emg, dev_feats=dvg,
                       action_mask=amask)
            return out.z_next

        return _graph_vmap(
            per_graph, ops, (keys,),
            with_sim=ops.dev_feats is not None and ops.sim is not None,
            with_dev=ops.dev_feats is not None)

    def _pbt(ops, params, pop, z, use_greedy: bool):
        """One full-view PBT transition (culling + exchange + restarts).

        The elite broadcast is written as one-hot masked sums so the
        sharded mirror (same sums per shard tile + psum over "chains") is
        the identical computation at mesh=1×1.
        """
        G, B = pop.temperature.shape
        k_use, k_greedy, k_next = jax.random.split(pop.rng, 3)
        culled, inherit, new_temp, jstar = popmod.pbt_rows(
            population, k_use, pop.best_latency, pop.temperature,
            jnp.arange(G))
        onehot = jnp.arange(B)[None, :] == jstar[:, None]        # (G, B)
        lat_star = jnp.sum(jnp.where(onehot, pop.best_latency, 0.0),
                           axis=1)                               # (G,)
        fine_star = jnp.sum(pop.best_fine * onehot[:, :, None],
                            axis=1)                              # (G, V)
        z_star = jnp.sum(z * onehot[:, :, None, None].astype(z.dtype),
                         axis=1)                                 # (G, V, d)
        if use_greedy:
            gkeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                k_greedy, jnp.arange(G))
            z_src = _greedy_state(ops, params, gkeys)
        else:
            z_src = z_star
        new_z = jnp.where(culled[:, :, None, None], z_src[:, None], z)
        new_pop = pop._replace(
            temperature=new_temp,
            best_latency=jnp.where(inherit, lat_star[:, None],
                                   pop.best_latency),
            best_fine=jnp.where(inherit[:, :, None], fine_star[:, None],
                                pop.best_fine),
            rng=k_next)
        return new_pop, new_z

    return PopulationWindowFns(
        rollout=_rollout_window_pop, loss=_window_loss_pop, greedy=_greedy,
        greedy_state=_greedy_state, pbt=_pbt,
        update_bests=popmod.update_chain_bests)


class DynamicRolloutEngine:
    """The (G, B) window engine with graph data as jit *operands*.

    :class:`RolloutEngine` closes over one fixed graph batch — right for
    ``train_multi``, where the same G graphs ride every episode, but a
    corpus trainer resamples its subset per episode, and closure constants
    would mean one recompile per subset.  This engine takes a
    :class:`GraphOperands` argument per call instead: compilations are
    cached by *shape*, so recompiles are bounded by the number of size
    buckets, not the number of subsets (``shape_keys_seen`` records the
    distinct shapes for the CI bound check).

    Masks always ride along (corpus batches are padded by construction;
    the masked computation on an unpadded batch equals the unmasked one),
    and the fused reward hook scores against the operand ``sim`` tree.
    """

    def __init__(self, step_fn, cfg, *, backend=None, population=None):
        self._step = step_fn
        self._cfg = cfg
        self._backend = backend
        self._fused = backend is not None and backend.jit_fused
        self._fns = None
        self._population = population
        self._pop_fns = None
        self.shape_keys_seen = set()
        # AOT-loaded greedy executables by shape key: decodes served from
        # here never trace (shape_keys_seen stays untouched) — the serving
        # layer preloads them from a persistent cache so a fresh process
        # pays zero compiles for previously-seen bucket shapes.
        self._aot_greedy: dict = {}
        self.aot_hits = 0

    # ------------------------------------------------------------- builders
    def _build(self):
        rollout, loss, greedy = build_window_fns(
            self._step, self._cfg, fused=self._fused, backend=self._backend)
        return (jax.jit(rollout,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(jax.grad(loss, argnums=1),
                        static_argnames=("num_steps", "start_first")),
                jax.jit(greedy))

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
            fns = build_window_fns(self._step, self._cfg, fused=self._fused,
                                   backend=self._backend,
                                   population=self._population)
            self._pop_fns = (
                jax.jit(fns.rollout,
                        static_argnames=("num_steps", "start_first")),
                jax.jit(jax.grad(fns.loss, argnums=1),
                        static_argnames=("num_steps", "start_first")),
                jax.jit(fns.pbt, static_argnames=("use_greedy",)),
                jax.jit(fns.update_bests),
            )
        return self._pop_fns

    def _note(self, ops: GraphOperands) -> None:
        self.shape_keys_seen.add(ops.shape_key())

    # ----------------------------------------------------------- public API
    def rollout_window(self, ops: GraphOperands, params, z, rngs, *,
                       num_steps: int, start_first: bool):
        self._note(ops)
        return self._built[0](ops, params, z, rngs, num_steps=num_steps,
                              start_first=start_first)

    def window_grads(self, ops: GraphOperands, params, z0, keys, weights, *,
                     num_steps: int, start_first: bool):
        self._note(ops)
        return self._built[1](ops, params, z0, keys, weights,
                              num_steps=num_steps, start_first=start_first)

    def greedy_decode(self, ops: GraphOperands, params, keys):
        aot = self._aot_greedy.get(ops.shape_key())
        if aot is not None:
            self.aot_hits += 1
            return aot(ops, params, keys)
        self._note(ops)
        return self._built[2](ops, params, keys)

    # ------------------------------------------------------- population API
    # Separate jitted functions, separate methods: the base path above
    # never sees a population operand, so population=None callers exercise
    # byte-identical traces to the population-free build.
    @property
    def population(self):
        return self._population

    def init_population(self, key, *, num_graphs: int, num_chains: int,
                        num_nodes: int, temperatures=None):
        from ..train import population as popmod
        return popmod.init_chain_state(
            self._population, key, num_graphs=num_graphs,
            num_chains=num_chains, num_nodes=num_nodes,
            temperatures=temperatures)

    def rollout_window_pop(self, ops: GraphOperands, params, z, rngs, pop, *,
                           num_steps: int, start_first: bool):
        """Population rollout: ``pop.temperature`` scales every sample; →
        ``(z, rngs, pop, keys, fine, ngroups, rewards, latencies)`` with the
        chain bests already folded when rewards are fused."""
        self._note(ops)
        return self._pop_built[0](ops, params, z, rngs, pop,
                                  num_steps=num_steps,
                                  start_first=start_first)

    def window_grads_pop(self, ops: GraphOperands, params, z0, keys, weights,
                         temps, *, num_steps: int, start_first: bool):
        """Eq.-14 replay gradient at the sampling pass's temperatures."""
        self._note(ops)
        return self._pop_built[1](ops, params, z0, keys, weights, temps,
                                  num_steps=num_steps,
                                  start_first=start_first)

    def pbt_step(self, ops: GraphOperands, params, pop, z, *,
                 use_greedy: bool = False):
        """One in-jit PBT transition (cull + exchange [+ greedy restart])."""
        self._note(ops)
        return self._pop_built[2](ops, params, pop, z, use_greedy=use_greedy)

    def update_population(self, pop, fines, latencies):
        """Fold a window's (T, G, B, V) fines / (T, G, B) latencies into the
        chain-best records — the host-scored mirror of the fused in-jit
        update."""
        return self._pop_built[3](pop, fines, latencies)

    # ------------------------------------------------------------ AOT export
    def export_greedy(self, ops: GraphOperands, params, keys) -> bytes:
        """Serialize the greedy decode at ``ops``'s shapes via ``jax.export``.

        The returned blob is the lowered StableHLO module plus the call
        signature: a fresh process :meth:`preload_greedy`-s it and serves
        this shape without ever tracing the policy step (the dominant cost
        of a cold decode).  Parameter *values* are call-time operands, so
        one export survives policy updates; only shape changes invalidate.
        """
        return jax_export.export(self._built[2])(ops, params, keys) \
            .serialize()

    def preload_greedy(self, blob: bytes) -> Tuple:
        """Install a serialized greedy decode; → its operand shape key.

        Subsequent :meth:`greedy_decode` calls at that shape run the
        deserialized executable (counted in ``aot_hits``) instead of
        tracing.  The shape key is recovered from the export's own input
        signature, so the caller needs no side channel.
        """
        exported = jax_export.deserialize(bytes(blob))
        if jax.default_backend() not in exported.platforms:
            raise ValueError(
                f"executable was exported for {exported.platforms}; this "
                f"process runs on {jax.default_backend()!r}")
        args, _ = exported.in_tree.unflatten(list(exported.in_avals))
        key = tuple((tuple(a.shape), str(a.dtype))
                    for a in jax.tree.leaves(args[0]))
        self._aot_greedy[key] = exported.call
        return key
