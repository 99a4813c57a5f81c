"""HSDAG — the paper's five-step framework, end-to-end (§2, Fig. 1, Alg. 1).

Usage::

    graph  = inception_v3()                       # step 1: graph construction
    arrays = extract_features(graph)              # step 2: features (§2.3)
    agent  = HSDAG(HSDAGConfig(num_devices=2, batch_chains=16))
    result = agent.search(graph, arrays, platform=paper_platform())

Reward sources (one ``core/sim`` RewardPipeline behind both):

* ``platform=`` (preferred) — rewards come from a registered simulator
  backend: ``engine="scan"`` (default) fuses ``simulate_jax`` *inside* the
  jitted rollout, so a whole ``update_timestep`` window of ``batch_chains``
  parallel REINFORCE chains runs device-resident with no host↔device sync;
  the window's placements are scored together after its step scan, in one
  simulator walk.  ``engine="level"`` scores each window in one batched
  call of the level-parallel Pallas kernel; ``engine="reference"`` scores
  on the host with the ground-truth Python scheduler.
* ``reward_fn(fine_placement) -> (reward, latency)`` — any host callable
  (e.g. ``MeasuredExecutor``, the paper's OpenVINO measurement slot).  The
  rollout is still batched; rewards are filled in on the host per window.

Training is exact REINFORCE via *replayed rollouts*: the sampling pass records
PRNG keys and rewards; the gradient pass re-runs the identical rollout
differentiably (a ``lax.scan`` over the window) with rewards as constants, so
∇θ J matches Eq. 14 including gradients through the GPN's straight-through
pooling gates.  All rollout machinery lives in ``core/sim/rollout.py`` —
ONE parameterized (G, B)-chain engine drives ``search``, the batched search
and ``train_multi``; ``engine="scalar"`` keeps the original
one-placement-at-a-time reference loop (used by the B=1 equivalence tests).
The episode loop itself (rollout → score → track → update) lives in
``core/train/loop.py``, shared with the corpus
:class:`~repro.core.train.CurriculumTrainer`; ``train_multi`` is a thin
wrapper over it.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..optim import adam, apply_updates
from .costmodel import Platform, simulate
from .features import (FeatureConfig, GraphArrays, GraphArraysBatch,
                       batch_graph_arrays, extract_features,
                       shared_feature_config)
from .gnn import encoder_apply, encoder_init, mlp_apply, mlp_init
from .gpn import ParseResult, gpn_apply, gpn_init
from .graph import CompGraph
from .policy import PolicyOutput, policy_apply, policy_init
from .reinforce import RolloutBuffer, RunningBaseline, step_weights
from .sim import RewardPipeline, RolloutEngine, backend_names, get_backend
from .train.loop import BestTracker, EpisodeRunner, WindowStream
from .train.population import PopulationConfig, PopulationController

__all__ = ["HSDAGConfig", "HSDAG", "SearchResult",
           "MultiGraphTrainer", "MultiSearchResult"]

#: rollout-loop selectors accepted by ``engine=`` on top of the registered
#: simulator-backend names (which imply the batched loop + that backend).
_LOOP_ENGINES = ("auto", "scalar", "batched")


def _validate_engine(engine: str) -> str:
    if engine in _LOOP_ENGINES or engine in backend_names():
        return engine
    raise ValueError(
        f"unknown engine {engine!r}; rollout loops: {_LOOP_ENGINES}; "
        f"registered simulator backends: {backend_names()}")


def _as_population(population) -> PopulationConfig:
    """Accept a :class:`PopulationConfig` or its JSON (dict/str) form."""
    if isinstance(population, PopulationConfig):
        return population
    if isinstance(population, (dict, str)):
        return PopulationConfig.from_json(population)
    raise TypeError(
        f"population must be a PopulationConfig or its JSON form, "
        f"got {type(population).__name__}")


@dataclasses.dataclass(frozen=True)
class HSDAGConfig:
    """Appendix H, Table 6 defaults."""

    num_devices: int = 2
    hidden_channel: int = 128
    layer_trans: int = 2
    layer_gnn: int = 2
    layer_parsingnet: int = 2
    gnn_model: str = "gcn"
    dropout_network: float = 0.2
    dropout_parsing: float = 0.0
    link_ignore_self_loop: bool = True   # S is masked by A (no self loops)
    activation_final: bool = True
    learning_rate: float = 1e-4
    max_episodes: int = 100
    update_timestep: int = 20
    k_epochs: int = 1            # 1 = exact Eq. 14 replay (paper value unlisted)
    gamma: float = 0.99          # discount (paper value unlisted)
    # --- beyond-paper, opt-in (EXPERIMENTS.md §Perf notes usage) ---
    entropy_coef: float = 0.0
    reward_to_go: bool = False
    use_baseline: bool = False
    normalize_weights: bool = False
    state_norm: bool = True      # RMS-normalize the recurrent state Z between
    # rounds; pure numerical stabilizer for the Alg.1 line-10 accumulation
    # (sum-pooling grows ‖Z‖ geometrically over 20 rounds otherwise).
    seed: int = 0
    # Number of parallel REINFORCE chains per rollout window.  Chain 0 uses
    # the exact PRNG stream of the scalar engine, so B=1 reproduces it.
    batch_chains: int = 1
    # Rollout engine: "auto" | "scalar" | "batched" pick the loop (batched
    # defaults to the fused "scan" simulator backend); a registered backend
    # name ("reference" | "scan" | "level" | any plug-in) picks the batched
    # loop with that reward backend.  Validated against the registry at
    # construction; recorded in policy checkpoints.
    engine: str = "auto"
    # Policy head: "dense" (the paper's fixed Dense(num_devices) layer,
    # bit-for-bit pinned) or "device" (node × device-embedding compatibility
    # scores conditioned on the platform's feature table — one policy for
    # any fleet size, with per-device capacity masking at sample time).
    # "device" requires a platform= reward source; see repro.platforms.
    head: str = "dense"

    def __post_init__(self):
        _validate_engine(self.engine)
        if self.head not in ("dense", "device"):
            raise ValueError(f"unknown head {self.head!r}; "
                             f"expected 'dense' or 'device'")

    # ----------------------------------------------------------- (de)serialize
    def to_json(self) -> str:
        """Canonical JSON form (sorted keys) — ``from_json`` round-trips it.

        The serialization is what :class:`repro.api.PlacementSpec` embeds
        (and hashes) to name a run, so it must be deterministic: same
        config → same string → same spec hash.
        """
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, doc: Union[str, Dict]) -> "HSDAGConfig":
        """Inverse of :meth:`to_json` (also accepts the dict form).

        Unknown fields are rejected by name — a typo'd knob in a spec
        document must fail loudly, not silently train with defaults.  Field
        values pass through ``__post_init__``, so e.g. an unregistered
        ``engine`` raises listing the registered backends.
        """
        data = json.loads(doc) if isinstance(doc, str) else dict(doc)
        if not isinstance(data, dict):
            raise ValueError(
                f"HSDAGConfig JSON must be an object, got {type(data).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(data) - known)
        if unknown:
            raise ValueError(
                f"unknown HSDAGConfig fields {unknown}; known fields: "
                f"{sorted(known)}")
        return cls(**data)


class StepOutput(NamedTuple):
    policy: PolicyOutput
    parse: ParseResult
    z_next: jnp.ndarray


class SearchResult(NamedTuple):
    best_placement: np.ndarray
    best_latency: float
    history: List[dict]          # per-episode stats
    params: Dict
    baseline_latencies: Dict[str, float]
    wall_time_s: float
    num_evaluations: int = 0     # placements scored during the search
    evals_per_sec: float = 0.0   # rollout throughput (placements / wall-s)
    chain_best: Optional[np.ndarray] = None   # (B,) per-chain best latency


class MultiSearchResult(NamedTuple):
    """Outcome of one joint cross-graph training run (``train_multi``)."""

    best_placements: List[np.ndarray]   # per graph: best sampled, (V_g,) i64
    best_latencies: np.ndarray          # (G,) seconds
    greedy_placements: List[np.ndarray]  # per graph: greedy decode after train
    greedy_latencies: np.ndarray        # (G,) seconds
    history: List[dict]                 # per-episode stats
    params: Dict                        # the one shared policy/GNN/GPN tree
    wall_time_s: float
    num_evaluations: int                # placements scored (episodes·T·G·B)
    evals_per_sec: float
    chain_best: Optional[np.ndarray] = None   # (G, B) per-chain best latency


def _dev_feature_dim() -> int:
    # Local import: core must stay importable without the platforms package
    # loaded (platforms itself imports core.costmodel).
    from ..platforms.topology import DEV_FEATURE_DIM
    return DEV_FEATURE_DIM


def _rms_normalize(z: jnp.ndarray, node_mask=None) -> jnp.ndarray:
    if node_mask is None:
        rms = jnp.sqrt(jnp.mean(jnp.square(z)) + 1e-6)
        return z / rms
    # Padded batch: the mean-square runs over real rows only, otherwise the
    # pad fraction (which varies per graph) would rescale real activations.
    m = node_mask.astype(z.dtype)[:, None]
    mean_sq = jnp.sum(jnp.square(z) * m) / (jnp.sum(m) * z.shape[1])
    return z / jnp.sqrt(mean_sq + 1e-6)


class HSDAG:
    """The framework object: owns params, jitted rollout/update functions."""

    def __init__(self, cfg: HSDAGConfig = HSDAGConfig()):
        self.cfg = cfg
        self.params: Optional[Dict] = None
        self._opt = adam(cfg.learning_rate)
        self._opt_state = None
        # Set by train_multi(); the config held-out graphs must be featurized
        # with so the shared policy sees a consistent feature layout.
        self.feature_config: Optional[FeatureConfig] = None
        # head="device": the (D, F_dev) fleet feature table the policy is
        # conditioned on.  Set by bind_platform() (search/train call it from
        # their platform argument); place() decodes with the bound fleet.
        self._dev_feats: Optional[np.ndarray] = None

    def bind_platform(self, platform: Platform) -> None:
        """Condition the ``head="device"`` policy on ``platform``'s fleet.

        Computes and stores the device feature table the compatibility head
        scores against.  A no-op for ``head="dense"``.  ``search`` /
        ``train_multi`` / ``train_corpus`` call this from their
        ``platform=``; restored sessions must call it before ``place``.
        """
        if self.cfg.head != "device":
            return
        from ..platforms.topology import device_feature_table
        self._dev_feats = device_feature_table(platform)

    # ------------------------------------------------------------------ init
    def init(self, rng, arrays: GraphArrays) -> Dict:
        cfg = self.cfg
        k_enc, k_gpn, k_pol = jax.random.split(rng, 3)
        d_in = arrays.x.shape[1]
        params = {
            "enc": encoder_init(k_enc, d_in, cfg.hidden_channel,
                                layer_trans=cfg.layer_trans,
                                layer_gnn=cfg.layer_gnn,
                                gnn_model=cfg.gnn_model),
            "gpn": gpn_init(k_gpn, cfg.hidden_channel,
                            layer_parsingnet=cfg.layer_parsingnet),
            "pol": (policy_init(k_pol, cfg.hidden_channel, cfg.num_devices)
                    if cfg.head == "dense" else
                    policy_init(k_pol, cfg.hidden_channel, cfg.num_devices,
                                head="device",
                                dev_feat_dim=_dev_feature_dim())),
        }
        self.params = params
        self._opt_state = self._opt.init(params)
        return params

    def apply_grads(self, grads: Dict) -> None:
        """One optimizer step on the shared tree (the Eq.-14 update), in the
        ``repro.optim.update`` host span."""
        with jax.profiler.TraceAnnotation("repro.optim.update"):
            updates, self._opt_state = self._opt.update(
                grads, self._opt_state, self.params)
            self.params = apply_updates(self.params, updates)

    # ------------------------------------------------------------- one round
    def _step(self, params: Dict, z: jnp.ndarray, x0: jnp.ndarray,
              adj: jnp.ndarray, edges: jnp.ndarray, rng, *,
              first: bool, train: bool, greedy: bool = False,
              node_mask=None, edge_mask=None,
              temperature=None, dev_feats=None,
              action_mask=None) -> StepOutput:
        """One Alg.-1 iteration: encode → parse → place → state update.

        ``node_mask``/``edge_mask`` (``None`` for single-graph use) thread the
        padded multi-graph batch contract through the encoder, the GPN and the
        state update; the masked computation on an unpadded graph is the
        unmasked one.  ``temperature`` (``None`` = off, a trace-time branch)
        is the per-chain sampling temperature population search threads into
        the policy head.  ``dev_feats`` (the (D, F_dev) fleet table) selects
        the device-compatibility head; ``action_mask`` ((V, D) capacity
        feasibility, ``SimArrays.fit_ok``) masks impossible devices.  All
        ``None`` defaults are trace-time branches — the dense jaxpr is
        unchanged.
        """
        cfg = self.cfg
        k_net, k_parse, k_pol = jax.random.split(rng, 3)
        feats = x0 if first else z
        z_enc = encoder_apply(
            params["enc"], feats, adj, transform=first,
            dropout_rng=k_net if train else None,
            edge_dropout=cfg.dropout_network if train else 0.0,
            node_mask=node_mask)
        parse = gpn_apply(
            params["gpn"], z_enc, edges, adj,
            dropout_rng=k_parse if train else None,
            dropout_parsing=cfg.dropout_parsing if train else 0.0,
            node_mask=node_mask, edge_mask=edge_mask)
        pol = policy_apply(params["pol"], parse.pooled_z, parse.active,
                           parse.labels, k_pol, greedy=greedy,
                           temperature=temperature, dev_feats=dev_feats,
                           action_mask=action_mask)
        # Alg. 1 line 10: Z_v ← Z_v + Z_{v'}.
        z_next = z_enc + parse.pooled_z[parse.labels]
        if cfg.state_norm:
            z_next = _rms_normalize(z_next, node_mask)
        return StepOutput(pol, parse, z_next)

    # ----------------------------------------------------- engine construction
    def _engine_single(self, arrays: GraphArrays,
                       pipeline: Optional[RewardPipeline],
                       population=None) -> RolloutEngine:
        """The unified (G, B) engine over a single graph (G=1).

        A G=1 batch normally needs no padding, so masks drop at trace time
        and the computation is exactly the unmasked single-graph one.  The
        exception is an edge-free graph: ``batch_graph_arrays`` pads the
        edge table to one (masked) slot, and the masks must ride along or
        the phantom edge would enter the GPN unmasked.
        """
        return self._engine_multi(batch_graph_arrays([arrays]), pipeline,
                                  population)

    def _engine_multi(self, gb: GraphArraysBatch,
                      pipeline: Optional[RewardPipeline],
                      population=None) -> RolloutEngine:
        """The same engine over a padded multi-graph batch."""
        use_masks = gb.padded
        dev_feats = None
        if self.cfg.head == "device":
            if self._dev_feats is None:
                raise ValueError(
                    "head='device' needs a bound platform (its device "
                    "feature table conditions the policy); call "
                    "bind_platform(platform) or pass platform= to "
                    "search/train")
            dev_feats = jnp.asarray(self._dev_feats)
        return RolloutEngine(
            self._step, self.cfg, x0=gb.x, adj=gb.adj, edges=gb.edges,
            node_mask=gb.node_mask if use_masks else None,
            edge_mask=gb.edge_mask if use_masks else None,
            pipeline=pipeline, population=population, dev_feats=dev_feats)

    # ---------------------------------------------------------------- search
    def search(self, graph: CompGraph, arrays: GraphArrays,
               reward_fn: Optional[Callable[[np.ndarray],
                                            Tuple[float, float]]] = None,
               rng=None, verbose: bool = False, *,
               platform: Optional[Platform] = None,
               engine: Optional[str] = None,
               population: Optional[PopulationConfig] = None) -> SearchResult:
        """Run the full RL search (Alg. 1) and return the best placement.

        Reward source: ``platform`` (a registered simulator backend — the
        fused ``scan`` kernel by default) or ``reward_fn`` (host callable;
        batched rollout, host rewards).  ``engine`` overrides
        ``cfg.engine``: ``"auto"`` picks batched unless ``batch_chains == 1``
        with a host ``reward_fn`` (the original scalar loop, kept as the
        reference implementation); ``"batched"``/``"scalar"`` force a loop;
        a backend name ("reference"/"scan"/"level"/plug-ins) forces the
        batched loop with that reward backend.

        ``population`` (a :class:`~repro.core.train.PopulationConfig` or its
        dict form) turns the B chains into a PBT-style population: every
        chain samples at its own temperature, and every ``cull_every``
        windows the worst chains are re-seeded from the elites (optionally
        from a greedy decode) with perturbed temperatures.  ``None`` (the
        default) leaves the engine bit-for-bit identical to the plain
        batched loop.
        """
        cfg = self.cfg
        engine = _validate_engine(engine if engine is not None
                                  else cfg.engine)
        if platform is None and reward_fn is None:
            raise ValueError("search() needs a reward source: platform= or "
                             "reward_fn")
        if platform is not None and reward_fn is not None:
            raise ValueError(
                "search() got both platform= and reward_fn — ambiguous "
                "reward source (the in-jit cost model would silently shadow "
                "the callable); pass exactly one")
        if platform is not None and cfg.num_devices > platform.num_devices:
            # jnp gathers inside the simulator kernels would silently clip
            # policy device ids ≥ platform.num_devices; fail loudly up front.
            raise ValueError(
                f"cfg.num_devices={cfg.num_devices} exceeds the platform's "
                f"{platform.num_devices} devices")
        if cfg.head == "device":
            if platform is None:
                raise ValueError(
                    "head='device' conditions the policy on a platform's "
                    "device feature table; a bare reward_fn carries no "
                    "fleet description — pass platform=")
            if engine == "scalar":
                raise ValueError(
                    "head='device' needs the batched engine (the scalar "
                    "reference loop predates device conditioning)")
            self.bind_platform(platform)
        if engine not in _LOOP_ENGINES and reward_fn is not None:
            raise ValueError(
                f"engine={engine!r} names a simulator backend but a host "
                f"reward_fn was also given — pass exactly one reward source")
        if population is not None:
            population = _as_population(population)
            if engine == "scalar" or (engine == "auto"
                                      and cfg.batch_chains == 1
                                      and platform is None):
                raise ValueError(
                    "population search needs the batched multi-chain loop; "
                    "engine='scalar' (and the batch_chains==1 auto-scalar "
                    "path) has no chain population")
        if engine == "scalar":
            if cfg.batch_chains != 1:
                raise ValueError("engine='scalar' requires batch_chains == 1")
            if reward_fn is None:
                def reward_fn(p, _g=graph, _plat=platform):
                    r = simulate(_g, p, _plat)
                    return r.reward, r.latency
            return self._search_scalar(arrays, reward_fn, rng, verbose)
        if engine == "auto" and cfg.batch_chains == 1 and platform is None:
            return self._search_scalar(arrays, reward_fn, rng, verbose)
        if reward_fn is not None:
            pipeline = RewardPipeline.from_reward_fn(
                reward_fn, num_nodes=graph.num_nodes)
        else:
            backend = engine if engine not in _LOOP_ENGINES else "scan"
            pipeline = RewardPipeline.from_platform(graph, platform, backend)
        return self._search_batched(arrays, pipeline, rng, verbose,
                                    population=population)

    # ------------------------------------------------- scalar reference loop
    def _search_scalar(self, arrays: GraphArrays, reward_fn,
                       rng, verbose: bool) -> SearchResult:
        cfg = self.cfg
        t_start = time.perf_counter()
        rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
        if self.params is None:
            rng, k_init = jax.random.split(rng)
            self.init(k_init, arrays)

        engine = self._engine_single(arrays, pipeline=None)
        baseline = RunningBaseline() if cfg.use_baseline else None
        buffer = RolloutBuffer()

        best_latency = float("inf")
        best_placement = np.zeros(arrays.num_nodes, dtype=np.int64)
        history: List[dict] = []

        x0 = jnp.asarray(arrays.x)
        z = x0  # replaced on the first (transforming) step
        z0_window = z
        first_of_window = True
        step_in_episode = 0

        for episode in range(cfg.max_episodes):
            t_ep = time.perf_counter()
            ep_rewards: List[float] = []
            ep_groups: List[int] = []
            for _ in range(cfg.update_timestep):
                rng, k_step = jax.random.split(rng)
                first = step_in_episode == 0
                fine, coarse, ngroups, z_next = engine.rollout_step(
                    self.params, z, k_step, first=first)
                fine_np = np.asarray(fine)
                reward, latency = reward_fn(fine_np)
                if baseline is not None:
                    baseline.update(reward)
                buffer.add(k_step, reward, fine_np, latency)
                ep_rewards.append(reward)
                ep_groups.append(int(ngroups))
                if latency < best_latency:
                    best_latency = float(latency)
                    best_placement = fine_np.copy()
                z = z_next
                step_in_episode += 1

            # ---- policy update over the buffer window (Eq. 14) ----
            weights = step_weights(
                np.asarray(buffer.rewards), cfg.gamma,
                reward_to_go=cfg.reward_to_go,
                baseline=(baseline.value if baseline is not None else None),
                normalize=cfg.normalize_weights)
            rngs = jnp.stack(buffer.rngs)
            for _ in range(max(1, cfg.k_epochs)):
                grads = engine.window_grads_scalar(
                    self.params, z0_window, rngs, jnp.asarray(weights),
                    num_steps=len(buffer), start_first=first_of_window)
                self.apply_grads(grads)
            buffer.clear()
            # next window starts from the current state
            z0_window = z
            first_of_window = False
            history.append({
                "episode": episode,
                "mean_reward": float(np.mean(ep_rewards)),
                "best_latency": best_latency,
                "mean_groups": float(np.mean(ep_groups)),
                "wall_s": time.perf_counter() - t_ep,
            })
            if verbose:
                h = history[-1]
                print(f"ep {episode:3d} reward {h['mean_reward']:.4g} "
                      f"best {best_latency:.6f}s groups {h['mean_groups']:.1f}")

        jax.block_until_ready(self.params)     # the last update is in wall
        wall = time.perf_counter() - t_start
        n_evals = cfg.max_episodes * cfg.update_timestep
        return SearchResult(best_placement, best_latency, history,
                            self.params, {}, wall, n_evals,
                            n_evals / max(wall, 1e-9))

    # ------------------------------------------------ batched multi-chain loop
    def _search_batched(self, arrays: GraphArrays,
                        pipeline: RewardPipeline,
                        rng, verbose: bool,
                        population: Optional[PopulationConfig] = None
                        ) -> SearchResult:
        """B parallel chains through the unified (G, B) engine at G=1."""
        cfg = self.cfg
        nchains = max(1, cfg.batch_chains)
        t_start = time.perf_counter()
        rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
        if self.params is None:
            rng, k_init = jax.random.split(rng)
            self.init(k_init, arrays)

        engine = self._engine_single(arrays, pipeline, population)
        baseline = RunningBaseline() if cfg.use_baseline else None

        # Population search: per-chain temperatures + in-jit PBT transitions.
        # The key is fold_in-derived so the chain PRNG streams below are
        # untouched — population=None stays bit-for-bit the plain loop.
        controller = pop = None
        if population is not None:
            controller = PopulationController(population, num_chains=nchains,
                                              in_jit_pbt=True)
            pop = engine.init_population(jax.random.fold_in(rng, 0x706F70),
                                         num_chains=nchains)

        best_latency = float("inf")
        best_placement = np.zeros(arrays.num_nodes, dtype=np.int64)
        chain_best = np.full(nchains, np.inf)
        history: List[dict] = []

        # Chain 0 carries the exact scalar-engine PRNG stream; chains ≥ 1 get
        # independent folded streams, so B=1 reproduces the scalar trajectory.
        chain_rngs = jnp.stack(
            [rng] + [jax.random.fold_in(rng, b)
                     for b in range(1, nchains)])[None]       # (1, B, 2)
        x0 = jnp.asarray(arrays.x)
        z = jnp.broadcast_to(x0, (1, nchains) + x0.shape)
        z0_window = z
        first_of_window = True
        tsteps = cfg.update_timestep

        for episode in range(cfg.max_episodes):
            t_ep = time.perf_counter()
            if pop is not None:
                (z, chain_rngs, pop, keys, fines, ngroups, rewards,
                 latencies) = engine.rollout_window_pop(
                    self.params, z0_window, chain_rngs, pop,
                    num_steps=tsteps, start_first=first_of_window)
            else:
                (z, chain_rngs, keys, fines, ngroups, rewards,
                 latencies) = engine.rollout_window(
                    self.params, z0_window, chain_rngs,
                    num_steps=tsteps, start_first=first_of_window)
            sample_temps = pop.temperature if pop is not None else None
            fines_np = np.asarray(fines)[:, 0]                # (T, B, V)
            if pipeline.fused:
                rewards = np.asarray(rewards, dtype=np.float64)[:, 0]
                latencies = np.asarray(latencies, dtype=np.float64)[:, 0]
            else:
                # Window scoring: host reward_fn loop, or one batched device
                # call for jit_window backends (the level kernel).
                rewards, latencies = pipeline.score_window(fines_np)
                if pop is not None:
                    pop = engine.update_population(
                        pop, fines,
                        jnp.asarray(latencies, jnp.float32)[:, None, :])

            # Bookkeeping in (t, b) order — identical to the scalar loop at
            # B=1 (EMA baseline order and strict-< best tie-breaks matter).
            for t in range(tsteps):
                for b in range(nchains):
                    if baseline is not None:
                        baseline.update(rewards[t, b])
                    if latencies[t, b] < best_latency:
                        best_latency = float(latencies[t, b])
                        best_placement = fines_np[t, b].astype(np.int64)
            chain_best = np.minimum(chain_best, latencies.min(axis=0))

            # ---- policy update over the (B, T) window (Eq. 14) ----
            weights_bt = step_weights(
                rewards.T, cfg.gamma,
                reward_to_go=cfg.reward_to_go,
                baseline=(baseline.value if baseline is not None else None),
                normalize=cfg.normalize_weights)
            weights_tgb = jnp.asarray(weights_bt.T)[:, None]  # (T, 1, B)
            for _ in range(max(1, cfg.k_epochs)):
                if pop is not None:
                    grads = engine.window_grads_pop(
                        self.params, z0_window, keys, weights_tgb,
                        sample_temps, num_steps=tsteps,
                        start_first=first_of_window)
                else:
                    grads = engine.window_grads(
                        self.params, z0_window, keys, weights_tgb,
                        num_steps=tsteps, start_first=first_of_window)
                self.apply_grads(grads)
            pop_stats: Dict = {}
            if controller is not None:
                # PBT runs AFTER the replay update (the gradient must see the
                # temperatures this window actually sampled at); re-seeded
                # chain states and new temperatures take effect next window.
                due, use_greedy = controller.note_window()
                if due:
                    pop, z = engine.pbt_step(self.params, pop, z,
                                             use_greedy=use_greedy)
                pop_stats = {
                    "culled": bool(due),
                    "pop_best_latency": float(
                        np.min(np.asarray(pop.best_latency))),
                    "temp_mean": float(np.mean(np.asarray(pop.temperature))),
                }
            z0_window = z
            first_of_window = False
            history.append({
                "episode": episode,
                "mean_reward": float(np.mean(rewards)),
                "best_latency": best_latency,
                "mean_groups": float(np.mean(np.asarray(ngroups))),
                "wall_s": time.perf_counter() - t_ep,
                **pop_stats,
            })
            if verbose:
                h = history[-1]
                print(f"ep {episode:3d} reward {h['mean_reward']:.4g} "
                      f"best {best_latency:.6f}s groups {h['mean_groups']:.1f}"
                      f" chains {nchains}")

        jax.block_until_ready(self.params)     # the last update is in wall
        wall = time.perf_counter() - t_start
        n_evals = cfg.max_episodes * tsteps * nchains
        return SearchResult(best_placement, best_latency, history,
                            self.params, {}, wall, n_evals,
                            n_evals / max(wall, 1e-9), chain_best)

    def train_multi(self, graphs: List[CompGraph],
                    arrays: Optional[List[GraphArrays]] = None, *,
                    platform: Platform,
                    rng=None, verbose: bool = False,
                    feature_cfg: Optional[FeatureConfig] = None,
                    reward_norm: str = "pergraph",
                    population: Optional[PopulationConfig] = None
                    ) -> MultiSearchResult:
        """Train ONE policy jointly over ``graphs`` (GDP/Placeto-style).

        Runs ``(G, batch_chains)`` REINFORCE chains in a single jitted
        window rollout per episode — every chain's rewards come from the
        padded in-jit cost model (``simulate_jax`` over the stacked
        :class:`SimArraysBatch`), and one shared parameter tree receives the
        averaged Eq.-14 gradient.  Example::

            graphs = [inception_v3(), resnet50()]
            trainer = MultiGraphTrainer(HSDAGConfig(batch_chains=8))
            res = trainer.train(graphs, platform=paper_platform(),
                                rng=jax.random.PRNGKey(0))
            bert_lat = trainer.evaluate_zero_shot(  # held-out transfer
                bert_base(), platform=paper_platform())[1]

        ``reward_norm="pergraph"`` standardizes each graph's rewards within
        the update window so graphs with very different latency scales (BERT
        at ~60 ms vs Inception at ~9 ms) contribute comparably scaled
        gradients; it subsumes ``cfg.use_baseline`` (the standardization is
        itself a per-graph baseline, so the raw-scale scalar EMA is not also
        subtracted).  ``"none"`` keeps raw 1/latency rewards and the scalar
        baseline (with G=1 this reproduces the single-graph batched engine
        bit for bit).

        When ``arrays`` is omitted, features are extracted with a
        :func:`shared_feature_config` spanning all graphs (stored on
        ``self.feature_config`` — held-out graphs must reuse it).
        """
        cfg = self.cfg
        if not graphs:
            raise ValueError("train_multi needs at least one graph")
        if reward_norm not in ("none", "pergraph"):
            raise ValueError(f"unknown reward_norm {reward_norm!r}")
        if population is not None:
            population = _as_population(population)
        if cfg.num_devices > platform.num_devices:
            raise ValueError(
                f"cfg.num_devices={cfg.num_devices} exceeds the platform's "
                f"{platform.num_devices} devices")
        self.bind_platform(platform)
        G = len(graphs)
        nchains = max(1, cfg.batch_chains)
        t_start = time.perf_counter()

        if arrays is None:
            fc = feature_cfg or shared_feature_config(graphs)
            self.feature_config = fc
            arrays = [extract_features(g, fc) for g in graphs]
        elif feature_cfg is not None:
            self.feature_config = feature_cfg
        gb = batch_graph_arrays(arrays)
        # cfg.engine names the reward backend; "auto"/"batched" mean the
        # fused default.  "scalar" explicitly requests the reference loop,
        # which has no multi-graph form — reject rather than silently train
        # (and checkpoint) under a different engine.
        if cfg.engine == "scalar":
            raise ValueError(
                "train_multi has no scalar loop; use engine='auto' or a "
                f"simulator backend name {backend_names()}")
        backend = (cfg.engine if cfg.engine not in _LOOP_ENGINES else "scan")
        pipeline = RewardPipeline.from_graphs(graphs, platform,
                                              backend=backend,
                                              v_max=gb.max_nodes)

        rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
        if self.params is None:
            rng, k_init = jax.random.split(rng)
            self.init(k_init, arrays[0])

        engine = self._engine_multi(gb, pipeline, population)
        # The per-graph standardization below already centers rewards (it IS
        # a per-graph baseline); layering the scalar EMA baseline on top
        # would subtract a raw-reward-scale value (~1/latency) from ~N(0, 1)
        # standardized rewards and swamp the learning signal.
        baseline = (RunningBaseline()
                    if cfg.use_baseline and reward_norm != "pergraph"
                    else None)

        # The episode loop itself lives in ``core/train/loop.py`` now — ONE
        # runner shared with the corpus trainer.  The stream's PRNG layout
        # (graph 0 / chain 0 = the single-graph batched stream) keeps G=1
        # with reward_norm="none" bit-for-bit the single-graph engine.
        num_nodes = [int(n) for n in gb.num_nodes]
        tracker = BestTracker(num_nodes, nchains)
        controller = pop0 = None
        if population is not None:
            controller = PopulationController(population, num_chains=nchains,
                                              in_jit_pbt=True)
            pop0 = engine.init_population(jax.random.fold_in(rng, 0x706F70),
                                          num_chains=nchains)
        runner = EpisodeRunner(self, engine, pipeline=pipeline,
                               tracker=tracker, reward_norm=reward_norm,
                               baseline=baseline, controller=controller)
        stream = WindowStream.fresh(rng, gb.x, nchains, pop=pop0)
        history: List[dict] = []
        tsteps = cfg.update_timestep

        for episode in range(cfg.max_episodes):
            stats = runner.run_episode(stream)
            history.append({"episode": episode, **stats})
            if verbose:
                h = history[-1]
                per_g = "/".join(f"{l*1e3:.2f}" for l in h["per_graph_best"])
                print(f"ep {episode:3d} reward {h['mean_reward']:.4g} "
                      f"best[ms] {per_g} groups {h['mean_groups']:.1f} "
                      f"G={G} B={nchains}")

        # Per-graph greedy decodes with the final shared policy.
        greedy_placements: List[np.ndarray] = []
        greedy_latencies = np.empty(G)
        for g in range(G):
            p = self.place(arrays[g], greedy=True).astype(np.int64)
            greedy_placements.append(p)
            greedy_latencies[g] = simulate(graphs[g], p, platform).latency

        jax.block_until_ready(self.params)     # the last update is in wall
        wall = time.perf_counter() - t_start
        n_evals = cfg.max_episodes * tsteps * G * nchains
        return MultiSearchResult(
            tracker.best_placements, tracker.best_latencies,
            greedy_placements, greedy_latencies, history, self.params, wall,
            n_evals, n_evals / max(wall, 1e-9), tracker.chain_best)

    # ------------------------------------------------------------- inference
    def place(self, arrays: GraphArrays, rng=None,
              greedy: bool = True) -> np.ndarray:
        """One greedy forward placement with the current policy."""
        assert self.params is not None, "call init()/search() first"
        engine = self._engine_single(arrays, pipeline=None)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        fine, _, _, _ = engine.rollout_step(
            self.params, jnp.asarray(arrays.x), rng, first=True,
            greedy=greedy)
        return np.asarray(fine)


class MultiGraphTrainer(HSDAG):
    """Cross-graph trainer: one policy over a padded multi-graph batch.

    A thin facade over :meth:`HSDAG.train_multi` that pins the reward
    normalization, remembers the shared feature layout for held-out graphs,
    and adds zero-shot evaluation plus checkpointing of the shared policy::

        trainer = MultiGraphTrainer(HSDAGConfig(batch_chains=8))
        res = trainer.train([inception_v3(), resnet50()],
                            platform=paper_platform())
        placement, latency = trainer.evaluate_zero_shot(
            bert_base(), platform=paper_platform())
        trainer.save_policy("ckpt/joint")
    """

    def __init__(self, cfg: HSDAGConfig = HSDAGConfig(), *,
                 reward_norm: str = "pergraph"):
        super().__init__(cfg)
        if reward_norm not in ("none", "pergraph"):
            raise ValueError(f"unknown reward_norm {reward_norm!r}")
        self.reward_norm = reward_norm

    def train(self, graphs: List[CompGraph],
              arrays: Optional[List[GraphArrays]] = None, *,
              platform: Platform, rng=None, verbose: bool = False,
              feature_cfg: Optional[FeatureConfig] = None,
              population: Optional[PopulationConfig] = None
              ) -> MultiSearchResult:
        return self.train_multi(graphs, arrays, platform=platform, rng=rng,
                                verbose=verbose, feature_cfg=feature_cfg,
                                reward_norm=self.reward_norm,
                                population=population)

    def evaluate_zero_shot(self, graph: CompGraph, *, platform: Platform,
                           arrays: Optional[GraphArrays] = None,
                           rng=None) -> Tuple[np.ndarray, float]:
        """Greedy-decode an *unseen* graph with the trained shared policy.

        → (placement, latency).  The graph is featurized with the training
        run's shared feature config so one-hot columns line up.
        """
        assert self.params is not None, "train() first"
        if arrays is None:
            if self.feature_config is None:
                raise ValueError(
                    "no stored feature_config; pass arrays= extracted with "
                    "the training config")
            arrays = extract_features(graph, self.feature_config)
        p = self.place(arrays, rng=rng, greedy=True).astype(np.int64)
        return p, simulate(graph, p, platform).latency

    # ------------------------------------------------------------ checkpoint
    def save_policy(self, directory: str, step: int = 0,
                    meta: Optional[Dict] = None) -> None:
        """Atomically persist the shared policy (+ feature layout).

        The manifest records the training config — in particular which
        simulation engine/backend produced the rewards, so a restored policy
        can be re-evaluated (or fine-tuned) under the same cost model.
        """
        from ..checkpoint import save_policy
        assert self.params is not None, "train() first"
        full_meta = dict(meta or {})
        full_meta.setdefault("engine", self.cfg.engine)
        full_meta.setdefault("config", dataclasses.asdict(self.cfg))
        save_policy(directory, self.params, step=step,
                    feature_config=self.feature_config, meta=full_meta)

    def load_policy(self, directory: str,
                    step: Optional[int] = None) -> int:
        """Restore a saved shared policy into this trainer.

        ``self.params`` must already be initialized (``init()`` on any graph
        featurized with the same config) so the pytree structure is known.
        Restores the stored feature config onto ``self.feature_config`` and
        returns the restored step.
        """
        from ..checkpoint import restore_policy
        assert self.params is not None, \
            "init() first (the checkpoint restores into the param structure)"
        self.params, self.feature_config, step, manifest = restore_policy(
            directory, self.params, step=step)
        recorded = manifest.get("engine")
        if recorded is not None and recorded not in (
                _LOOP_ENGINES + tuple(backend_names())):
            raise ValueError(
                f"checkpoint was trained with engine {recorded!r}, which is "
                f"not registered here; registered simulator backends: "
                f"{backend_names()}")
        self._opt_state = self._opt.init(self.params)
        return step
