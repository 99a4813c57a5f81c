"""HSDAG placement search through the v1 facade (`repro.api`).

Every run is one declarative :class:`repro.api.PlacementSpec` — workload,
platform, engine config, training mode — driven by one
:class:`repro.api.PlacementSession`.  The three modes map onto the three
trainers (and are equivalence-pinned against them bit-for-bit):

``--mode search`` (default) reproduces the paper's experiment — BERT graph,
CPU/GPU platform, convergence trace — then runs the same algorithm in its
production slot, partitioning an assigned architecture's layer graph across
2 pods (DESIGN.md §3.2)::

    PYTHONPATH=src python examples/placement_search.py [--episodes N]

``--mode multi`` trains ONE policy jointly over the workload's graphs
(Inception-V3 + ResNet-50 by default) in a single jitted (G, B) batched
loop, then zero-shot transfers it to the held-out BERT graph::

    PYTHONPATH=src python examples/placement_search.py --mode multi

``--mode corpus`` curriculum-trains over a workload corpus — any mix the
workload registry can build, size-bucketed so the corpus never has to fit
one device batch — with optional run checkpointing and warm starts::

    PYTHONPATH=src python examples/placement_search.py --mode corpus \\
        --workload "benchmark;synthetic:family=mixed:count=9:size=30:seed=0" \\
        --checkpoint ckpt/corpus

A full spec document can also be supplied verbatim (``--spec-json file``),
and every run can print its canonical document + hash (``--print-spec``) —
the same JSON a :meth:`PlacementSession.save` manifest records.

Deprecated (thin shims over the facade, warn on use): the pre-v1 flags
``--multi-graph``, ``--corpus SPEC`` and ``--warm-start`` without
``--mode corpus`` — use ``--mode``/``--workload`` instead.
"""
import argparse
import warnings

import numpy as np

from repro.api import (PlacementSession, PlacementSpec, build_platform,
                       parse_platform_spec)
from repro.compile_cache import use_compile_cache
from repro.core import HSDAGConfig, PopulationConfig, simulate
from repro.core.baselines import cpu_only, gpu_only
from repro.core.planner import plan_stages
from repro.configs import get
from repro.graphs import bert_base

DEFAULT_WORKLOADS = {
    "search": "benchmark:names=bert_base",
    "multi": "benchmark:names=inception_v3+resnet50",
    "corpus": "benchmark;synthetic:family=mixed:count=9:size=30:seed=0",
}


def build_spec(args) -> PlacementSpec:
    """One declarative document from the CLI knobs."""
    if args.spec_json:
        with open(args.spec_json) as f:
            return PlacementSpec.from_json(f.read())
    workload = args.workload or DEFAULT_WORKLOADS[args.mode]
    # search/multi keep the paper driver's variance-reduction knobs; the
    # corpus trainer's per-graph standardization subsumes them.
    extras = ({} if args.mode == "corpus"
              else dict(use_baseline=True, normalize_weights=True))
    # --platform takes the same colon-separated spec form as --workload
    # (parse errors name the offending segment); the policy's action space
    # follows the platform's device count.
    pname, pargs = parse_platform_spec(args.platform)
    num_devices = 2
    if pname != "paper":
        num_devices = build_platform(
            PlacementSpec(workload="", platform=pname,
                          platform_args=pargs)).num_devices
    return PlacementSpec(
        workload=workload, mode=args.mode,
        platform=pname, platform_args=pargs,
        head=(args.head or None),
        config=HSDAGConfig(num_devices=num_devices,
                           max_episodes=args.episodes,
                           update_timestep=10, batch_chains=args.chains,
                           engine=args.engine, **extras),
        max_buckets=args.max_buckets,
        graphs_per_episode=args.graphs_per_episode,
        sampler=args.sampler,
        checkpoint_dir=(args.checkpoint or None
                        if args.mode == "corpus" else None),
        checkpoint_every=(max(1, args.episodes // 4)
                          if args.mode == "corpus" and args.checkpoint
                          else 0),
        warm_start=(args.warm_start or None
                    if args.mode == "corpus" else None),
        mesh=([int(x) for x in args.mesh.split("x")] if args.mesh else None),
        stream=bool(args.stream),
        population=(PopulationConfig(
            cull_every=args.cull_every,
            greedy_restart_every=args.greedy_restart_every)
            if args.population else None),
        prefetch=args.prefetch)


def report_search(session: PlacementSession, res) -> None:
    graph = session.graphs[0]
    print(f"evaluated {res.num_evaluations} placements "
          f"at {res.evals_per_sec:.1f}/s")
    cpu = simulate(graph, cpu_only(graph), session.platform).latency
    print(f"\n{graph.name}: CPU-only {cpu*1e3:.3f} ms → HSDAG "
          f"{res.best_latency*1e3:.3f} ms "
          f"({100*(cpu-res.best_latency)/cpu:.1f}% speedup; paper: 58.2%)")
    groups = [h["mean_groups"] for h in res.history]
    print(f"learned group count ranged {min(groups):.0f}–{max(groups):.0f} "
          f"(emergent, never preset — §2.4)")

    # ---- production slot: pipeline stages across pods ----
    cfg = get("jamba-1.5-large-398b").config
    plan = plan_stages(cfg, seq_len=4096, batch=256, num_stages=2,
                       kind="train")
    print(f"\njamba-1.5-large-398b × train_4k across 2 pods:")
    print(f"  even-split makespan : {plan.baseline_latency*1e3:.2f} ms")
    print(f"  HSDAG-planned       : {plan.latency*1e3:.2f} ms")
    print(f"  stage boundaries at layer-graph nodes {plan.boundaries}")


def report_multi(session: PlacementSession, res) -> None:
    G = len(session.graphs)
    print(f"\njoint training: {res.num_evaluations} placements "
          f"at {res.evals_per_sec:.1f}/s "
          f"(G={G} × B={session.spec.config.batch_chains} chains, "
          f"one policy)")
    for g, best, greedy in zip(session.graphs, res.best_latencies,
                               res.greedy_latencies):
        cpu = simulate(g, cpu_only(g), session.platform).latency
        print(f"  {g.name:16s} CPU-only {cpu*1e3:7.3f} ms → joint best "
              f"{best*1e3:7.3f} ms (greedy decode {greedy*1e3:7.3f} ms)")
    trained_on = {g.name for g in session.graphs}
    if "bert_base" not in trained_on:
        # Held-out transfer deliberately tolerates out-of-vocabulary ops
        # (they one-hot to zeros), so it goes through the PR-2 trainer API
        # rather than the session's strictly-validated place().
        held = bert_base()
        _, lat = session.trainer.evaluate_zero_shot(
            held, platform=session.platform)
        cpu = simulate(held, cpu_only(held), session.platform).latency
        gpu = simulate(held, gpu_only(held), session.platform).latency
        print(f"\nzero-shot transfer → {held.name} (never trained on):")
        print(f"  CPU-only {cpu*1e3:.3f} ms | GPU-only {gpu*1e3:.3f} ms | "
              f"transferred policy {lat*1e3:.3f} ms "
              f"({100*(cpu-lat)/cpu:.1f}% vs CPU)")


def report_corpus(session: PlacementSession, res) -> None:
    print(f"\ncorpus training: {res.num_evaluations} placements at "
          f"{res.evals_per_sec:.1f}/s over {len(res.buckets)} size buckets "
          f"{[len(b) for b in res.buckets]}")
    for g, best, greedy in zip(session.graphs, res.best_latencies,
                               res.greedy_latencies):
        cpu = simulate(g, cpu_only(g), session.platform).latency
        sampled = (f"{best*1e3:7.3f} ms" if np.isfinite(best)
                   else "  (unsampled)")
        print(f"  {g.name[:28]:28s} CPU-only {cpu*1e3:7.3f} ms → "
              f"best {sampled} | greedy {greedy*1e3:7.3f} ms")


def run_spec(args, platform=None) -> PlacementSession:
    """spec → session → fit → mode-specific report (+ optional save)."""
    spec = build_spec(args)
    if args.print_spec:
        print(f"spec_hash {spec.spec_hash()}")
        print(spec.to_json())
    session = PlacementSession(spec)
    if spec.mode == "corpus":
        print(f"corpus: {spec.workload}")
        if spec.warm_start:
            print(f"warm-starting from {spec.warm_start} (saved feature "
                  f"layout validated against the corpus before restoring)")
    res = session.fit(verbose=True, platform=platform)
    {"search": report_search, "multi": report_multi,
     "corpus": report_corpus}[spec.mode](session, res)
    if args.checkpoint:
        policy_dir = (args.checkpoint + "_policy"
                      if spec.mode == "corpus" else args.checkpoint)
        session.save(policy_dir)
        print(f"policy + feature layout + spec saved to {policy_dir} "
              f"(PlacementSession.load / PlacementService serve it; "
              f"--mode corpus --warm-start fine-tunes from it)")
    return session


# ------------------------------------------------------- deprecated shims
def _fill_defaults(args) -> None:
    """Backfill CLI knobs a pre-v1 args namespace never carried."""
    for k, v in (("spec_json", ""), ("print_spec", False), ("workload", ""),
                 ("episodes", 10), ("chains", 8), ("engine", "auto"),
                 ("warm_start", ""), ("max_buckets", 4),
                 ("graphs_per_episode", 4), ("sampler", "stratified"),
                 ("checkpoint", ""), ("mode", "search"),
                 ("population", False), ("cull_every", 4),
                 ("greedy_restart_every", 0), ("prefetch", "auto"),
                 ("platform", "paper"), ("head", "")):
        if not hasattr(args, k):
            setattr(args, k, v)


def run_corpus(args, platform=None) -> None:
    """Deprecated pre-v1 entry point; use ``run_spec`` (``--mode corpus``)."""
    warnings.warn("run_corpus() is deprecated; drive a PlacementSpec with "
                  "mode='corpus' through run_spec()/PlacementSession",
                  DeprecationWarning, stacklevel=2)
    _fill_defaults(args)
    args.mode = "corpus"
    args.workload = args.corpus
    run_spec(args, platform)


def run_multi_graph(args, platform=None) -> None:
    """Deprecated pre-v1 entry point; use ``run_spec`` (``--mode multi``)."""
    warnings.warn("run_multi_graph() is deprecated; drive a PlacementSpec "
                  "with mode='multi' through run_spec()/PlacementSession",
                  DeprecationWarning, stacklevel=2)
    _fill_defaults(args)
    args.mode = "multi"
    args.workload = args.workload or DEFAULT_WORKLOADS["multi"]
    run_spec(args, platform)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="search",
                    choices=("search", "multi", "corpus"),
                    help="search = single-graph RL search (paper Alg. 1); "
                         "multi = one policy jointly over the workload's "
                         "graphs; corpus = bucketed curriculum over a "
                         "workload corpus")
    ap.add_argument("--workload", default="",
                    help="workload-corpus spec string (registry providers "
                         "';'-separated), e.g. 'benchmark;synthetic:family="
                         "mixed:count=9:size=30:seed=0'; default depends "
                         "on --mode")
    ap.add_argument("--spec-json", default="",
                    help="path to a full PlacementSpec JSON document "
                         "(overrides every other spec knob)")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the run's canonical spec JSON + hash")
    ap.add_argument("--episodes", type=int, default=10)
    ap.add_argument("--chains", type=int, default=8,
                    help="parallel rollout chains (B); rewards are computed "
                         "inside the jitted rollout by simulate_jax")
    ap.add_argument("--engine", default="auto",
                    help="rollout engine / simulator backend: auto | scalar "
                         "| batched | reference | scan | level (scan = fused "
                         "in-jit node-scan kernel, the default; level = "
                         "level-parallel Pallas kernel, window-scored)")
    ap.add_argument("--warm-start", default="",
                    help="with --mode corpus: fine-tune from a saved policy "
                         "checkpoint instead of training from scratch")
    ap.add_argument("--max-buckets", type=int, default=4,
                    help="with --mode corpus: bound on size buckets (jit "
                         "recompiles stay O(#buckets))")
    ap.add_argument("--graphs-per-episode", type=int, default=4,
                    help="with --mode corpus: graphs subsampled per episode")
    ap.add_argument("--sampler", default="stratified",
                    choices=("uniform", "stratified", "plateau"),
                    help="with --mode corpus: curriculum sampling strategy")
    ap.add_argument("--checkpoint", default="",
                    help="directory to save the trained policy (+ run state "
                         "in corpus mode)")
    ap.add_argument("--mesh", default="",
                    help="with --mode corpus: GxB device-mesh factorization "
                         "for sharded rollouts, e.g. 2x4 (needs matching "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--stream", action="store_true",
                    help="with --mode corpus: build the workload as a "
                         "streaming corpus (lazy graphs behind an LRU)")
    ap.add_argument("--population", action="store_true",
                    help="PBT-style chain-population search: per-chain "
                         "sampling temperatures, periodic culling of the "
                         "worst chains, elite exchange and greedy restarts "
                         "(scale --chains to 256+ to benefit)")
    ap.add_argument("--cull-every", type=int, default=4,
                    help="with --population: PBT transition period, in "
                         "update windows (search/multi) or episodes (corpus)")
    ap.add_argument("--greedy-restart-every", type=int, default=0,
                    help="with --population: every Nth PBT transition "
                         "re-seeds culled chains from a greedy decode "
                         "instead of the per-graph best chain (0 = never)")
    ap.add_argument("--platform", default="paper",
                    help="platform spec 'name[:key=value:...]', e.g. "
                         "'nvlink_island:islands=2:gpus_per_island=4' — "
                         "registered names: paper, tpu_stage, "
                         "nvlink_island, multi_host, torus, ring; parse "
                         "errors name the offending segment")
    ap.add_argument("--head", default="", choices=("", "dense", "device"),
                    help="policy output head: dense = the paper's fixed "
                         "Dense(num_devices) layer; device = platform-"
                         "conditioned node x device compatibility scores "
                         "with capacity-aware action masking (pairs with "
                         "multi-device --platform topologies)")
    ap.add_argument("--prefetch", default="auto",
                    choices=("auto", "on", "off"),
                    help="with --mode corpus: overlap host featurization of "
                         "episode t+1 with device rollouts of episode t")
    # ---- deprecated pre-v1 spellings (shims over --mode/--workload) ----
    ap.add_argument("--multi-graph", action="store_true",
                    help="DEPRECATED: use --mode multi")
    ap.add_argument("--corpus", default="",
                    help="DEPRECATED: use --mode corpus --workload SPEC")
    args = ap.parse_args()

    if args.corpus:
        warnings.warn("--corpus SPEC is deprecated; use --mode corpus "
                      "--workload SPEC", DeprecationWarning, stacklevel=2)
        args.mode, args.workload = "corpus", args.corpus
    elif args.multi_graph:
        warnings.warn("--multi-graph is deprecated; use --mode multi",
                      DeprecationWarning, stacklevel=2)
        args.mode = "multi"
    if args.warm_start and args.mode != "corpus":
        ap.error("--warm-start requires --mode corpus")
    if (args.mesh or args.stream) and args.mode != "corpus":
        ap.error("--mesh/--stream require --mode corpus")
    if args.mesh and not all(p.isdigit() for p in args.mesh.split("x")):
        ap.error(f"--mesh wants GxB (e.g. 2x4), got {args.mesh!r}")
    try:
        parse_platform_spec(args.platform)
    except ValueError as e:
        ap.error(str(e))
    use_compile_cache()
    run_spec(args)


if __name__ == "__main__":
    main()
