#!/usr/bin/env python3
"""Smoke test of the placement system's main path on a TPU.

Drives the user entry points — ``PlacementSpec`` → ``PlacementSession`` →
simulator backends → ``PlacementService`` / ``AsyncPlacementServer`` — at
the paper's full graph sizes, in one process, phase by phase:

1. device: the first device must be a TPU;
2. search: the paper's training loop (``mode="search"``, the fused ``scan``
   engine, 256 chains, 10-step windows) on Inception-v3 and BERT; the best
   latency and the greedy decode's latency, both computed on the device,
   must match the host list scheduler;
3. level: the level-major Pallas kernel scores 256 placements per graph,
   must match the host list scheduler on the same order, and must be a
   compiled kernel (``tpu_custom_call``), not the interpreter;
4. serving: a ``PlacementService`` with a persistent AOT cache and an
   ``AsyncPlacementServer`` answer a mixed burst; every placement must equal
   ``session.place``; a second service on the same AOT directory must serve
   every bucket without tracing.

``--chips 4`` runs only the sharded rollout engine: a corpus fit on a 2x2
device mesh against the same fit on one device.

Any failed check raises and the script exits non-zero; on success the last
line of standard output is one JSON object naming the device.  Timings
printed on the way are informational, not benchmark results.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # mesh path only, four chips
"""
import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

RTOL = 1e-5
OUT_DIR = os.path.join(ROOT, "smoke_out")
SERVE_WORKLOAD = "benchmark;synthetic:family=mixed:count=3:size=30:seed=0"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def device_check(chips: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    log(f"[device] {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    return devices


# ------------------------------------------------------------------ phases
def search_phase(name: str, *, chains: int = 256, window: int = 10,
                 episodes: int = 3):
    """The paper's loop on one Table-2 graph; → the fitted session."""
    import jax
    from repro.api import PlacementSession, PlacementSpec
    from repro.core import HSDAGConfig, simulate
    from repro.core.sim import get_backend

    spec = PlacementSpec(
        workload=f"benchmark:names={name}", mode="search",
        config=HSDAGConfig(batch_chains=chains, update_timestep=window,
                           max_episodes=episodes, use_baseline=True,
                           normalize_weights=True))
    session = PlacementSession(spec)
    res = session.fit(rng=jax.random.PRNGKey(0))
    g, plat = session.graphs[0], session.platform
    hist = res.history
    steady_s = sum(h["wall_s"] for h in hist[2:])
    steady = (chains * window * len(hist[2:]) / steady_s
              if steady_s > 0 else float("nan"))
    log(f"[search] {name}: V={g.num_nodes} B={chains} T={window} "
        f"episodes={episodes}; episode 0 (compile) {hist[0]['wall_s']:.1f} s, "
        f"episode 1 {hist[1]['wall_s']:.1f} s, steady {steady:.1f} "
        f"placements/s")

    host_best = simulate(g, res.best_placement, plat).latency
    err = rel_err(res.best_latency, host_best)
    log(f"[search] {name}: best {res.best_latency * 1e3:.6f} ms in-jit vs "
        f"{host_best * 1e3:.6f} ms host, rel err {err:.2e}")
    check(np.isfinite(res.best_latency) and err <= RTOL,
          f"{name} in-jit best latency vs host simulate ({err:.2e})")

    greedy = session.place(g)
    scan = get_backend("scan")
    dev_lat = scan.simulate_batch(scan.prepare(g, plat),
                                  greedy[None]).latency[0]
    host_lat = simulate(g, greedy, plat).latency
    err = rel_err(dev_lat, host_lat)
    log(f"[search] {name}: greedy place() {dev_lat * 1e3:.6f} ms on device "
        f"vs {host_lat * 1e3:.6f} ms host, rel err {err:.2e}")
    check(err <= RTOL, f"{name} greedy latency device vs host ({err:.2e})")
    return session


def level_phase(name: str, *, batch: int = 256, seed: int = 0) -> None:
    """The level Pallas kernel against the host scheduler, compiled."""
    from repro.core import paper_platform, simulate
    from repro.core.sim import get_backend
    from repro.core.sim.level import _level_batch_fn
    from repro.graphs import PAPER_BENCHMARKS

    g, plat = PAPER_BENCHMARKS[name](), paper_platform()
    level = get_backend("level")
    prep = level.prepare(g, plat)
    rng = np.random.default_rng(seed)
    placements = rng.integers(0, plat.num_devices, (batch, g.num_nodes))
    t0 = time.perf_counter()
    got = level.simulate_batch(prep, placements).latency
    first_s = time.perf_counter() - t0
    want = [simulate(g, p, plat, order=prep.arrays.order).latency
            for p in placements]
    err = rel_err(got, want)
    log(f"[level] {name}: {batch} placements, L={prep.levels.num_levels} "
        f"W={prep.levels.max_width}; first call {first_s:.1f} s; max rel "
        f"err vs host {err:.2e}")
    check(np.all(np.isfinite(got)) and err <= RTOL,
          f"{name} level kernel vs host simulate ({err:.2e})")
    hlo = _level_batch_fn().lower(prep.arrays, prep.levels,
                                  placements.astype(np.int32),
                                  interpret=False).as_text()
    check("tpu_custom_call" in hlo,
          f"{name} level scorer lowers to no tpu_custom_call")
    log(f"[level] {name}: lowered scorer holds a tpu_custom_call")


def serving_phase(*, workload: str = SERVE_WORKLOAD, requests: int = 24,
                  chains: int = 8, seed: int = 0) -> None:
    """Service + async server answer a mixed burst; AOT reload traces 0."""
    import jax
    from repro.api import (AotExecutableCache, AsyncPlacementServer,
                           PlacementService, PlacementSession, PlacementSpec)
    from repro.core import HSDAGConfig

    # A search session knows only its own graph's op vocabulary; serving the
    # Table-2 graphs and synthetic ones together needs a corpus policy.
    session = PlacementSession(PlacementSpec(
        workload=workload, mode="corpus",
        config=HSDAGConfig(batch_chains=chains, update_timestep=2,
                           max_episodes=1),
        max_buckets=2, graphs_per_episode=2))
    t0 = time.perf_counter()
    session.fit(rng=jax.random.PRNGKey(seed))
    graphs = list(session.graphs)
    log(f"[serve] corpus policy over {len(graphs)} graphs fitted in "
        f"{time.perf_counter() - t0:.1f} s")

    order = np.random.default_rng(seed).permutation(
        np.arange(requests) % len(graphs))
    burst = [graphs[i] for i in order]
    expected = {g.name: session.place(g) for g in graphs}

    aot_dir = os.path.join(OUT_DIR, "aot")
    shutil.rmtree(aot_dir, ignore_errors=True)
    service = PlacementService(session, aot_cache=AotExecutableCache(aot_dir))
    t0 = time.perf_counter()
    got = service.place_many(burst)
    cold_s = time.perf_counter() - t0
    for g, p in zip(burst, got):
        check(np.array_equal(p, expected[g.name]),
              f"place_many placement of {g.name} != session.place")
    stats = service.stats()
    log(f"[serve] place_many: {len(burst)} requests in {cold_s:.1f} s "
        f"(cold), {stats['shape_keys_seen']} bucket shapes traced, "
        f"{stats['aot_stores']} AOT executables stored")
    check(stats["aot_stores"] == stats["shape_keys_seen"] > 0,
          f"every traced bucket exported to the AOT cache ({stats})")

    with AsyncPlacementServer(batch_slots=service.batch_slots) as server:
        tenant = server.register(service)
        futures = [server.submit(g, tenant=tenant) for g in burst]
        for g, f in zip(burst, futures):
            p = f.result(timeout=600)
            check(np.array_equal(p, expected[g.name]),
                  f"async placement of {g.name} != session.place")
    log(f"[serve] async server: {len(futures)} futures resolved, all equal "
        f"to session.place")

    fresh = PlacementService(session, aot_cache=AotExecutableCache(aot_dir))
    got = fresh.place_many(burst)
    for g, p in zip(burst, got):
        check(np.array_equal(p, expected[g.name]),
              f"AOT-served placement of {g.name} != session.place")
    fstats = fresh.stats()
    log(f"[serve] second service on the AOT directory: "
        f"{fstats['aot_hits']} AOT hits, {fstats['aot_decodes']} AOT "
        f"decodes, {fstats['shape_keys_seen']} traces")
    check(fstats["aot_hits"] == stats["aot_stores"]
          and fstats["aot_decodes"] > 0 and fstats["shape_keys_seen"] == 0,
          f"warm AOT service must serve every bucket untraced ({fstats})")


def mesh_phase(*, mesh=(2, 2), workload: str = "benchmark", chains: int = 8,
               episodes: int = 2) -> None:
    """Corpus fit on a device mesh against the same fit on one device."""
    import jax
    from repro.api import PlacementSession, PlacementSpec
    from repro.core import HSDAGConfig
    from repro.core.sim.sharded import make_rollout_mesh

    ids = [d.id for d in make_rollout_mesh(*mesh).devices.flat]
    log(f"[mesh] rollout mesh {mesh[0]}x{mesh[1]} over device ids {ids}")
    check(len(set(ids)) == mesh[0] * mesh[1], f"mesh spans {ids}")

    def fit(mesh_shape):
        spec = PlacementSpec(
            workload=workload, mode="corpus",
            config=HSDAGConfig(batch_chains=chains, update_timestep=4,
                               max_episodes=episodes),
            max_buckets=1, graphs_per_episode=2, mesh=mesh_shape)
        t0 = time.perf_counter()
        res = PlacementSession(spec).fit(rng=jax.random.PRNGKey(0))
        log(f"[mesh] mesh={mesh_shape}: fit in "
            f"{time.perf_counter() - t0:.1f} s, greedy latencies (ms) "
            f"{np.round(np.asarray(res.greedy_latencies) * 1e3, 6).tolist()}")
        return res

    # Sharding reorders the chain reductions of the replay gradient.  At the
    # TPU's default matmul precision (one bf16 pass) those last-bit
    # parameter differences are amplified into different greedy decisions,
    # so both fits run at f32 matmul precision: the regime in which the
    # virtual-device parity tests hold the same 1e-5.
    with jax.default_matmul_precision("highest"):
        ref, got = fit(None), fit(list(mesh))
    dparams = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in zip(jax.tree.leaves(ref.params),
                                  jax.tree.leaves(got.params)))
    err = rel_err(got.greedy_latencies, ref.greedy_latencies)
    log(f"[mesh] sharded vs one-device: max abs parameter difference "
        f"{dparams:.2e}, greedy latencies max rel err {err:.2e}")
    check(np.all(np.isfinite(ref.greedy_latencies)) and err <= RTOL,
          f"mesh {mesh} greedy latencies vs unsharded ({err:.2e})")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the 2x2-mesh rollout comparison")
    args = ap.parse_args(argv)

    use_compile_cache()
    devices = device_check(args.chips)
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase()
    else:
        # BERT first: its replay window is the largest program, so it runs
        # while nothing else holds device memory.
        for name in ("bert_base", "inception_v3"):
            search_phase(name)
        for name in ("inception_v3", "bert_base"):
            level_phase(name)
        serving_phase()
    stats = devices[0].memory_stats() or {}
    log(f"[memory] peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"{stats.get('bytes_limit')}; all phases {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
