"""Table 10 — sharded fleet rollouts + streaming corpora (PR-6).

Two sections:

* **throughput** — corpus training placements/s in this process, on the
  devices it sees: mesh factorizations 1×1 / 1×2 / 2×2 / 2×4 over the
  ("graphs", "chains") axes for 1/2/4/8 devices, as many as are visible.
  One warmup run amortizes compiles; the measured run is steady-state.
  Each row names the platform, device kind and device count it ran on
  (virtual CPU devices, from
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, share the host's
  cores and measure partition overhead, not speedup).
* **memory** — in child processes, each with a fresh heap: peak
  Python-heap (tracemalloc) and peak RSS for an eager ``build_corpus`` +
  full featurization versus a ``StreamingCorpus`` pass (LRU
  ``cache_graphs=8``), at 24 and 240 synthetic graphs of size ~150.
  Eager memory grows with the corpus; streaming stays ~flat (bounded by
  the LRU working set).

Env knobs: ``REPRO_BENCH_SHARDED_DEVICES`` (default ``1,2,4,8``),
``REPRO_BENCH_SHARDED_EPISODES`` (measured episodes, default 2),
``REPRO_BENCH_STREAM_COUNTS`` (default ``24,240``).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

from common import emit

_MESHES = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4)}


def _throughput(gm: int, bm: int, episodes: int) -> float:
    """Steady corpus-training placements/s on a gm×bm rollout mesh."""
    from repro.core.costmodel import paper_platform
    from repro.core.hsdag import HSDAGConfig
    from repro.core.train.curriculum import CurriculumTrainer
    from repro.graphs import build_corpus

    cfg = HSDAGConfig(num_devices=2, hidden_channel=32,
                      update_timestep=10, batch_chains=4, max_episodes=1)
    corpus = build_corpus("synthetic:family=mixed:count=8:size=24:seed=0")
    tr = CurriculumTrainer(cfg, max_buckets=1, graphs_per_episode=4,
                           mesh_shape=None if gm * bm == 1 else (gm, bm))
    tr.train_corpus(corpus, platform=paper_platform())       # compile warmup
    t0 = time.perf_counter()
    tr.train_corpus(corpus, platform=paper_platform(), episodes=episodes)
    wall = time.perf_counter() - t0
    return episodes * cfg.update_timestep * 4 * cfg.batch_chains / wall


_MEMORY_CHILD = """
    import resource, tracemalloc
    tracemalloc.start()
    from repro.core.features import extract_features, shared_feature_config
    from repro.graphs import build_corpus

    count, stream = {count}, {stream}
    spec = f"synthetic:family=mixed:count={{count}}:size=150:seed=0"
    if stream:
        corpus = build_corpus(spec, stream=True, cache_graphs=8)
        fc = shared_feature_config(corpus.meta)
        for i in range(len(corpus)):            # one full featurize pass
            extract_features(corpus[i], fc)
    else:
        corpus = build_corpus(spec)
        fc = shared_feature_config(corpus)
        arrays = [extract_features(g, fc) for g in corpus]   # trainer-style
    peak_kb = tracemalloc.get_traced_memory()[1] / 1024.0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(f"RESULT,{{peak_kb:.1f}},{{rss_mb:.1f}}")
"""


def _run_child(code: str) -> list:
    """Host-heap measurement in a fresh process; it never needs a device."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
            env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"table10 child failed:\n{out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT,"):
            return line.split(",")[1:]
    raise RuntimeError(f"table10 child emitted no RESULT line:\n"
                       f"{out.stdout[-2000:]}")


def main() -> None:
    import jax
    dev = jax.devices()
    where = (f"platform={dev[0].platform};device_kind={dev[0].device_kind};"
             f"device_count={len(dev)}")
    episodes = int(os.environ.get("REPRO_BENCH_SHARDED_EPISODES", "2"))
    devices = [int(d) for d in os.environ.get(
        "REPRO_BENCH_SHARDED_DEVICES", "1,2,4,8").split(",")
        if d and int(d) <= len(dev)]

    base_pps = None
    for n in devices:
        gm, bm = _MESHES[n]
        pps = _throughput(gm, bm, episodes)
        if base_pps is None:
            base_pps = pps
        emit(f"table10_sharded_throughput_d{n}_mesh{gm}x{bm}",
             1e6 / max(pps, 1e-9),
             f"placements_per_s={pps:.1f};speedup_vs_d1="
             f"{pps / base_pps:.2f}x;{where}")

    counts = [int(c) for c in os.environ.get(
        "REPRO_BENCH_STREAM_COUNTS", "24,240").split(",") if c]
    for count in counts:
        for stream in (False, True):
            kind = "stream" if stream else "eager"
            peak_kb, rss_mb = _run_child(
                _MEMORY_CHILD.format(count=count, stream=stream))
            emit(f"table10_{kind}_corpus_mem_n{count}", float(peak_kb),
                 f"peak_heap_kb={peak_kb};rss_mb={rss_mb};graphs={count};"
                 f"col=us_per_call_holds_peak_heap_kb")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    main()
