"""The plain references against the program, and the control against the
comparison."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.tests.helpers import REPO, tiny_checkout

sys.path.insert(0, os.path.join(REPO, "src"))


@pytest.mark.parametrize("name", ["bert_base", "inception_v3"])
def test_reference_scheduler_matches_the_program_simulator(name):
    from repro.core import paper_platform, simulate
    from repro.graphs import PAPER_BENCHMARKS

    from bench.ref_sim import RefScheduler
    g, plat = PAPER_BENCHMARKS[name](), paper_platform()
    placements = np.random.default_rng(0).integers(0, 2, (16, g.num_nodes))
    got = RefScheduler(g, plat).latency(placements)
    want = [simulate(g, p, plat).latency for p in placements]
    assert np.allclose(got, want, rtol=1e-12)


# Every fleet builder of the program's platform registry besides the paper's
# host (above), each with the keyword that sets its devices' memory.
FLEETS = {"nvlink_island": "mem_capacity", "multi_host": "mem_capacity",
          "torus": "mem_capacity", "ring": "mem_capacity",
          "tpu_stage": "chips_per_stage"}


def test_every_registered_fleet_is_compared():
    from repro.api import platform_names
    assert set(platform_names()) == set(FLEETS) | {"paper"}


@pytest.mark.parametrize("fleet", sorted(FLEETS))
@pytest.mark.parametrize("workload", ["traced:archs=jamba-1.5-large-398b",
                                      "lm:archs=jamba-1.5-large-398b"])
def test_reference_scheduler_matches_the_program_on_every_fleet(fleet,
                                                                 workload):
    """Latencies and OOM flags on a traced graph (jaxpr primitives) and a
    planner layer graph, with devices whose memory sits at the median of
    the placements' fullest device, so that about half of them overflow
    (a stage of TPU chips holds whole multiples of a chip's memory)."""
    from repro.api import PlacementSpec, build_platform
    from repro.core import simulate
    from repro.graphs import build_corpus

    from bench.ref_sim import RefScheduler

    def build(**args):
        return build_platform(PlacementSpec(workload="", platform=fleet,
                                            platform_args=args))
    (g,) = build_corpus(workload)
    num = build().num_devices
    placements = np.random.default_rng(1).integers(0, num,
                                                   (16, g.num_nodes))
    fullest = np.stack([(g.bytes_out() * (placements == d)).sum(1)
                        for d in range(num)]).max(0)
    target = float(np.median(fullest))
    if FLEETS[fleet] == "mem_capacity":
        plat = build(mem_capacity=target)
    else:           # the capacity of a stage is that of its chips
        chip = build(chips_per_stage=1).devices[0].mem_capacity
        plat = build(chips_per_stage=max(1, round(target / chip)))
    ref = RefScheduler(g, plat)
    runs = [simulate(g, p, plat) for p in placements]
    assert np.allclose(ref.latency(placements), [r.latency for r in runs],
                       rtol=1e-12)
    oom = ref.oom(placements)
    assert oom.tolist() == [r.oom for r in runs]
    if plat.devices[0].mem_capacity < fullest.max():
        assert 0 < oom.sum() < len(oom)


def test_control_fails_the_comparison(tmp_path):
    root = tiny_checkout(tmp_path)
    out = str(tmp_path / "readings.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/readings.py", "--workload", "search.tiny",
         "--control", "31,32,33", "--out", out, "--cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(os.path.join(root, "bench", "workloads",
                           "search.tiny.json")) as f:
        limits = json.load(f)["limits"]
    with open(out) as f:
        readings = [json.loads(line) for line in f]
    assert len(readings) == 3
    for r in readings:
        assert any(v > limits[k] for k, v in r["numbers"].items()), r
