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
