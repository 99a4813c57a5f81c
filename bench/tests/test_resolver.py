"""How a configuration names its graph and its fleet: the admitted cell's
configuration resolves to what it always ran, and a configuration that does
not resolve ends the run at set-up, with exit code 2 and no result."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import common
from bench.tests.helpers import REPO, tiny_checkout

sys.path.insert(0, os.path.join(REPO, "src"))


def test_bert_base_resolves_to_the_paper_graph_and_host():
    from repro.core import paper_platform
    from repro.core.features import extract_features, shared_feature_config
    from repro.graphs import PAPER_BENCHMARKS

    config = common.load_json("configs", "bert_base")
    assert (config["graph"]["builder"], config["platform"]) == (
        "bert_base", "paper")
    graph, want = common.build_graph(config), PAPER_BENCHMARKS["bert_base"]()
    assert (graph.num_nodes, graph.num_edges) == (776, 834)
    assert np.array_equal(graph.edges, want.edges)
    assert [(n.op_type, n.flops, n.bytes_out) for n in graph.nodes] == [
        (n.op_type, n.flops, n.bytes_out) for n in want.nodes]
    got = extract_features(graph, shared_feature_config([graph]))
    ref = extract_features(want, shared_feature_config([want]))
    for name in ("x", "adj", "edges"):
        assert np.array_equal(getattr(got, name), getattr(ref, name)), name

    platform, host = common.build_platform(config), paper_platform()
    assert platform.devices == host.devices
    assert np.array_equal(platform.link_bw, host.link_bw)
    assert np.array_equal(platform.link_latency, host.link_latency)
    assert platform.coords is None and host.coords is None


# Each case: (configuration it starts from, the key path it sets, the value,
# a fragment of the error).  ``tiny`` is a builder graph on the paper's host;
# ``tiny_fleet`` a traced graph on four devices (bench/tests/helpers.py).
BAD = {
    "no_graph": ("tiny", ("graph",), {"workload": "synthetic:count=0",
                                      "nodes": 1, "edges": 0},
                 "yields 0 graphs"),
    "two_graphs": ("tiny", ("graph",), {"workload": "synthetic:count=2",
                                        "nodes": 1, "edges": 0},
                   "yields 2 graphs"),
    "nodes": ("tiny", ("graph", "nodes"), 95,
              "built with 94 nodes, not the 95"),
    "edges": ("tiny", ("graph", "edges"), 98,
              "built with 97 edges, not the 98"),
    "devices": ("tiny_fleet", ("policy", "num_devices"), 2,
                "has 4 devices; the policy places on num_devices=2"),
    "platform": ("tiny", ("platform",), "paper_host", "unknown platform"),
    "both": ("tiny", ("graph", "workload"), "benchmark:names=bert_base",
             "give exactly one of 'builder' and 'workload'"),
}

# run.main with the look for a chip skipped; the check windows and the timed
# window must not be reached.
MAIN = """
import sys
sys.path.insert(0, ".")
from bench import drive_search, run
def reached(self):
    raise AssertionError("the configuration's checks let set-up go on")
drive_search.SearchRun.setup = reached
full = run.run
run.run = lambda args: full(args, require_tpu=False)
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.fixture(scope="module")
def bad_checkout(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("resolver"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "workloads", "search.tiny.json")) as f:
        cell = json.load(f)
    for case, (base, path, value, _) in BAD.items():
        with open(os.path.join(b, "configs", f"{base}.json")) as f:
            config = json.load(f)
        entry = config
        for key in path[:-1]:
            entry = entry[key]
        entry[path[-1]] = value
        name = f"bad_{case}"
        with open(os.path.join(b, "configs", f"{name}.json"), "w") as f:
            json.dump(dict(config, name=name), f)
        with open(os.path.join(b, "workloads", f"{name}.json"), "w") as f:
            json.dump(dict(cell, config=name), f)
    return root


@pytest.mark.parametrize("case", sorted(BAD))
def test_a_configuration_that_does_not_resolve_exits_2(bad_checkout, case):
    proc = subprocess.run(
        [sys.executable, "-c", MAIN, "--workload", f"bad_{case}", "--seed",
         str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=bad_checkout, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert BAD[case][-1] in proc.stderr.splitlines()[-1], proc.stderr[-3000:]
    assert proc.stdout == ""
