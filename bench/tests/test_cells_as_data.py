"""Whole runs on the CPU, of cells added as data files only, and with the
timed path broken underneath: the comparison has to catch each fault."""
from bench.faults import SEARCH_FAULTS
from bench.tests.helpers import run_cells, tiny_checkout


def test_added_cells_run_and_are_correct(tmp_path):
    root = tiny_checkout(tmp_path)
    results = run_cells(root, [
        dict(workload="search.tiny", seed=2 ** 31 + 7, seconds=2, trace=0),
        dict(workload="search.tiny", seed=11, seconds=2, trace=1),
        dict(workload="search.tiny_fleet", seed=2 ** 31 + 5, seconds=2,
             trace=0),
        dict(workload="mesh.tiny", seed=2 ** 31 + 9, seconds=2, trace=0),
        dict(workload="serve.tiny", seed=2 ** 32 + 3, seconds=2, trace=0),
        dict(workload="serve.tiny", seed=12, seconds=2, trace=1)])
    for r in results:
        res = r["result"]
        assert res["correct"], res["checks"]
        assert list(res)[-1] == "checks"
        assert res["failed"] == 0 and res["attempted"] > 0
    search, _, fleet, mesh, serve, _ = (r["result"] for r in results)
    assert set(search["metrics"]) == {"placements_per_s", "setup_s"}
    assert set(fleet["metrics"]) == {"placements_per_s", "setup_s"}
    assert mesh["device"]["count"] == 4
    assert set(serve["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                     "setup_s"}
    # A CPU trace has no device plane, so device readers report nothing;
    # the server's counters still read.
    assert "batch_fill.serve" in results[-1]["result"]["metrics"]


def test_each_fault_makes_the_run_incorrect(tmp_path):
    root = tiny_checkout(tmp_path)
    specs = [dict(workload=cell, seed=21, seconds=1, trace=0, fault=f)
             for cell in ("search.tiny", "search.tiny_fleet")
             for f in SEARCH_FAULTS]
    specs += [dict(workload="mesh.tiny", seed=23, seconds=1, trace=0,
                   fault=f) for f in ("no_exchange", "replay_z")]
    specs.append(dict(workload="serve.tiny", seed=22, seconds=2, trace=0,
                      fault="altered_serve"))
    for r in run_cells(root, specs):
        assert not r["result"]["correct"], (r["spec"], r["result"]["checks"])
