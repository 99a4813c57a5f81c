"""Serving layer (PR 7): per-request isolation, async bucket-batching,
multi-tenant registry, persistent AOT executable cache.

Acceptance pins:

* the pre-fix ``place_many`` counter/validation-order bug stays fixed —
  an invalid request fails alone, ``stats()`` never drifts;
* greedy decodes are slot-position invariant (a request's placement does
  not depend on which padded slot it lands in);
* a **fresh process** serving a previously-seen (spec_hash, bucket shape)
  performs **0 recompiles**: ``shape_keys_seen`` stays empty and every
  decode is served by a preloaded executable (subprocess test, marked
  ``slow``).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api import (AotExecutableCache, AsyncPlacementServer,
                       PlacementRequestError, PlacementService,
                       PlacementSession, PlacementSpec)
from repro.core import CompGraph, HSDAGConfig

WL = "synthetic:family=mixed:count=4:size=12:seed=6"


def _cfg(**kw):
    base = dict(num_devices=2, hidden_channel=16, max_episodes=1,
                update_timestep=3, batch_chains=2)
    base.update(kw)
    return HSDAGConfig(**base)


@pytest.fixture(scope="module")
def fitted_session():
    session = PlacementSession(PlacementSpec(
        workload=WL, mode="corpus", config=_cfg(),
        max_buckets=2, graphs_per_episode=2))
    session.fit()
    return session


def _oov_graph() -> CompGraph:
    """An op type no synthetic family emits — must fail vocab validation."""
    g = CompGraph("oov")
    g.add_op("in", "Parameter", output_shape=(1, 4), flops=0, bytes_out=16)
    g.add_op("sm", "Softmax", ["in"], (1, 4), flops=10, bytes_out=16)
    return g


# ------------------------------------------------- per-request isolation
def test_place_many_invalid_request_raises_before_counters_move(
        fitted_session):
    """PR-7 regression: the pre-fix code incremented ``requests`` and lost
    the burst when one graph failed validation mid-burst."""
    service = PlacementService(fitted_session, batch_slots=2,
                               size_granularity=32)
    graphs = list(fitted_session.graphs)
    burst = [graphs[0], _oov_graph(), graphs[1]]
    with pytest.raises(PlacementRequestError, match="oov.*Softmax"):
        service.place_many(burst)
    stats = service.stats()
    assert stats["requests"] == 0          # nothing was decoded
    assert stats["failed"] == 1            # the bad request, alone
    # the valid requests' featurized arrays were NOT lost: serving them
    # again hits the prepared LRU
    service.place_many([graphs[0], graphs[1]])
    assert service.cache_hits == 2
    assert service.stats()["requests"] == 2


def test_place_many_return_exceptions_serves_the_rest(fitted_session):
    service = PlacementService(fitted_session, batch_slots=2,
                               size_granularity=32)
    graphs = list(fitted_session.graphs)
    burst = [graphs[0], _oov_graph(), graphs[1]]
    out = service.place_many(burst, return_exceptions=True)
    assert isinstance(out[1], ValueError) and "Softmax" in str(out[1])
    np.testing.assert_array_equal(out[0], service.place(graphs[0]))
    np.testing.assert_array_equal(out[2], service.place(graphs[1]))
    assert service.stats()["failed"] == 1
    assert service.stats()["requests"] == 2 + 2   # burst + the two re-places


def test_duplicate_graphs_within_one_burst(fitted_session):
    """Duplicates in one burst: every copy decodes, all copies equal, and
    the prepared LRU is hit (featurization once per distinct graph)."""
    service = PlacementService(fitted_session, batch_slots=2,
                               size_granularity=32)
    g0, g1 = fitted_session.graphs[0], fitted_session.graphs[1]
    out = service.place_many([g0, g0, g1, g0])
    assert service.cache_misses == 2            # g0, g1 featurized once each
    assert service.cache_hits == 2              # the two repeat g0 slots
    np.testing.assert_array_equal(out[0], out[1])
    np.testing.assert_array_equal(out[0], out[3])
    assert out[0].shape == (g0.num_nodes,)
    assert out[2].shape == (g1.num_nodes,)
    np.testing.assert_array_equal(out[0], service.place(g0))


def test_slot_position_invariance(fitted_session):
    """Greedy decode must not depend on which padded slot a request lands
    in: place() (slot 0) and every place_many permutation agree."""
    service = PlacementService(fitted_session, batch_slots=4,
                               size_granularity=64)   # one bucket for all
    graphs = list(fitted_session.graphs)
    solo = [service.place(g) for g in graphs]
    forward = service.place_many(graphs)
    backward = service.place_many(graphs[::-1])[::-1]
    for g, a, b, c in zip(graphs, solo, forward, backward):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"{g.name}: solo vs forward")
        np.testing.assert_array_equal(a, c,
                                      err_msg=f"{g.name}: solo vs backward")


# ------------------------------------------------------- async server
def test_async_server_futures_and_isolation(fitted_session):
    graphs = list(fitted_session.graphs)
    with AsyncPlacementServer(batch_slots=2, max_delay_ms=2.0) as server:
        tenant = server.register(fitted_session)
        futs = [server.submit(g, tenant=tenant) for g in graphs]
        bad = server.submit(_oov_graph(), tenant=tenant)
        # the bad request failed alone, immediately, without a decode
        with pytest.raises(ValueError, match="Softmax"):
            bad.result(timeout=5)
        svc = PlacementService(fitted_session, batch_slots=2,
                               size_granularity=16)
        for g, f in zip(graphs, futs):
            np.testing.assert_array_equal(f.result(timeout=120),
                                          svc.place(g))
        stats = server.stats()
        assert stats["requests"] == len(graphs)
        assert stats["failed"] == 1
        assert stats["queued"] == 0
    # after close: no new admissions
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(graphs[0], tenant=tenant)


def test_async_server_fills_batches_under_load(fitted_session):
    graphs = list(fitted_session.graphs)
    # one shared bucket + a deadline far beyond the submit loop: the
    # flusher must form a full batch rather than decode singletons
    with AsyncPlacementServer(batch_slots=4, max_delay_ms=2000.0,
                              size_granularity=64) as server:
        server.register(fitted_session)
        futs = [server.submit(g) for g in graphs[:4]]
        out = [f.result(timeout=300) for f in futs]
        assert server.batches_full >= 1
        assert server.batches_deadline == 0
    for g, p in zip(graphs, out):
        assert p.shape == (g.num_nodes,)


def test_async_server_place_many_and_default_tenant(fitted_session):
    graphs = list(fitted_session.graphs)
    with AsyncPlacementServer(batch_slots=2, max_delay_ms=1.0) as server:
        with pytest.raises(ValueError, match="tenant= is required"):
            server.submit(graphs[0])          # zero tenants registered
        server.register(fitted_session)
        out = server.place_many(graphs)       # single tenant: no tenant=
        svc = PlacementService(fitted_session, batch_slots=2)
        for g, p in zip(graphs, out):
            np.testing.assert_array_equal(p, svc.place(g))
        mixed = server.place_many([graphs[0], _oov_graph()],
                                  return_exceptions=True)
        np.testing.assert_array_equal(mixed[0], out[0])
        assert isinstance(mixed[1], ValueError)
        with pytest.raises(ValueError, match="Softmax"):
            server.place_many([graphs[0], _oov_graph()])
        with pytest.raises(KeyError, match="unknown tenant"):
            server.submit(graphs[0], tenant="nope")


@pytest.mark.slow
def test_async_server_multi_tenant_registry(fitted_session):
    """Two policies behind one server: spec-hash tenant ids, independent
    decodes, recompiles ≤ distinct (tenant, bucket) pairs."""
    other = PlacementSession(PlacementSpec(
        workload=WL, mode="corpus", config=_cfg(hidden_channel=8),
        max_buckets=2, graphs_per_episode=2))
    other.fit()
    graphs = list(fitted_session.graphs)
    with AsyncPlacementServer(batch_slots=2, max_delay_ms=1.0,
                              size_granularity=64) as server:
        t_a = server.register(fitted_session)
        t_b = server.register(other)
        assert t_a == fitted_session.spec.spec_hash()
        assert t_b == other.spec.spec_hash()
        assert t_a != t_b
        # idempotent re-register
        assert server.register(fitted_session) == t_a
        assert server.tenants() == [t_a, t_b]

        out_a = server.place_many(graphs, tenant=t_a)
        out_b = server.place_many(graphs, tenant=t_b)
        svc_a = PlacementService(fitted_session, batch_slots=2,
                                 size_granularity=64)
        svc_b = PlacementService(other, batch_slots=2, size_granularity=64)
        for g, pa, pb in zip(graphs, out_a, out_b):
            np.testing.assert_array_equal(pa, svc_a.place(g))
            np.testing.assert_array_equal(pb, svc_b.place(g))

        stats = server.stats()
        assert stats["tenants"] == 2
        assert stats["requests"] == 2 * len(graphs)
        # at granularity 64 every graph shares one bucket per tenant
        assert stats["recompiles"] <= 2      # ≤ distinct (tenant, bucket)
        assert set(stats["per_tenant"]) == {t_a, t_b}


# ------------------------------------------------------------- AOT cache
def test_aot_cache_unit_roundtrip(tmp_path):
    cache = AotExecutableCache(str(tmp_path / "aot"))
    assert cache.load("h1", (16, 32), 2) is None
    assert cache.stats()["aot_misses"] == 1
    cache.store("h1", (16, 32), 2, b"blob-a")
    cache.store("h1", (32, 32), 2, b"blob-b")
    cache.store("h2", (16, 32), 2, b"blob-c")
    assert cache.load("h1", (16, 32), 2) == b"blob-a"
    # batch_slots is part of the key: a different decode width misses
    assert cache.load("h1", (16, 32), 4) is None
    assert len(cache.entries()) == 3
    assert len(cache.entries("h1")) == 2
    assert cache.clear("h1") == 2
    assert cache.entries("h1") == []
    assert cache.load("h1", (16, 32), 2) is None


def test_aot_fresh_engine_serves_without_tracing(fitted_session, tmp_path):
    """Same process, fresh engine: 0 traces, decodes bitwise equal."""
    graphs = list(fitted_session.graphs)
    aot = AotExecutableCache(str(tmp_path / "aot"))
    warm = PlacementService(fitted_session, batch_slots=2,
                            size_granularity=32, aot_cache=aot)
    expected = warm.place_many(graphs)
    assert warm.stats()["aot_stores"] == len(warm.shape_keys_seen) > 0

    fresh = PlacementService(fitted_session, batch_slots=2,
                             size_granularity=32, aot_cache=aot)
    got = fresh.place_many(graphs)
    assert len(fresh.shape_keys_seen) == 0           # zero traces
    assert fresh.aot_decodes > 0
    assert fresh.stats()["aot_hits"] == warm.stats()["aot_stores"]
    for a, b in zip(expected, got):
        np.testing.assert_array_equal(a, b)


def test_aot_corrupt_blob_falls_back_to_trace(fitted_session, tmp_path):
    graphs = list(fitted_session.graphs)
    aot = AotExecutableCache(str(tmp_path / "aot"))
    warm = PlacementService(fitted_session, batch_slots=2,
                            size_granularity=32, aot_cache=aot)
    expected = warm.place_many(graphs)
    for rel in aot.entries():                        # poison every blob
        with open(os.path.join(aot.directory, rel), "wb") as f:
            f.write(b"not a jax export")
    fresh_cache = AotExecutableCache(aot.directory)
    fresh = PlacementService(fitted_session, batch_slots=2,
                             size_granularity=32, aot_cache=fresh_cache)
    got = fresh.place_many(graphs)                   # must not crash
    for a, b in zip(expected, got):
        np.testing.assert_array_equal(a, b)
    stats = fresh.stats()
    assert stats["aot_load_failures"] == len(fresh.shape_keys_seen) > 0
    assert stats["aot_stores"] > 0                   # bad blobs overwritten


def test_aot_blob_for_another_device_is_a_miss(fitted_session, tmp_path):
    """Blobs are keyed by platform and device kind: a directory written
    under another device's key is a miss here — the service traces and
    stores its own executables instead of failing requests."""
    graphs = list(fitted_session.graphs)
    aot_dir = str(tmp_path / "aot")
    other = AotExecutableCache(aot_dir)
    other.device_key = "tpu-TPU-v5-lite"
    warm = PlacementService(fitted_session, batch_slots=2,
                            size_granularity=32, aot_cache=other)
    expected = warm.place_many(graphs)
    stored = other.stats()["aot_stores"]
    assert stored > 0

    here = AotExecutableCache(aot_dir)
    assert here.device_key == "cpu-cpu"
    fresh = PlacementService(fitted_session, batch_slots=2,
                             size_granularity=32, aot_cache=here)
    got = fresh.place_many(graphs)
    for a, b in zip(expected, got):
        np.testing.assert_array_equal(a, b)
    stats = fresh.stats()
    assert stats["aot_hits"] == 0 and stats["aot_load_failures"] == 0
    assert stats["aot_misses"] == len(fresh.shape_keys_seen) == stored
    assert stats["aot_stores"] == stored
    assert len(here.entries()) == 2 * stored


def test_preload_rejects_executable_for_another_platform():
    import jax
    import jax.numpy as jnp
    from jax import export
    from repro.core.sim.rollout import DynamicRolloutEngine
    blob = export.export(jax.jit(lambda x: x + 1), platforms=("tpu",))(
        jax.ShapeDtypeStruct((2,), jnp.float32)).serialize()
    with pytest.raises(ValueError, match="exported for"):
        DynamicRolloutEngine(None, HSDAGConfig()).preload_greedy(blob)


_FRESH_PROCESS_SCRIPT = textwrap.dedent("""
    import sys, numpy as np
    from repro.api import PlacementService
    ckpt, aot_dir, expected_npz = sys.argv[1:4]
    service = PlacementService(ckpt, batch_slots=2, size_granularity=32,
                               aot_cache=aot_dir)
    data = np.load(expected_npz, allow_pickle=True)
    from repro.graphs import build_corpus
    graphs = build_corpus(str(data["workload"]))
    got = service.place_many(graphs)
    assert len(service.shape_keys_seen) == 0, (
        "fresh process traced %d shapes" % len(service.shape_keys_seen))
    assert service.aot_decodes > 0
    assert service.stats()["aot_hits"] > 0
    for i, p in enumerate(got):
        np.testing.assert_array_equal(p, data["p%d" % i])
    print("FRESH_PROCESS_OK traces=0 aot_decodes=%d"
          % service.aot_decodes)
""")


@pytest.mark.slow
def test_aot_fresh_process_zero_recompiles(fitted_session, tmp_path):
    """THE acceptance pin: a brand-new OS process serving previously-seen
    (spec_hash, bucket shape) pairs performs zero recompiles and decodes
    bitwise identically."""
    graphs = list(fitted_session.graphs)
    ckpt = str(tmp_path / "policy")
    aot_dir = str(tmp_path / "aot")
    fitted_session.save(ckpt)
    warm = PlacementService(fitted_session, batch_slots=2,
                            size_granularity=32, aot_cache=aot_dir)
    expected = warm.place_many(graphs)
    assert warm.stats()["aot_stores"] > 0

    npz = str(tmp_path / "expected.npz")
    np.savez(npz, workload=WL,
             **{f"p{i}": p for i, p in enumerate(expected)})
    script = str(tmp_path / "fresh.py")
    with open(script, "w") as f:
        f.write(_FRESH_PROCESS_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, script, ckpt, aot_dir, npz],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "FRESH_PROCESS_OK traces=0" in proc.stdout
