"""Serve cells: tenants asking an ``AsyncPlacementServer`` where to place the
graphs they serve, as an open loop.

Set-up makes one policy from the seed, saves it as a checkpoint and
registers the checkpoint with a server whose AOT executable directory sits
in the checkout (so only a checkout's first run traces), then decodes each
variant once to warm every bucket.  The window submits requests at the
times the traffic file's generator fixes, from one thread, whether or not
earlier ones have finished; each request's latency runs from the moment it
was due to the moment its placement was ready.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time

import numpy as np

from . import arrivals, common


def build_variants(config: dict, traffic: dict):
    """The configuration's graph at each of the traffic's (layers, seq_len)
    sizes; the configuration's fleet is checked against its policy."""
    common.build_platform(config)
    return [common.build_graph(config, dict(layers=layers, seq_len=seq_len))
            for layers, seq_len in traffic["variants"]]


class ServeRun:
    def __init__(self, cell_name: str, cell: dict, config: dict,
                 traffic: dict):
        from repro.core.features import shared_feature_config
        self.cell, self.config, self.traffic = cell, config, traffic
        self.variants = build_variants(config, traffic)
        self.fc = shared_feature_config(self.variants)
        self.dir = os.path.join(common.CACHE, "serve", cell_name)
        self.server = None

    def start(self, seed: int):
        """Policy from the seed → checkpoint → a fresh server → warm."""
        import jax
        from repro.api import (AotExecutableCache, AsyncPlacementServer,
                               PlacementSession, PlacementSpec)
        from repro.core import HSDAG, HSDAGConfig
        from repro.core.features import extract_features
        from . import ref_policy

        self.close()
        key, self.rng = common.seed_key(seed)
        policy = self.config["policy"]
        probe = extract_features(self.variants[0], self.fc)
        self.params = jax.jit(lambda k: ref_policy.init_params(
            k, probe.x.shape[1], policy))(key)
        spec = PlacementSpec(
            workload=f"benchmark:names={self.config['graph']['builder']}",
            mode="search", config=HSDAGConfig(**policy))
        session = PlacementSession(spec)
        trainer = HSDAG(spec.resolved_config())
        trainer.init(key, probe)
        trainer.params = self.params
        trainer.feature_config = self.fc
        session.trainer = trainer
        ckpt = os.path.join(self.dir, "policy")
        shutil.rmtree(ckpt, ignore_errors=True)
        session.save(ckpt)
        t = self.traffic
        self.server = AsyncPlacementServer(
            batch_slots=t["batch_slots"], max_delay_ms=t["max_delay_ms"],
            size_granularity=t["size_granularity"],
            aot_cache=AotExecutableCache(os.path.join(self.dir, "aot")))
        self.tenant = self.server.register(ckpt)
        for g in self.variants:
            self.server.place(g, tenant=self.tenant, timeout=600)

    def close(self):
        if self.server is not None:
            self.server.close(timeout=120)
            self.server = None

    def window(self, seed: int, rate: float, seconds: float, tracer=None,
               trace_seconds: float = 0.0):
        """Open-loop window → dict of per-request times and counters."""
        t = self.traffic
        due, picks = arrivals.open_loop(seed, rate, seconds,
                                        len(self.variants), t["zipf_s"])
        n = len(due)
        done = np.full(n, np.nan)
        lateness = np.zeros(n)
        futures = [None] * n
        before = self.server.stats()
        lock = threading.Lock()

        def finished(i):
            def cb(_f):
                now = time.perf_counter()
                with lock:
                    done[i] = now
            return cb

        import jax
        ann = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        if tracer is not None:
            tracer.start()
        tracing = tracer is not None
        for i in range(n):
            target = t0 + due[i]
            now = time.perf_counter()
            if now < target:
                with ann("bench.until_due"):
                    time.sleep(target - now)
            if tracing and time.perf_counter() - t0 >= trace_seconds:
                tracer.stop(seconds=trace_seconds)
                tracing = False
            lateness[i] = time.perf_counter() - target
            with ann("bench.submit"):
                f = self.server.submit(self.variants[picks[i]],
                                       tenant=self.tenant)
            futures[i] = f
            f.add_done_callback(finished(i))
        if tracing:
            tracer.stop(seconds=time.perf_counter() - t0)
        deadline = time.perf_counter() + t["drain_s"]
        placements = [None] * n
        for i, f in enumerate(futures):
            try:
                placements[i] = f.result(
                    timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:       # noqa: BLE001 — a failed request counts
                placements[i] = None
        after = self.server.stats()
        latency = (done - (t0 + due)) * 1e3
        return dict(due=due, picks=picks, latency_ms=latency,
                    lateness_ms=lateness * 1e3, placements=placements,
                    before=before, after=after, span_s=seconds)


def percentile(latency_ms, q):
    """q-th percentile over every request; a failed one counts as missing
    any limit (+inf)."""
    lat = np.where(np.isfinite(latency_ms), latency_ms, np.inf)
    return float(np.percentile(lat, q, method="higher"))


def run(cell, config, traffic, args, *, devices, peaks, trace_dir, t_start):
    from . import compare_serve
    from .trace import Tracer

    serve = ServeRun(args.workload, cell, config, traffic)
    try:
        serve.start(args.seed)
        setup_s = time.perf_counter() - t_start
        tracer = Tracer(trace_dir) if trace_dir else None
        win = serve.window(args.seed, traffic["rate_per_s"], args.seconds,
                           tracer=tracer,
                           trace_seconds=traffic["trace_seconds"])
        device = common.device_info(devices)
    finally:
        serve.close()
    lat = win["latency_ms"]
    failed = int(np.sum(~np.isfinite(lat)))
    numbers, details = compare_serve.compare(serve, win, args.seed)
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    b, a = win["before"], win["after"]
    batches = ((a["batches_full"] - b["batches_full"])
               + (a["batches_deadline"] - b["batches_deadline"]))
    served = a["requests"] - b["requests"]
    out = dict(attempted=len(lat), failed=failed, device=device,
               checks=checks,
               metrics={"serve_p50_ms": {"value": percentile(lat, 50),
                                         "unit": "ms"},
                        "serve_p95_ms": {"value": percentile(lat, 95),
                                         "unit": "ms"},
                        "setup_s": {"value": setup_s, "unit": "s"}})
    details.update(generator_late_p95_ms=float(
        np.percentile(win["lateness_ms"], 95)), requests=len(lat),
        batches=batches, recompiles=a["recompiles"] - b["recompiles"])
    if tracer is not None:
        trace = tracer.load()
        busy = trace.busy_s()
        out["device"].update(busy_s=busy, window_s=tracer.window_s)
        out["breakdown"] = trace.breakdown()
        with open(os.path.join(common.CACHE, "last_trace.json"), "w") as f:
            json.dump(dict(summary=trace.summary(), details=details), f)
        out["ctx"] = dict(trace=trace, window_s=tracer.window_s, busy_s=busy,
                          batches=batches, served=served,
                          batch_slots=traffic["batch_slots"],
                          chips=len(devices), peaks=peaks)
    out["details"] = details
    return out
