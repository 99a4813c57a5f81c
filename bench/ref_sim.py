"""Plain reference of the makespan cost model, in NumPy.

The list scheduler the placement system scores with: every op runs on the
device its placement names, in one fixed topological order (Kahn's, smallest
node id first); an op starts once all its inputs have arrived and one of its
device's queues is free; a cross-device input pays bytes / link bandwidth +
link latency; "data" ops (weights, inputs, converts) cost nothing and their
outputs need no transfer; the makespan is the latest finish.  A placement
whose resident bytes overflow a device scores reward 0.

It reads only the graph and the platform description and shares no code with
the program.  Placements are scored in a batch: the loop runs over nodes and
every step is vectorised over placements.  ``dtype`` sets the arithmetic:
float64 for the reference, ``ml_dtypes.bfloat16`` for the control.
"""
from __future__ import annotations

import heapq

import numpy as np

DATA_OPS = ("Const", "Parameter", "Convert")
CLASS_OF = {"Convolution": "conv", "conv_general_dilated": "conv",
            "MatMul": "gemm", "Gemm": "gemm", "dot_general": "gemm"}


def op_class(op_type: str) -> str:
    return "data" if op_type in DATA_OPS else CLASS_OF.get(op_type, "eltwise")


def topo_order(num_nodes: int, edges: np.ndarray) -> np.ndarray:
    indeg = np.zeros(num_nodes, np.int64)
    succ = [[] for _ in range(num_nodes)]
    for s, d in edges:
        succ[int(s)].append(int(d))
        indeg[int(d)] += 1
    ready = [v for v in range(num_nodes) if indeg[v] == 0]
    heapq.heapify(ready)
    order = []
    while ready:
        v = heapq.heappop(ready)
        order.append(v)
        for w in succ[v]:
            indeg[w] -= 1
            if indeg[w] == 0:
                heapq.heappush(ready, w)
    if len(order) != num_nodes:
        raise ValueError("graph has a cycle")
    return np.asarray(order, np.int64)


def _device_eff(dev, cls: str, out_elems: float, meta) -> float:
    if meta and f"eff_{dev.kind}" in meta:
        return float(meta[f"eff_{dev.kind}"])
    eff = dict(dev.efficiency).get(cls, 1.0)
    if dev.util_ramp_elems > 0 and cls in ("conv", "gemm") and out_elems > 0:
        eff *= min(1.0, out_elems / dev.util_ramp_elems)
    return eff


class RefScheduler:
    """Scores (N, V) placements of one graph on one platform."""

    def __init__(self, graph, platform, dtype=np.float64):
        self.dtype = dtype
        n = graph.num_nodes
        edges = np.asarray(graph.edges, np.int64).reshape(-1, 2)
        self.order = topo_order(n, edges)
        self.preds = [[] for _ in range(n)]
        for s, d in edges:
            self.preds[int(d)].append(int(s))
        nodes = graph.nodes
        self.classes = [op_class(nd.op_type) for nd in nodes]
        self.is_data = np.asarray([c == "data" for c in self.classes])
        flops = np.asarray([nd.flops for nd in nodes], np.float64)
        byts = np.asarray([nd.bytes_out for nd in nodes], np.float64)
        devs = platform.devices
        op_time = np.zeros((len(devs), n))
        for d, dev in enumerate(devs):
            per_class = dict(dev.dispatch_per_class)
            for v, nd in enumerate(nodes):
                cls = self.classes[v]
                if cls == "data":
                    continue
                eff = _device_eff(dev, cls, byts[v] / 4.0, nd.meta)
                op_time[d, v] = (max(flops[v] / (dev.peak_flops * eff),
                                     byts[v] / dev.mem_bw)
                                 + per_class.get(cls, dev.dispatch_overhead))
        self.op_time = op_time.astype(dtype)
        self.bytes = byts.astype(dtype)
        bw = np.asarray(platform.link_bw, np.float64)
        inv_bw = np.where(np.isfinite(bw), 1.0 / bw, 0.0)
        np.fill_diagonal(inv_bw, 0.0)
        self.inv_bw = inv_bw.astype(dtype)
        self.lat = np.asarray(platform.link_latency, np.float64).astype(dtype)
        self.capacity = np.asarray([dv.mem_capacity for dv in devs])
        self.queues = [max(1, dv.parallel_queues) for dv in devs]
        self.byts64 = byts

    def latency(self, placements) -> np.ndarray:
        """(N, V) device ids → (N,) makespans in float64 (inf on OOM-free
        graphs never occurs; OOM is reported by :meth:`oom`)."""
        p = np.asarray(placements, np.int64)
        num, n = p.shape
        dt = self.dtype
        ndev, qmax = len(self.queues), max(self.queues)
        queues = np.full((num, ndev, qmax), np.inf, dt)
        for d, q in enumerate(self.queues):
            queues[:, d, :q] = 0
        finish = np.zeros((num, n), dt)
        rows = np.arange(num)
        for v in self.order:
            if self.is_data[v]:
                continue
            d = p[:, v]
            ready = np.zeros(num, dt)
            for u in self.preds[v]:
                t = finish[:, u]
                if not self.is_data[u]:
                    du = p[:, u]
                    tx = (self.bytes[u] * self.inv_bw[du, d]
                          + self.lat[du, d]).astype(dt)
                    t = np.where(du != d, (t + tx).astype(dt), t)
                ready = np.maximum(ready, t)
            qrow = queues[rows, d]                           # (N, Q)
            q = np.argmin(qrow, axis=1)
            start = np.maximum(ready, qrow[rows, q])
            fin = (start + self.op_time[d, v]).astype(dt)
            finish[:, v] = fin
            queues[rows, d, q] = fin
        return finish.max(axis=1).astype(np.float64) if n else np.zeros(num)

    def oom(self, placements) -> np.ndarray:
        p = np.asarray(placements, np.int64)
        ndev = len(self.queues)
        dev_bytes = np.stack([(self.byts64[None] * (p == d)).sum(1)
                              for d in range(ndev)], axis=1)
        return np.any(dev_bytes > self.capacity[None], axis=1)

    def reward(self, placements) -> np.ndarray:
        lat = self.latency(placements)
        bad = self.oom(placements) | ~np.isfinite(lat)
        return np.where(bad, 0.0, 1.0 / np.where(bad, 1.0, lat)), lat
