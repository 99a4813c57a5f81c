"""Profiler capture, and the reduction from a trace to numbers.

A trace is flattened to events ``(plane, line, name, start_ns, dur_ns)``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed operation and their ``XLA Modules`` line one per
program call (named after the jitted function).  The host plane's threads
hold the benchmark's own ``bench.*`` annotations.
"""
from __future__ import annotations

import glob
import os
import re
import time

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    """Starts and stops the profiler around a span of the measured window."""

    def __init__(self, directory: str):
        self.directory = directory
        self.window_s = None
        self.units = 0

    def start(self):
        import jax
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def stop(self, **units):
        import jax
        self.window_s = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.units = units

    def load(self) -> "Trace":
        paths = sorted(glob.glob(os.path.join(
            self.directory, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise RuntimeError(f"no trace written under {self.directory}")
        return Trace(load_events(paths[-1]), self.window_s)


def load_events(path: str):
    """The events the reduction reads: every event of the device planes'
    ``XLA Ops`` and ``XLA Modules`` lines, and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device or name.startswith("bench."):
                    out.append((plane.name, line.name, name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def merge(intervals):
    """Union of (start, end) intervals → sorted disjoint (n, 2) array."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # A new piece starts where an interval begins after all before it ended.
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = reach[np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def covered(intervals) -> float:
    m = merge(intervals)
    return float(np.sum(m[:, 1] - m[:, 0])) if len(m) else 0.0


class Trace:
    def __init__(self, events, window_s: float):
        self.window_s = window_s
        grouped = {}
        for p, ln, n, s, d in events:
            grouped.setdefault((p, ln), []).append((n, s, d))
        # (plane, line) → (names, starts ns, durations ns)
        self.lines = {k: ([n for n, _, _ in v],
                          np.array([s for _, s, _ in v], np.float64),
                          np.array([d for _, _, d in v], np.float64))
                      for k, v in grouped.items()}
        self.devices = sorted({int(DEVICE_PLANE.match(p).group(1))
                               for p, _ in self.lines
                               if DEVICE_PLANE.match(p)})
        self.spans = [(n, s, s + d) for (p, _), (names, st, du)
                      in self.lines.items() if not DEVICE_PLANE.match(p)
                      for n, s, d in zip(names, st, du)
                      if n.startswith("bench.") and n != "bench.traced"]

    def _device(self, dev: int, line: str):
        empty = ([], np.zeros(0), np.zeros(0))
        return self.lines.get((f"/device:TPU:{dev}", line), empty)

    def ops(self, dev: int):
        return self._device(dev, OPS_LINE)

    @staticmethod
    def _intervals(starts, durs, mask=None):
        if mask is not None:
            starts, durs = starts[mask], durs[mask]
        return np.stack([starts, starts + durs], axis=1)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        per = [covered(self._intervals(*self.ops(dev)[1:]))
               for dev in self.devices]
        return sum(per) / len(per) * 1e-9

    def module_time(self, pattern: str):
        """(seconds, calls) of program calls whose name matches, averaged
        over the devices."""
        if not self.devices:
            return 0.0, 0
        rx = re.compile(pattern)
        total, calls = 0.0, 0
        for dev in self.devices:
            names, _, durs = self._device(dev, MODULES_LINE)
            hit = np.array([bool(rx.search(n)) for n in names], bool)
            total += float(np.sum(durs[hit])) if len(names) else 0.0
            calls += int(np.sum(hit))
        n = len(self.devices)
        return total / n * 1e-9, calls // n

    def exposed_s(self, pattern: str) -> float:
        """Seconds in which an operation whose name matches ran while no
        other operation did, averaged over the devices."""
        if not self.devices:
            return 0.0
        rx = re.compile(pattern)
        per = []
        for dev in self.devices:
            names, starts, durs = self.ops(dev)
            hit = np.array([bool(rx.search(n)) for n in names], bool)
            every = self._intervals(starts, durs)
            rest = self._intervals(starts, durs, ~hit)
            per.append(covered(every) - covered(rest))
        return sum(per) / len(per) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps labelled by the innermost benchmark span around them."""
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        names, starts, durs = self.ops(self.devices[0])
        per_name = {}
        for n, d in zip(names, durs):
            n = n.split(" = ")[0]           # "%fusion.3 = f32[...] ..." → name
            per_name[n] = per_name.get(n, 0.0) + d
        top_ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        busy = merge(self._intervals(starts, durs))
        lengths = busy[1:, 0] - busy[:-1, 1]
        labelled = []
        for i in np.argsort(-lengths, kind="stable")[:top]:
            s, e = busy[i, 1], busy[i + 1, 0]
            mid = (s + e) / 2
            around = [(pe - ps, n) for n, ps, pe in self.spans
                      if ps <= mid <= pe]
            label = min(around)[1] if around else "no benchmark span"
            labelled.append([label, float(lengths[i]) * 1e-9])
        return {"device_ops": [[n, float(d) * 1e-9] for n, d in top_ops],
                "idle_gaps": labelled}

    def summary(self) -> dict:
        """Lines read with their event counts, the program calls seen and
        the benchmark spans: for reading a trace by hand."""
        lines = {f"{p} | {ln}": len(v[0]) for (p, ln), v in self.lines.items()}
        modules = sorted({n for (p, ln), (names, _, _) in self.lines.items()
                          if ln == MODULES_LINE for n in names})
        first = self.ops(self.devices[0]) if self.devices else ([], [], [])
        return dict(lines=lines, modules=modules[:50],
                    spans=self.spans[:20],
                    first_ops=[(n, float(s), float(d)) for n, s, d in
                               zip(first[0][:5], first[1][:5], first[2][:5])])
