"""The program's own scopes and host spans, read from a run's profiler file.

The program names the layers of its window programs with ``jax.named_scope``
(``repro.encoder``, ``repro.gpn``, ``repro.gpn_components`` nested in it,
``repro.head``, ``repro.sim``) and its host steps with
``jax.profiler.TraceAnnotation`` (``repro.rollout``, ``repro.replay``,
``repro.optim.update``).  A scope reaches the profiler file only as the
``op_name`` metadata of the compiled program's instructions, which the file
carries in each program's HLO on its ``/host:metadata`` plane; the spans are
host events on the same clock as the device's operations.

A device operation is one event of ``/device:TPU:0``'s ``XLA Ops`` line (on
the CPU, where there is no device plane, a host event that carries an
``hlo_op`` stat).  Its program is the ``XLA Modules`` event around it (on the
CPU its ``program_id`` stat), and its instruction the event's name up to
" = ".  Scopes are resolved once per distinct (program, instruction) and
matched as tokens anywhere in the ``op_name`` path, so that
``transpose(jvp(repro.encoder))`` counts as ``repro.encoder``.  ``while``,
``conditional`` and ``call`` operations span their bodies: times count leaf
operations only.  The profiler puts the device's clock on the host's only
roughly (the chip's programs can read as starting before the host launched
them), so device times are shifted by the least amount under which no window
program starts before its ``repro.rollout`` or ``repro.replay`` span.

``load(ctx)`` reads the file once per run and keeps the result in ``ctx``.
It takes the file from the one directory under ``<cache>/trace``, which the
benchmark clears before and after each run, and gives ``None`` where there is
not exactly one.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

from . import common
from .trace import merge

WINDOW_PROGRAMS = r"rollout_window|window_loss"
ROLLOUT_PROGRAM = r"rollout_window"
CONTROL_OPCODES = ("while", "conditional", "call")
TRACED_SPAN = "bench.traced"
SPAN_PREFIX = "repro."
LAUNCH_SPANS = ("repro.rollout", "repro.replay")


# ------------------------------------------------------------ protobuf wire
def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, start=0, end=None):
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field."""
    i, end = start, len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(b, span):
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(b, value):
    """A repeated int64 field's value: one varint, or a packed slice."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(b, i)
        out.append(v)
    return out


class Instruction:
    __slots__ = ("name", "opcode", "op_name", "computation", "called")

    def __init__(self, name, opcode, op_name, computation, called):
        self.name, self.opcode, self.op_name = name, opcode, op_name
        self.computation, self.called = computation, called


def parse_hlo(b, start=0, end=None) -> dict:
    """HloProto bytes → {instruction name: Instruction} of its module, each
    with the ``op_name`` it has or, where a compiler pass left it none, the
    one of the instruction that calls its computation (a loop's condition
    belongs to the loop)."""
    out = {}
    for f, module in _fields(b, start, end):
        if f != 1:                                  # HloProto.hlo_module
            continue
        for f2, comp in _fields(b, *module):
            if f2 != 3:                             # .computations
                continue
            comp_id, instrs = None, []
            for f3, v in _fields(b, *comp):
                if f3 == 5:                         # HloComputationProto.id
                    comp_id = v
                elif f3 == 2:                       # .instructions
                    instrs.append(v)
            for span in instrs:
                name = opcode = op_name = ""
                called = []
                for f4, v in _fields(b, *span):
                    if f4 == 1:
                        name = _text(b, v)
                    elif f4 == 2:
                        opcode = _text(b, v)
                    elif f4 == 7:                   # OpMetadata
                        for f5, v5 in _fields(b, *v):
                            if f5 == 2:
                                op_name = _text(b, v5)
                    elif f4 == 38:                  # called_computation_ids
                        called.extend(_ints(b, v))
                out[name] = Instruction(name, opcode, op_name, comp_id,
                                        called)
    caller = {c: ins for ins in out.values() for c in ins.called}
    for ins in out.values():
        seen, at = set(), ins
        while not at.op_name and at.computation in caller \
                and at.computation not in seen:
            seen.add(at.computation)
            at = caller[at.computation]
        ins.op_name = at.op_name
    return out


def programs_from_file(path: str) -> dict:
    """{program id: (module name, {instruction: Instruction})} from the
    HLO each program left on the file's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        b = f.read()
    programs = {}
    for f, plane in _fields(b):
        if f != 1:                                  # XSpace.planes
            continue
        parts = list(_fields(b, *plane))
        names = [v for f2, v in parts if f2 == 2]
        if not names or _text(b, names[0]) != "/host:metadata":
            continue
        stat_names = {}
        for f2, entry in parts:
            if f2 != 5:                             # XPlane.stat_metadata
                continue
            for f3, meta in _fields(b, *entry):
                if f3 == 2:
                    md = dict(_fields(b, *meta))
                    stat_names[md.get(1)] = _text(b, md.get(2, (0, 0)))
        for f2, entry in parts:
            if f2 != 4:                             # XPlane.event_metadata
                continue
            for f3, meta in _fields(b, *entry):
                if f3 != 2:
                    continue
                pid, name, hlo = None, "", None
                for f4, v in _fields(b, *meta):
                    if f4 == 1:
                        pid = v
                    elif f4 == 2:
                        name = _text(b, v)
                    elif f4 == 5:                   # XStat
                        stat = dict(_fields(b, *v))
                        if (stat_names.get(stat.get(1), "").lower()
                                .replace(" ", "_") == "hlo_proto"
                                and 6 in stat):
                            hlo = stat[6]
                if pid is not None and hlo is not None:
                    programs[pid] = (name, parse_hlo(b, *hlo))
    return programs


# ------------------------------------------------------------------ reading
def _token(scope: str):
    return re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")


class ProgramTrace:
    """Device operations with their program and scopes, and the host spans,
    on the profiler's clock (ns)."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        self.programs = programs_from_file(path)
        data = ProfileData.from_file(path)
        planes = list(data.planes)
        device = [p for p in planes if p.name == "/device:TPU:0"]
        self.spans = {}
        self.window = None
        self.clock_offset_ns = 0.0
        # One table row per distinct (program, instruction); ``kind`` holds
        # each operation's row.
        self._index, self.table = {}, []
        kinds, starts, durs = [], [], []
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    n = ev.name
                    if n.startswith(SPAN_PREFIX) or n == TRACED_SPAN:
                        s = float(ev.start_ns)
                        self.spans.setdefault(n, []).append(
                            (s, s + float(ev.duration_ns)))
                    elif not device:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            kinds.append(self._kind(stats.get("program_id"),
                                                    stats["hlo_op"]))
                            starts.append(float(ev.start_ns))
                            durs.append(float(ev.duration_ns))
        if device:
            kinds, starts, durs = self._device_ops(device[0])
        if TRACED_SPAN in self.spans:
            self.window = self.spans.pop(TRACED_SPAN)[0]
        self.spans = {k: np.asarray(v, np.float64).reshape(-1, 2)
                      for k, v in self.spans.items()}
        self.kind = np.asarray(kinds, np.int64)
        self.start = np.asarray(starts, np.float64) + self.clock_offset_ns
        self.end = self.start + np.asarray(durs, np.float64)

    def _kind(self, pid, op):
        key = (pid, op)
        k = self._index.get(key)
        if k is None:
            k = self._index[key] = len(self.table)
            self.table.append(self._resolve(pid, op))
        return k

    def _device_ops(self, plane):
        """Rows, starts and durations of the ``XLA Ops`` events, each in
        the program of the ``XLA Modules`` event around it."""
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((float(ev.start_ns), float(ev.duration_ns), ev.name)
                      for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        mod_start = [m[0] for m in mods]
        mod_end = [m[0] + m[1] for m in mods]
        mod_id = [_program_id(m[2]) for m in mods]
        self.clock_offset_ns = self._launch_offset(
            [m[0] for m in mods if re.search(WINDOW_PROGRAMS, m[2])])
        kinds, starts, durs = [], [], []
        by_name = {}
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s = float(ev.start_ns)
            m = bisect.bisect_right(mod_start, s) - 1
            pid = mod_id[m] if m >= 0 and s <= mod_end[m] else None
            name = ev.name
            k = by_name.get((pid, name))
            if k is None:
                k = by_name[(pid, name)] = self._kind(
                    pid, name.split(" = ", 1)[0].lstrip("%"))
            kinds.append(k)
            starts.append(s)
            durs.append(float(ev.duration_ns))
        return kinds, starts, durs

    def _launch_offset(self, program_starts) -> float:
        """The least shift of the device's clock onto the host's under
        which no window program starts before the host span that launched
        it (the i-th program with the i-th launch span).  The profiler
        aligns the two clocks only roughly; 0 where nothing shows a lag."""
        launches = sorted(s for n in LAUNCH_SPANS
                          for s, _ in self.spans.get(n, []))
        if not launches or len(launches) != len(program_starts):
            return 0.0
        return max(0.0, max(h - d for h, d in zip(launches, program_starts)))

    def _resolve(self, pid, op):
        name, instrs = self.programs.get(pid, ("", {}))
        ins = instrs.get(op)
        return dict(program=name, op=op, instruction=ins, pid=pid,
                    opcode=ins.opcode if ins is not None else "",
                    op_name=ins.op_name if ins is not None else "")

    # ------------------------------------------------------------ selections
    def _rows(self, test) -> np.ndarray:
        """Mask over events whose table row passes ``test``."""
        hit = np.array([bool(test(r)) for r in self.table], bool)
        return hit[self.kind] if len(self.kind) else np.zeros(0, bool)

    def leaf(self, programs: str = WINDOW_PROGRAMS, scope=None):
        """Mask of leaf operations of the matching programs, within
        ``scope`` if given."""
        rx = re.compile(programs)
        tok = _token(scope) if scope else None
        return self._rows(lambda r: r["opcode"] not in CONTROL_OPCODES
                          and rx.search(r["program"])
                          and (tok is None or tok.search(r["op_name"])))

    def busy_ns(self, mask) -> float:
        iv = merge(np.stack([self.start[mask], self.end[mask]], axis=1))
        return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0

    def scope_ns(self, scope: str, programs: str = WINDOW_PROGRAMS) -> float:
        """Device time (union of intervals) of the scope's leaf operations
        in the matching programs."""
        return self.busy_ns(self.leaf(programs, scope))

    # ---------------------------------------------------------------- rounds
    def loop_passes(self, scope: str, programs: str = ROLLOUT_PROGRAM):
        """(loop executions, body executions) of the ``while`` loops of the
        scope in the matching programs.  A body's executions are those of
        the most often run instruction of its computation."""
        rx, tok = re.compile(programs), _token(scope)
        counts = np.bincount(self.kind, minlength=len(self.table))
        per_body, loops = {}, 0
        for k, r in enumerate(self.table):
            ins = r["instruction"]
            if (ins is None or not rx.search(r["program"])
                    or r["opcode"] != "while" or not tok.search(r["op_name"])):
                continue
            loops += int(counts[k])
            body = ins.called[0] if ins.called else None
            per_body[(r["pid"], body)] = 0
        for k, r in enumerate(self.table):
            ins = r["instruction"]
            if ins is None:
                continue
            key = (r["pid"], ins.computation)
            if key in per_body:
                per_body[key] = max(per_body[key], int(counts[k]))
        return loops, sum(per_body.values())

    # ------------------------------------------------------------------ idle
    def idle(self) -> np.ndarray:
        """Intervals of the traced window in which no device operation
        ran."""
        if self.window is None:
            return np.zeros((0, 2))
        t0, t1 = self.window
        busy = merge(np.stack([self.start, self.end], axis=1))
        busy = busy[(busy[:, 1] > t0) & (busy[:, 0] < t1)] if len(busy) \
            else busy
        edges = np.concatenate([[t0], np.clip(busy.ravel(), t0, t1), [t1]])
        gaps = edges.reshape(-1, 2)
        return gaps[gaps[:, 1] > gaps[:, 0]]

    def idle_in_ns(self, span_names) -> float:
        """Idle time inside any of the named host spans."""
        spans = [self.spans[n] for n in span_names if n in self.spans]
        if not spans:
            return 0.0
        return _overlap_ns(self.idle(), merge(np.concatenate(spans)))


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval sets."""
    if not len(a) or not len(b):
        return 0.0
    lengths = a[:, 1] - a[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lengths)])

    def measure_upto(t):            # |a ∩ (-inf, t]|
        i = np.searchsorted(a[:, 0], t, side="right") - 1
        inside = np.clip(t - a[np.maximum(i, 0), 0], 0.0,
                         lengths[np.maximum(i, 0)])
        return np.where(i >= 0, before[np.maximum(i, 0)] + inside, 0.0)

    return float(np.sum(measure_upto(b[:, 1]) - measure_upto(b[:, 0])))


_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


def _program_id(module_event_name: str):
    m = _PROGRAM_ID.search(module_event_name)
    return int(m.group(1)) if m else None


def trace_file():
    """The profiler file of this run, or None where there is not exactly
    one run directory under ``<cache>/trace``."""
    runs = glob.glob(os.path.join(common.CACHE, "trace", "*", ""))
    if len(runs) != 1:
        return None
    paths = sorted(glob.glob(os.path.join(runs[0], "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(ctx):
    """The run's ProgramTrace, read once and kept in ``ctx``; None where
    the file cannot be found."""
    if "program_trace" not in ctx:
        path = trace_file()
        ctx["program_trace"] = ProgramTrace(path) if path else None
    return ctx["program_trace"]
