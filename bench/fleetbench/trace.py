"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns JAX's ``.xplane.pb`` into plain events:

- ``device``: ``[chip, op, opcode, scope, start_ns, dur_ns]`` for every
  op on the "XLA Ops" line of each ``/device:TPU:<n>`` plane, where ``op``
  is the HLO instruction's name (``fusion.55``, ``psum.23``), ``opcode``
  its HLO opcode (``fusion``, ``all-reduce``) and ``scope`` its
  ``op_name``, the name stack from the program
  (``jit(...)/while/body/...``).  A TPU op event carries none of these as
  a stat: its name is the instruction's text, and the opcode and
  ``op_name`` are read from the program's HLO, which the trace keeps in
  the ``/host:metadata`` plane, in the program that the "XLA Modules"
  line shows running at the op's start;
- ``host``: ``[name, start_ns, dur_ns]`` for the benchmark's own
  ``TraceAnnotation`` spans (names starting ``bench/``).

``Reduced`` takes those events and the traced window (the
``bench/window`` span) and gives, per chip, the union of op intervals
(busy time), device time per op and per scope pattern, and the longest
idle gaps, each labelled by the innermost host span running at the
time.  An op that holds others (a ``while`` or ``conditional`` around
its body's ops) is counted by its self time, its span less its
children's, so no second is counted twice.  Everything after ``load``
is plain Python over lists, so a small recorded trace checks it.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
WINDOW_SPAN = "bench/window"
#: an op event's name: the instruction's HLO text, ``%<name> = ...``.
OP_TEXT = re.compile(r"^%(\S+) = ")


def load(log_dir: str) -> dict:
    """Plain events from the one ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {paths}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    scopes = hlo_op_names(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    device, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {line.name: line for line in plane.lines}
        if m and OPS_LINE in lines:
            chip = int(m.group(1))
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [s for s, _, _ in mods]
            for e in lines[OPS_LINE].events:
                text = OP_TEXT.match(e.name)
                op = text.group(1) if text else e.name
                k = bisect.bisect_right(starts, e.start_ns) - 1
                names = (scopes.get(mods[k][2], {})
                         if k >= 0 and e.start_ns < mods[k][1] else {})
                scope, opcode = names.get(op, ("", ""))
                device.append([chip, op, opcode, scope, int(e.start_ns),
                               int(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in lines.values():
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


# -- the programs' HLO in the trace -----------------------------------------
# Field numbers of the protobuf messages read (tsl/profiler xplane.proto,
# xla/service/hlo.proto, xla/xla_data.proto).
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_BYTES = 1, 6
XSTAT_METADATA_NAME = 2
HLO_PROTO_MODULE = 1
HLO_MODULE_COMPUTATIONS = 3
HLO_COMPUTATION_INSTRUCTIONS = 2
HLO_INSTRUCTION_NAME, HLO_INSTRUCTION_OPCODE = 1, 2
HLO_INSTRUCTION_METADATA = 7
OP_METADATA_OP_NAME = 2


def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, span=None):
    """``(field number, value)`` of one protobuf message in ``b[span]``:
    an int for a varint, the ``(start, end)`` of a length-delimited
    field, None for a fixed-width one."""
    i, end = span if span else (0, len(b))
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode()


def hlo_op_names(raw: bytes) -> dict:
    """``{program name: {instruction name: (op_name, opcode)}}`` from the
    HLO that a serialized XSpace keeps in its ``/host:metadata`` plane."""
    b = memoryview(raw)
    out = {}
    for f, plane in _fields(b):
        if f != XSPACE_PLANES:
            continue
        name, metas, stat_names = None, [], {}
        for pf, v in _fields(b, plane):
            if pf == XPLANE_NAME:
                name = _text(b, v)
            elif pf == XPLANE_EVENT_METADATA:
                metas.append(v)
            elif pf == XPLANE_STAT_METADATA:
                for ef, ev in _fields(b, v):
                    if ef == MAP_VALUE:
                        d = dict(_fields(b, ev))
                        stat_names[d.get(XSTAT_METADATA_ID)] = (
                            _text(b, d[XSTAT_METADATA_NAME])
                            if XSTAT_METADATA_NAME in d else "")
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            for ef, ev in _fields(b, entry):
                if ef != MAP_VALUE:
                    continue
                program, protos = None, []
                for mf, mv in _fields(b, ev):
                    if mf == XEVENT_METADATA_NAME:
                        program = _text(b, mv)
                    elif mf == XEVENT_METADATA_STATS:
                        st = dict(_fields(b, mv))
                        if (stat_names.get(st.get(XSTAT_METADATA_ID))
                                == HLO_PROTO_STAT and XSTAT_BYTES in st):
                            protos.append(st[XSTAT_BYTES])
                for p in protos:
                    out[program] = _instruction_op_names(b, p)
    return out


def _instruction_op_names(b, proto) -> dict:
    names = {}
    for f, module in _fields(b, proto):
        if f != HLO_PROTO_MODULE:
            continue
        for mf, comp in _fields(b, module):
            if mf != HLO_MODULE_COMPUTATIONS:
                continue
            for cf, ins in _fields(b, comp):
                if cf != HLO_COMPUTATION_INSTRUCTIONS:
                    continue
                inst, opcode, op_name = None, "", ""
                for f2, v in _fields(b, ins):
                    if f2 == HLO_INSTRUCTION_NAME:
                        inst = _text(b, v)
                    elif f2 == HLO_INSTRUCTION_OPCODE:
                        opcode = _text(b, v)
                    elif f2 == HLO_INSTRUCTION_METADATA:
                        for of, ov in _fields(b, v):
                            if of == OP_METADATA_OP_NAME:
                                op_name = _text(b, ov)
                names[inst] = (op_name, opcode)
    return names


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping
    intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Op(NamedTuple):
    """One device op inside the window, clipped to it."""
    name: str
    opcode: str
    scope: str
    start: int
    end: int


def _self_times(ops: list) -> list:
    """Each op's span less the spans of the ops nested directly in it
    (``ops``: the ``Op``s of one chip)."""
    own = [o.end - o.start for o in ops]
    stack = []
    for i in sorted(range(len(ops)),
                    key=lambda i: (ops[i].start, -ops[i].end)):
        s, e = ops[i].start, ops[i].end
        while stack and ops[stack[-1]].end <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]].end) - s
        stack.append(i)
    return own


class Reduced:
    """What a trace says about one traced window."""

    def __init__(self, events: dict):
        host = events["host"]
        windows = [h for h in host if h[0] == WINDOW_SPAN]
        if len(windows) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                               f"{len(windows)}")
        _, w0, wd = windows[0]
        self.w0, self.w1 = w0, w0 + wd
        self.window_s = wd * 1e-9
        self.host = [h for h in host if h[0] != WINDOW_SPAN]
        self.ops: dict[int, list] = collections.defaultdict(list)
        for chip, op, opcode, scope, s, d in events["device"]:
            s, e = max(s, self.w0), min(s + d, self.w1)
            if e > s:
                self.ops[chip].append(Op(op, opcode, scope, s, e))
        self.self_ns = {c: _self_times(ops) for c, ops in self.ops.items()}
        if not self.ops:
            raise RuntimeError("no device op ran inside the traced window")
        self.chips = sorted(self.ops)

    # -- busy and idle -------------------------------------------------------
    def busy_intervals(self, chip: int):
        return _union((o.start, o.end) for o in self.ops[chip])

    def busy_s(self, chip: int) -> float:
        return sum(e - s for s, e in self.busy_intervals(chip)) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(c) for c in self.chips) / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    # -- device time by name -------------------------------------------------
    def seconds_where(self, pred) -> float:
        """Device self seconds of the ops for which ``pred(op)`` holds
        (``op``: an ``Op``), summed over chips and averaged per chip."""
        total = sum(t for c in self.chips
                    for o, t in zip(self.ops[c], self.self_ns[c])
                    if pred(o))
        return total * 1e-9 / len(self.chips)

    def scope_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return self.seconds_where(lambda o: bool(rx.search(o.scope)))

    def top_ops(self, n: int = 10):
        """The ``n`` ops that took most device time (per chip mean), named
        ``op`` plus the last parts of its scope."""
        tot = collections.Counter()
        for c in self.chips:
            for o, t in zip(self.ops[c], self.self_ns[c]):
                tot[label(o.name, o.scope)] += t
        return [[k, v * 1e-9 / len(self.chips)]
                for k, v in tot.most_common(n)]

    # -- idle gaps -----------------------------------------------------------
    def host_label(self, t: int) -> str:
        """The innermost benchmark span running at ``t``."""
        best = None
        for name, s, d in self.host:
            if s <= t < s + d and (best is None or d < best[1]):
                best = (name, d)
        return best[0] if best else "bench/other"

    def idle_gaps(self, n: int = 10, chip: int | None = None):
        """The ``n`` longest idle gaps of ``chip`` (the first by default)
        inside the window, each with the host span at its midpoint."""
        chip = self.chips[0] if chip is None else chip
        gaps, prev = [], self.w0
        for s, e in self.busy_intervals(chip) + [[self.w1, self.w1]]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
        gaps.sort(reverse=True)
        return [[self.host_label((a + b) // 2), d * 1e-9]
                for d, a, b in gaps[:n]]


def label(op: str, scope: str, parts: int = 3) -> str:
    tail = "/".join(p for p in scope.split("/")[-parts:] if p)
    return f"{op} [{tail}]" if tail else op
