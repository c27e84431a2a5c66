"""From a profiler trace to the numbers the per-layer readers take.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure: for each device plane (``/device:TPU:<i>``) the events of
its ``XLA Ops`` line — one per operation the device ran — and the
benchmark's own host spans (``dispatch``, ``wait``, ``drain``).  A TPU op
event is named by its HLO text, ``%<name> = <shape> <opcode>(...), kind=...``;
an :class:`Op` keeps the name and a category, ``<opcode>`` or
``fusion/<kind>``.  The line also holds the ``while`` and ``conditional``
ops around the ops of their bodies, so busy time is a union of intervals.
:class:`Trace` clips everything to the traced window (the first
``dispatch`` to the end of ``drain``) and offers the sums the readers need.
Times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

HOST_SPANS = ("dispatch", "wait", "drain")
OPS_LINE = "XLA Ops"
#: ops that only hold other ops of the same line
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def parse(text: str):
    """(name, category) of an op event's HLO text: ``"%fusion.3 = bf16[8]
    fusion(...), kind=kOutput"`` gives ("%fusion.3", "fusion/kOutput")."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    cat = m.group(1) if m else "?"
    if cat == "fusion":
        k = _KIND.search(rest)
        cat += "/" + (k.group(1) if k else "?")
    return name, cat


class Op(NamedTuple):
    name: str
    start: float
    dur: float
    category: str

    @property
    def end(self) -> float:
        return self.start + self.dur


def by_start(op: Op):
    return op.start


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) [^\n]*\{$")
_CALLS = re.compile(r"calls=%?([\w.-]+)")


def matmul_ops(hlo_text: str) -> set:
    """Names (``%name``) of the instructions of a compiled program's HLO
    text that multiply matrices: a ``convolution`` or ``dot``, or a fusion
    whose fused computation holds one."""
    bodies, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip().removeprefix("ROOT "))

    def is_matmul(opcode: str) -> bool:
        return opcode in ("convolution", "dot")

    def holds(comp: str, seen=()) -> bool:
        for line in bodies.get(comp, ()):
            name, cat = parse(line)
            if is_matmul(cat):
                return True
            c = _CALLS.search(line)
            if cat.startswith("fusion") and c and c.group(1) not in seen:
                if holds(c.group(1), seen + (comp,)):
                    return True
        return False

    out = set()
    for lines in bodies.values():
        for line in lines:
            name, cat = parse(line)
            c = _CALLS.search(line)
            if is_matmul(cat) or (cat.startswith("fusion") and c
                                  and holds(c.group(1))):
                out.add(name if name.startswith("%") else "%" + name)
    return out


def load(directory: str) -> dict:
    """The newest ``.xplane.pb`` under ``directory`` as ``{"devices":
    {plane: [Op, ...]}, "host": [Op, ...]}`` (host spans have category
    "host")."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no profiler trace under {directory}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, cat = parse(ev.name)
                    ops.append(Op(name, ev.start_ns, ev.duration_ns, cat))
            devices[plane.name] = sorted(ops, key=by_start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append(Op(ev.name, ev.start_ns, ev.duration_ns,
                                       "host"))
    return {"devices": devices, "host": sorted(host, key=by_start)}


def union(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Trace:
    """A loaded trace clipped to the traced window of ``steps`` steps."""

    def __init__(self, raw: dict, steps: int):
        self.steps = steps
        self.host = raw["host"]
        spans = [h for h in self.host if h.name == "dispatch"]
        drains = [h for h in self.host if h.name == "drain"]
        if not spans or not drains:
            raise RuntimeError("the trace holds no dispatch/drain spans")
        self.t0, self.t1 = spans[0].start, drains[-1].end
        self.devices = {
            name: [o for o in ops if o.end > self.t0 and o.start < self.t1]
            for name, ops in raw["devices"].items() if ops}

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0

    def busy_ns(self) -> float:
        """Busy time averaged over the devices: the union of the intervals
        in which an operation ran, inside the window."""
        if not self.devices:
            return 0.0
        return sum(union((max(o.start, self.t0), min(o.end, self.t1))
                         for o in ops)
                   for ops in self.devices.values()) / len(self.devices)

    def op_ns(self, keep) -> float:
        """Device time of the ops ``keep(op)`` selects, averaged over the
        devices (overlapping ops counted once)."""
        if not self.devices:
            return 0.0
        return sum(union((o.start, o.end) for o in ops if keep(o))
                   for ops in self.devices.values()) / len(self.devices)

    def top_ops(self, n=10):
        """The ``n`` kinds of op that took the most device time, each named
        by its category and its name without the instance number; the
        containers of other ops are left out."""
        tot: dict = {}
        for ops in self.devices.values():
            for o in ops:
                if o.category in CONTAINERS:
                    continue
                key = f"{o.category} {re.sub(r'[.][0-9]+$', '', o.name)}"
                tot[key] = tot.get(key, 0.0) + o.dur
        k = len(self.devices) or 1
        return sorted(((name, t / k) for name, t in tot.items()),
                      key=lambda x: -x[1])[:n]

    def idle_gaps(self, n=10):
        """The ``n`` longest idle gaps of the first device in the window,
        each named by the host span open at its middle."""
        if not self.devices:
            return []
        ops = sorted(next(iter(self.devices.values())), key=by_start)
        gaps, cur = [], self.t0
        for o in ops:
            if o.start > cur:
                gaps.append((cur, o.start))
            cur = max(cur, o.end)
        if self.t1 > cur:
            gaps.append((cur, self.t1))

        def label(mid):
            open_ = [h.name for h in self.host if h.start <= mid <= h.end]
            return open_[-1] if open_ else "none"
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(label((a + b) / 2), b - a) for a, b in gaps[:n]]
