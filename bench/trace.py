"""Reduce a profiler trace (``*.xplane.pb``) of a traced window to numbers.

The traced window is the benchmark's own host span ``bench.window``.
Device operations are the events of each device plane's "XLA Ops" line,
clipped to that window.  From them:

* ``busy_s`` — per device, the length of the union of op intervals;
  averaged over the devices (``window_s`` is the span's length);
* ``layer_s(kinds, patterns, exclude)`` — per device, the summed self
  time of the ops selected by HLO opcode and by regular expressions on the
  instruction's text (a TPU trace names each op by its HLO instruction,
  operands included), averaged over devices.  Self time leaves out the
  ops nested inside an op, as a ``while`` loop holds its body's ops;
* ``breakdown()`` — the ten ops that took most self time, and the idle
  time grouped by the host event open on the benchmark's thread at the
  middle of each gap.

Every time here is in seconds per device.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable, Sequence

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"

Interval = tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str
    start: float      # seconds on the trace's clock
    end: float
    text: str = ""    # the event's name: on a TPU, the HLO instruction
    kind: str = ""    # the HLO opcode (copy, custom-call, while, ...)
    self_s: float = 0.0   # duration less the ops nested inside it


@dataclasses.dataclass
class Reduced:
    window: Interval
    devices: list[list[Op]]               # per device plane, in the window
    host: list[Op]                        # events of the benchmark's thread
    _starts: list[float] | None = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(_length(_union((o.start, o.end) for o in ops))
                   for ops in self.devices) / len(self.devices)

    def select(self, kinds: str, patterns: Sequence[str] = (),
               exclude: Sequence[str] = ()) -> list[list[Op]]:
        """Per device, the ops whose opcode matches ``kinds`` (a regular
        expression matched whole), whose text matches every one of
        ``patterns`` and none of ``exclude``."""
        match = _matcher(kinds, patterns, exclude)
        return [[o for o in ops if match(o)] for ops in self.devices]

    def layer_s(self, kinds: str, patterns: Sequence[str] = (),
                exclude: Sequence[str] = ()) -> float:
        """Self time of the selected ops, per device."""
        if not self.devices:
            return 0.0
        return sum(o.self_s for ops in self.select(kinds, patterns, exclude)
                   for o in ops) / len(self.devices)

    def gaps(self) -> list[list[Interval]]:
        """Per device, the idle intervals inside the window."""
        out = []
        for ops in self.devices:
            busy = _union((o.start, o.end) for o in ops)
            out.append(_subtract([self.window], busy))
        return out

    def breakdown(self, top: int = 10) -> dict:
        n = max(len(self.devices), 1)
        by_op: dict[str, float] = {}
        for ops in self.devices:
            for o in ops:
                key = f"{o.name} {o.kind}"
                by_op[key] = by_op.get(key, 0.0) + o.self_s
        device_ops = sorted(((k, v / n) for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:top]
        by_host: dict[str, float] = {}
        for gaps in self.gaps():
            for a, b in gaps:
                label = self.host_at((a + b) / 2)
                by_host[label] = by_host.get(label, 0.0) + (b - a)
        idle = sorted(((k, v / n) for k, v in by_host.items()),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": [[k, v] for k, v in idle]}

    def host_at(self, t: float) -> str:
        """Name of the innermost host event open at ``t``: of the events
        that started by ``t``, the latest one still open (host events on
        one thread nest)."""
        if self._starts is None:
            self.host.sort(key=lambda o: o.start)
            self._starts = [o.start for o in self.host]
        for i in range(bisect.bisect_right(self._starts, t) - 1, -1, -1):
            if self.host[i].end > t:
                return self.host[i].name
        return "(no host event)"


def _union(intervals: Iterable[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _subtract(base: list[Interval], cut: list[Interval]) -> list[Interval]:
    """``base`` minus ``cut``; both sorted and disjoint."""
    out = []
    j = 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        cur, i = a, j
        while i < len(cut) and cut[i][0] < b:
            c, d = cut[i]
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            i += 1
        if cur < b:
            out.append((cur, b))
    return out


def _length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _matcher(kinds: str, patterns: Sequence[str], exclude: Sequence[str]):
    k = re.compile(kinds)
    inc = [re.compile(p) for p in patterns]
    exc = [re.compile(p) for p in exclude]
    return lambda o: (k.fullmatch(o.kind) is not None
                      and all(r.search(o.text) for r in inc)
                      and not any(r.search(o.text) for r in exc))


_NAME = re.compile(r"^%?([\w.\-]+)")
_BRACKETS = re.compile(r"\{[^{}]*\}|\[[^\[\]]*\]")
_OPCODE = re.compile(r"([a-z][\w\-]*)\(")


def parse_hlo(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an HLO instruction's text, such as
    ``%copy.4 = f32[8,128]{1,0:T(8,128)} copy(f32[8,128]{0,1} %x)``; a
    text that is no HLO instruction is its own name, of kind ``""``."""
    if " = " not in text:
        return text, ""
    name = _NAME.match(text)
    rhs = text.split(" = ", 1)[1]
    prev = None
    while prev != rhs:                   # strip nested {...} and [...]
        prev, rhs = rhs, _BRACKETS.sub("", rhs)
    kind = _OPCODE.search(rhs)
    return (name.group(1) if name else text), (kind.group(1) if kind else "")


def _nest(ops: list[Op]) -> list[Op]:
    """Sort ``ops`` and set each one's self time: its duration less that
    of the ops nested directly inside it (a loop holds its body's ops)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        o.self_s = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_s -= o.end - o.start
        stack.append(o)
    return ops


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str) -> Reduced:
    """Read ``path`` and keep what lies inside the ``bench.window`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    host: list[Op] = []
    devices: list[tuple[str, list[Op]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Op(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                win = [e for e in evs if e.name == WINDOW]
                if win:
                    window = (win[0].start, win[0].end)
                    host = evs
        elif plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, kind = parse_hlo(e.name)
                    ops.append(Op(name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  e.name, kind))
            if ops:
                devices.append((plane.name, ops))
    if window is None:
        raise ValueError(f"no {WINDOW!r} host span in {path}")
    a, b = window
    clipped = []
    for _, ops in sorted(devices, key=lambda d: d[0]):
        clipped.append(_nest([Op(o.name, max(o.start, a), min(o.end, b),
                                 o.text, o.kind)
                              for o in ops if o.end > a and o.start < b]))
    host = [o for o in host if o.end > a and o.start < b]
    return Reduced(window=window, devices=clipped, host=host)
