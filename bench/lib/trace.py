"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input: the ``.xplane.pb`` the JAX profiler writes (read with
``jax.profiler.ProfileData``, nothing else), and the ids of the cell's
devices.  Device planes are those named ``/device:TPU:<id>``; only the
cell's own are read, so a cell on fewer chips than its host holds
averages in no idle chip.  A device's operations are the events of its
``XLA Ops`` line.  Harness spans are the ``TraceAnnotation`` events of
the host's threads whose names the harness gave (``bench.lib.spans``).

Output (``Reduced``), inside the harness's ``window`` span:
  busy_s        union of the intervals in which an operation ran,
                averaged over the cell's devices
  window_s      length of the traced window
  op_s          per-name sums of operation time, over the cell's
                devices (innermost operations only: a loop's event
                spans its body's)
  ops           (name, start_ns, end_ns, jitted program) of every
                innermost operation, the cell's devices
  gap_s         idle time of the cell's first device attributed to the
                innermost harness span open at each gap's midpoint
  devices       the number of the cell's devices
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
from collections import defaultdict
from types import SimpleNamespace
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
NO_SPAN = "(outside harness spans)"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: dict
    ops: list
    gap_s: dict
    spans: list
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, match, module: str | None = None) -> float:
        """Summed device seconds, over the cell's devices, of the operations
        ``match(name)`` accepts; with ``module``, only those run by the
        jitted program of that name (``jit_fwd``, ``jit_step``)."""
        if module is None:
            return sum(s for n, s in self.op_s.items() if match(n))
        return sum(e - s for n, s, e, m in self.ops
                   if m == module and match(n)) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        by_short = defaultdict(float)
        for n, t in self.op_s.items():
            by_short[short(n)] += t
        ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


class _Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float


class _Line(NamedTuple):
    name: str
    events: list


class _Plane(NamedTuple):
    name: str
    lines: list


def load(path: str):
    """A profile: the ``.xplane.pb`` itself, or a ``.json.gz`` excerpt
    of one (``excerpt``), which holds the same planes, lines and
    events."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return SimpleNamespace(planes=[
            _Plane(p["name"], [_Line(ln["name"],
                                     [_Event(*e) for e in ln["events"]])
                               for ln in p["lines"]])
            for p in raw["planes"]])
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def excerpt(profile, span_names, t0_ns: float, t1_ns: float) -> dict:
    """The device operations and harness spans between two times, in
    the form ``load`` reads back."""
    keep = set(span_names) | {WINDOW}
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            dev = plane.name.startswith(DEVICE_PREFIX)
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for e in line.events:
                s, d = e.start_ns, e.duration_ns
                if not (dev or e.name in keep) or s >= t1_ns \
                        or s + d <= t0_ns:
                    continue
                if e.name == WINDOW:       # the excerpt is the window
                    s, d = max(s, t0_ns), min(s + d, t1_ns) - max(s, t0_ns)
                evs.append([e.name, s, d])
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    total, cur_s, cur_e = 0.0, None, None
    merged = []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
        total += cur_e - cur_s
    return total, merged


def _leaves(ops):
    """Operations that contain no other: a ``while`` or ``call`` event
    spans the events of its body on the same line, and counting both
    would count its time twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[2]]


def short(name: str) -> str:
    """``%copy.31 = s8[8101663,64]{1,0:...} copy(...)`` -> ``copy.31 =
    s8[8101663,64] copy``: the op, its output and its opcode."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name
    rest = re.sub(r"\{[^}]*\}", "", rest)
    out = (rest[:rest.find(")") + 1] if rest.startswith("(")
           else rest.split(" ")[0])
    m = re.search(r"\s([\w-]+)\(", rest)
    return f"{head.lstrip('%')} = {out} {m.group(1) if m else ''}".strip()


def head(name: str) -> str:
    """The op's own name, without its output and operands."""
    return name.partition(" = ")[0].lstrip("%")


def _innermost(spans, starts, t, depth: int = 8) -> str:
    """Name of the latest-starting harness span open at ``t`` (spans
    nest, so that is the innermost)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - depth, -1), -1):
        if spans[j][2] > t:
            return spans[j][0]
    return NO_SPAN


def _module_of(modules, starts, t) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][2] > t:
        return modules[i][0].split("(")[0]
    return ""


def reduce(profile, span_names, device_ids) -> Reduced:
    """``device_ids``: the ids of the cell's devices (``jax.Device.id``),
    the first of them the one whose gaps are attributed."""
    span_names = set(span_names) | {WINDOW}
    wanted = [f"{DEVICE_PREFIX}{i}" for i in device_ids]
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name in wanted:
            lines = {ln.name: [(ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in ln.events] for ln in plane.lines}
            devices.append((plane.name, lines.get(OPS_LINE, []),
                            sorted(lines.get(MODULES_LINE, []),
                                   key=lambda m: m[1])))
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name in span_names]
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError("trace holds no harness 'window' span")
    missing = set(wanted) - {d[0] for d in devices}
    if missing:
        raise ValueError(f"trace holds no plane for {sorted(missing)}")
    w0, w1 = windows[0][1], windows[0][2]
    devices.sort(key=lambda d: wanted.index(d[0]))
    op_s = defaultdict(float)
    busy, tagged = [], []
    first = None
    for _, ops, modules in devices:
        inside = _leaves([(n, max(s, w0), min(e, w1)) for n, s, e in ops
                          if e > w0 and s < w1])
        mstarts = [m[1] for m in modules]
        for n, s, e in inside:
            op_s[n] += (e - s) * 1e-9
            tagged.append((n, s, e, _module_of(modules, mstarts, s)))
        total, merged = _union([(s, e) for _, s, e in inside])
        busy.append(total)
        if first is None:
            first = merged
    inner = sorted((s for s in spans if s[0] != WINDOW),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    gap_s = defaultdict(float)
    prev = w0
    for s, e in first + [(w1, w1)]:
        if s > prev:
            gap_s[_innermost(inner, starts, 0.5 * (prev + s))] += \
                (s - prev) * 1e-9
        prev = max(prev, e)
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   op_s=dict(op_s), ops=tagged, gap_s=dict(gap_s),
                   spans=inner, devices=len(devices))
