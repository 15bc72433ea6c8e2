"""From a profiler trace to device time, idle share and the breakdown.

:func:`load` reduces JAX's ``.xplane.pb`` to a compact trace: the
benchmark's host spans (``chipbench.<kind>`` annotations with their call
index) and the device's operations (the ``XLA Ops`` line of each TPU
plane), on the trace's one clock, in nanoseconds.  The functions below
take only that compact form, so a small recorded trace
(``tests/chipbench/data``) checks them without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    spans: List[list]     # [kind, call index, start_ns, end_ns]
    ops: List[list]       # [op name, start_ns, end_ns, device plane]
    devices: int
    unions: object = dataclasses.field(default=None, repr=False)

    def to_json(self) -> dict:
        return {"spans": self.spans, "ops": self.ops, "devices": self.devices}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls(doc["spans"], doc["ops"], doc["devices"])

    def span_intervals(self, kind: str) -> List[Interval]:
        return [(s, e) for k, _, s, e in self.spans if k == kind]

    def window(self) -> Interval:
        """From the first step span's start to the last one's end."""
        steps = self.span_intervals("step")
        return min(s for s, _ in steps), max(e for _, e in steps)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans, ops, devices, seen = [], [], 0, []
    for plane in pd.planes:
        for line in plane.lines:
            seen.append(f"{plane.name}|{line.name}")
            if DEVICE_PLANE.match(plane.name) and line.name == OPS_LINE:
                for ev in line.events:
                    # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"
                    ops.append([ev.name.split(" = ", 1)[0].lstrip("%"),
                                ev.start_ns,
                                ev.start_ns + ev.duration_ns, plane.name])
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith("chipbench."):
                        stats = dict(ev.stats)
                        spans.append([ev.name[len("chipbench."):],
                                      int(stats["call"]), ev.start_ns,
                                      ev.start_ns + ev.duration_ns])
        if DEVICE_PLANE.match(plane.name):
            devices += 1
    if not ops or not spans:
        raise ValueError("trace holds no device ops or no benchmark spans; "
                         f"planes|lines seen: {seen}")
    return Trace(spans, ops, devices)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _op_unions(trace: Trace) -> List[Tuple[list, List[Interval]]]:
    """Per device: the union of its op intervals and their starts."""
    if trace.unions is None:
        per_dev: Dict[str, list] = {}
        for _, s, e, dev in trace.ops:
            per_dev.setdefault(dev, []).append((s, e))
        trace.unions = []
        for v in per_dev.values():
            u = union(v)
            trace.unions.append(([s for s, _ in u], u))
    return trace.unions


def busy(trace: Trace, within: Sequence[Interval]) -> float:
    """Nanoseconds in which an operation ran on the device, inside
    ``within``, averaged over the trace's devices."""
    unions = _op_unions(trace)
    total = 0.0
    for starts, u in unions:
        for s, e in union(within):
            lo = max(bisect.bisect_right(starts, s) - 1, 0)
            hi = bisect.bisect_left(starts, e)
            total += length(intersect(u[lo:hi], [(s, e)]))
    return total / max(len(unions), 1)


def device_ns_by_call(trace: Trace, kind: str) -> Dict[int, float]:
    """Device busy nanoseconds inside each ``kind`` span, by call index."""
    return {c: busy(trace, [(s, e)]) for k, c, s, e in trace.spans
            if k == kind}


def idle_in_ticks(trace: Trace) -> float:
    """Share of the union of step spans in which the device ran nothing."""
    steps = union(trace.span_intervals("step"))
    return 1.0 - busy(trace, steps) / length(steps)


def _inside(t: float, spans: Tuple[list, list]) -> bool:
    """Whether ``t`` lies in one of ``spans`` (their starts and the spans,
    sorted and disjoint)."""
    starts, sp = spans
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < sp[i][3]


def idle_gaps(trace: Trace) -> Dict[str, float]:
    """Idle device nanoseconds inside the traced window, by what the host
    was doing at each gap's midpoint: in a model call, in a step outside
    its calls, or between steps."""
    w0, w1 = trace.window()
    ops = intersect(union([(s, e) for _, s, e, _ in trace.ops]), [(w0, w1)])
    edges = [w0] + [x for iv in ops for x in iv] + [w1]
    by_kind = {}
    for k in ("prefill", "decode", "step"):
        sp = sorted((x for x in trace.spans if x[0] == k), key=lambda x: x[2])
        by_kind[k] = ([x[2] for x in sp], sp)
    out: Dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        t = (s + e) / 2
        who = next((f"{k}_call" for k in ("prefill", "decode")
                    if _inside(t, by_kind[k])),
                   "step_host" if _inside(t, by_kind["step"])
                   else "between_steps")
        out[who] = out.get(who, 0.0) + (e - s)
    return out


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device operations that took the most time, in nanoseconds."""
    tot: Dict[str, float] = {}
    for name, s, e, _ in trace.ops:
        tot[name] = tot.get(name, 0.0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def breakdown(trace: Trace) -> dict:
    """The result line's ``breakdown``, in seconds."""
    gaps = sorted(idle_gaps(trace).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in top_ops(trace)],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
