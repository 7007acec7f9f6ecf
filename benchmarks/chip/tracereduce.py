"""Reduction from a profiler trace to the benchmark's device numbers.

The input is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per HLO operation that ran on the TensorCore, and ``Async XLA Ops`` the
spans of asynchronous copies and collectives.  Host spans are the
benchmark's own ``TraceAnnotation``s, found by name on the host plane.
Host and device events share one clock in the file (nanoseconds from
the start of the profile).

Everything is computed from plain ``(start, end)`` intervals, so the
arithmetic is tested on synthetic intervals as well as on a recorded
trace (``tests/test_trace.py``).  It reads XLA opcodes and the
benchmark's span names only, never names from the program's source.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
# ops whose event spans the ops of their bodies, which have events of
# their own: counted in the busy union, not in the list of top ops
CONTAINERS = ("while", "conditional", "call")
# the host spans open in the window, which an idle gap is charged to
GAP_LABELS = ("loader", "dispatch", "block", "ir.run", "grad_assembly")
WINDOW_SPAN = "window"


@dataclass
class Op:
    name: str       # HLO instruction name, e.g. "fusion.12"
    opcode: str     # e.g. "fusion", "all-gather-start"
    start: float    # ns
    end: float      # ns


@dataclass
class DeviceTrace:
    ops: list = field(default_factory=list)        # XLA Ops line
    async_ops: list = field(default_factory=list)  # Async XLA Ops line


@dataclass
class Trace:
    devices: dict       # device index -> DeviceTrace
    spans: list         # (name, start_ns, end_ns) host spans


_HLO = re.compile(r"^%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")


def parse_hlo_event(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event name, which is
    the instruction's HLO text: ``%fusion.3 = bf16[..] fusion(...)``.
    An event that is not HLO text keeps its name as both."""
    m = _HLO.match(text)
    if not m:
        return text, text
    return m.group(1), m.group(2)


def is_collective(op: Op) -> bool:
    return any(c in op.opcode or op.name.startswith(c)
               for c in COLLECTIVES)


def load(path: str, span_names) -> Trace:
    """Read the device op events, and the host spans named in
    ``span_names``, of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: dict[int, DeviceTrace] = {}
    spans = []
    wanted = set(span_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dt = devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                out = dt.ops if line.name == "XLA Ops" else dt.async_ops
                for e in line.events:
                    name, opcode = parse_hlo_event(e.name)
                    out.append(Op(name, opcode, e.start_ns,
                                  e.start_ns + e.duration_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(devices=devices, spans=spans)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` around the disjoint ``busy``."""
    return subtract([(lo, hi)], busy)


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@dataclass
class Reduced:
    window_ns: float
    n_devices: int
    busy_ns: float               # mean over devices
    exposed_collective_ns: float  # mean over devices
    top_ops: list                # [(name, seconds)], mean over devices
    idle_by_label: list          # [(label, seconds)], mean over devices


def window_of(trace: Trace) -> tuple[float, float]:
    """The benchmark's ``window`` span: the traced measured window."""
    ws = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not ws:
        raise ValueError("the trace holds no 'window' span")
    return min(s for s, _ in ws), max(e for _, e in ws)


def covered(sorted_union, lo: float, hi: float) -> float:
    """How much of ``[lo, hi]`` the disjoint sorted intervals cover."""
    i = bisect.bisect_right(sorted_union, (lo, float("inf"))) - 1
    cover = 0.0
    for s, e in sorted_union[max(i, 0):]:
        if s >= hi:
            break
        cover += max(0.0, min(e, hi) - max(s, lo))
    return cover


def label_gap(gap, unions: dict) -> str:
    """The host span that covers most of an idle device gap; ``unions``
    maps each label to the disjoint sorted union of its spans."""
    best, best_cover = "host other", 0.0
    for label in GAP_LABELS:
        cover = covered(unions.get(label, []), *gap)
        if cover > best_cover:
            best, best_cover = label, cover
    return best


def reduce(trace: Trace, window=None, top: int = 10) -> Reduced:
    lo, hi = window if window is not None else window_of(trace)
    devs = sorted(trace.devices)
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy_sum = exposed_sum = 0.0
    op_time: dict[str, float] = {}
    idle: dict[str, float] = {}
    unions = {label: union((s, e) for n, s, e in trace.spans
                           if n == label and e > lo and s < hi)
              for label in GAP_LABELS}
    for d in devs:
        dt = trace.devices[d]
        ops = [o for o in dt.ops if o.end > lo and o.start < hi]
        busy = union(clip([(o.start, o.end) for o in ops], lo, hi))
        busy_sum += total(busy)
        compute = union(clip([(o.start, o.end) for o in ops
                              if not is_collective(o)
                              and o.opcode not in CONTAINERS], lo, hi))
        coll = union(clip([(o.start, o.end)
                           for o in ops + dt.async_ops
                           if is_collective(o)], lo, hi))
        exposed_sum += total(subtract(coll, compute))
        for o in ops:
            t = min(o.end, hi) - max(o.start, lo)
            if t > 0 and o.opcode not in CONTAINERS:
                op_time[o.name] = op_time.get(o.name, 0.0) + t
        for g in gaps(busy, lo, hi):
            lab = label_gap(g, unions)
            idle[lab] = idle.get(lab, 0.0) + (g[1] - g[0])
    n = len(devs)
    top_ops = sorted(((k, v / n * 1e-9) for k, v in op_time.items()),
                     key=lambda kv: -kv[1])[:top]
    idle_lab = sorted(((k, v / n * 1e-9) for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:top]
    return Reduced(window_ns=hi - lo, n_devices=n, busy_ns=busy_sum / n,
                   exposed_collective_ns=exposed_sum / n,
                   top_ops=top_ops, idle_by_label=idle_lab)
