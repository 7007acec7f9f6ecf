"""The program's own layer names in a profiler trace: device scopes,
kernel names and host spans.

The program names its layers with ``jax.named_scope`` (device ops) and
``jax.profiler.TraceAnnotation`` (host spans), and its kernels by their
own names.  A scope reaches the trace as the ``tf_op`` stat of each
``XLA Ops`` event's metadata: the op's name path, e.g. ``jit(step)/
transpose(jvp())/while/body/closed_call/checkpoint/attn/dot_general:``.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
``tf_ops`` decodes them from the ``.xplane.pb``'s protobuf wire format
(``XSpace`` -> ``XPlane`` ``event_metadata`` and ``stat_metadata`` ->
``XStat``) and the events are joined to them by their name, the op's
HLO text.

Each op keeps its scope path: the components of its name path, with the
transforms that wrap them stripped (``transpose(jvp(lm_head_ce))`` is
``lm_head_ce``) and the primitive, the last component, left off.  An op
is under a scope when the scope's name is one of those components, as a
whole (``attn_bias`` is not ``attn``); an op under a scope nested in
another is under both.  Container ops (``while``, ``call``,
``conditional``) are under no scope, since their bodies' ops have events
of their own.

A run's reading (``ScopeReading``) answers, over the ``window`` span and
averaged over the chips: the busy union of the ops under any scope name,
or of the ops whose instruction names match a pattern (a kernel's), the
busy time that no op under a given set of scopes covers, and the
device's idle time under any host span.  It names no scope, kernel or
span itself: the readers in ``metrics/`` do, so a reader of a new scope
or a new kernel's roofline is a file of its own.

The program's scopes, which ``unscoped_device_share`` leaves out, are
named in that reader alone (its ``SCOPES``), so that the metric changes
only with its own file.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass

import tracereduce as tr
from cellspec import load_plugin

_WRAPPED = re.compile(r"[A-Za-z_][\w.]*\((.*)\)")


# --------------------------------------------------------------------------
# protobuf wire format
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint or a fixed-width field, a memoryview for a
    length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _map_values(plane_fields, number: int) -> list:
    """The values of a ``map<int64, Message>`` field (each entry is a
    message with key 1 and value 2)."""
    return [v for f, entry in plane_fields if f == number
            for k, v in fields(entry) if k == 2]


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_tf_ops(plane_fields) -> dict[str, str]:
    """Event name -> ``tf_op`` of one plane's event metadata."""
    stat_names = {}
    for sm in _map_values(plane_fields, 5):          # XStatMetadata
        d = dict(fields(sm))
        stat_names[d.get(1, 0)] = _str(d.get(2, b""))
    tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
    out = {}
    for em in _map_values(plane_fields, 4):          # XEventMetadata
        name, value = None, None
        for f, v in fields(em):
            if f == 2:
                name = _str(v)
            elif f == 5:                             # XStat
                stat = dict(fields(v))
                if stat.get(1) not in tf_op_ids:
                    continue
                if 5 in stat:                        # str_value
                    value = _str(stat[5])
                elif 7 in stat:                      # ref_value
                    value = stat_names.get(stat[7])
        if name is not None and value is not None:
            out[name] = value
    return out


def tf_ops(path: str) -> dict[int, dict[str, str]]:
    """For each TPU device plane of the ``.xplane.pb`` at ``path``, its
    events' ``tf_op`` by event name."""
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for number, plane in fields(data):
        if number != 1:                              # XSpace.planes
            continue
        pf = list(fields(plane))
        name = next((_str(v) for f, v in pf if f == 2), "")
        m = tr.DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = _plane_tf_ops(pf)
    return out


# --------------------------------------------------------------------------
# scope paths
# --------------------------------------------------------------------------

def scope_path(tf_op: str) -> tuple[str, ...]:
    """The components of the op's name path, each stripped of the
    transforms that wrap it, without the primitive."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    out = []
    for c in path.split("/")[:-1]:
        while (m := _WRAPPED.fullmatch(c)):
            c = m.group(1)
        if c:
            out.append(c)
    return tuple(out)


# --------------------------------------------------------------------------
# the reading
# --------------------------------------------------------------------------

@dataclass
class ScopedOp:
    name: str           # HLO instruction name, e.g. "flash_attention.15"
    path: tuple         # scope path; () for none, or a container
    start: float        # ns
    end: float          # ns


@dataclass
class ScopeTrace:
    devices: dict       # device index -> [ScopedOp] of the XLA Ops line
    spans: list         # (name, start_ns, end_ns) host events


def load(path: str) -> ScopeTrace:
    """The ops of each TPU device plane with their scope paths, and every
    event of the host plane."""
    from jax.profiler import ProfileData
    names = tf_ops(path)
    devices, spans = {}, []
    for plane in ProfileData.from_file(str(path)).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            d = int(m.group(1))
            ops = devices.setdefault(d, [])
            tf = names.get(d, {})
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, opcode = tr.parse_hlo_event(e.name)
                    path = (() if opcode in tr.CONTAINERS
                            else scope_path(tf.get(e.name, "")))
                    ops.append(ScopedOp(name, path, e.start_ns,
                                        e.start_ns + e.duration_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return ScopeTrace(devices=devices, spans=spans)


def intersect(a, b) -> list:
    """The parts of the disjoint sorted intervals ``a`` that the
    disjoint sorted intervals ``b`` cover."""
    return tr.subtract(a, tr.subtract(a, b))


class ScopeReading:
    """A trace over its ``window`` span (or ``window``): each device's
    ops in it, and the host spans that start in it.  Times are in ns,
    averaged over the devices."""

    def __init__(self, trace: ScopeTrace, window=None) -> None:
        lo, hi = window if window is not None else tr.window_of(trace)
        if not trace.devices:
            raise ValueError("the trace holds no TPU device plane")
        self.lo, self.hi = lo, hi
        self.window_ns = hi - lo
        self.n_devices = len(trace.devices)
        self._ops = [[o for o in ops if o.end > lo and o.start < hi]
                     for ops in trace.devices.values()]
        self._busy = [self._union(ops) for ops in self._ops]
        self.busy_ns = self._mean(tr.total(b) for b in self._busy)
        self._spans = [(n, s, e) for n, s, e in trace.spans
                       if lo <= s < hi]

    def _union(self, ops) -> list:
        return tr.union(tr.clip([(o.start, o.end) for o in ops],
                                self.lo, self.hi))

    def _mean(self, values) -> float:
        return sum(values) / self.n_devices

    def _under(self, scopes):
        names = set(scopes)
        return lambda o: not names.isdisjoint(o.path)

    def n_ops_under(self, scopes) -> int:
        """Op events in the window under any of ``scopes``."""
        under = self._under(scopes)
        return sum(under(o) for ops in self._ops for o in ops)

    def busy_under(self, scopes) -> float:
        """The busy union of the ops under any of ``scopes``."""
        under = self._under(scopes)
        return self._mean(tr.total(self._union(o for o in ops if under(o)))
                          for ops in self._ops)

    def busy_matching(self, pattern: str) -> float:
        """The busy union of the ops whose instruction names match the
        regular expression ``pattern`` as a whole."""
        rx = re.compile(pattern)
        return self._mean(tr.total(self._union(
            o for o in ops if rx.fullmatch(o.name))) for ops in self._ops)

    def unscoped_ns(self, scopes) -> float:
        """Busy time that no op under any of ``scopes`` covers."""
        under = self._under(scopes)
        return self._mean(
            tr.total(tr.subtract(busy, self._union(o for o in ops
                                                   if under(o))))
            for ops, busy in zip(self._ops, self._busy))

    def span_count(self, name: str) -> int:
        """``name`` spans that start in the window."""
        return sum(n == name for n, _, _ in self._spans)

    def span_ns(self, name: str) -> float:
        """Total length of the ``name`` spans that start in the window."""
        return sum(e - s for n, s, e in self._spans if n == name)

    def idle_under_ns(self, name: str) -> float:
        """Device idle time in the window while a ``name`` span that
        starts in it is open."""
        spans = tr.union(tr.clip([(s, e) for n, s, e in self._spans
                                  if n == name], self.lo, self.hi))
        return self._mean(
            tr.total(intersect(tr.gaps(busy, self.lo, self.hi), spans))
            for busy in self._busy)


def read(path: str) -> ScopeReading:
    """The reading of the trace at ``path`` over its ``window`` span."""
    return ScopeReading(load(path))


# --------------------------------------------------------------------------
# what the metric readers share
# --------------------------------------------------------------------------

def _reading(r: dict):
    """The run's scope reading, ``r["scopes"]``, which a traced run has."""
    sr = r.get("scopes")
    if sr is None:
        print("scopes: no scope reading (an untraced run)", file=sys.stderr)
    return sr


def scoped_reading(r: dict, scopes):
    """The run's scope reading where some op in the window is under one
    of ``scopes``; else None, and why on standard error."""
    sr = _reading(r)
    if sr is None:
        return None
    if sr.n_ops_under(scopes) == 0:
        print(f"scopes: no op in the window is under {sorted(scopes)}: the "
              "program has no such scope, or came from a compile cache "
              "keyed without its metadata", file=sys.stderr)
        return None
    return sr


def scope_ms_per_step(r: dict, scope: str):
    """Busy time of the ops under ``scope`` per measured step, in ms."""
    sr = scoped_reading(r, (scope,))
    if sr is None:
        return None
    return sr.busy_under((scope,)) * 1e-6 / r["out"]["steps"]


def span_reading(r: dict, name: str):
    """The run's scope reading where the window holds one ``name`` span
    per measured step, give or take one; else None, and why on standard
    error."""
    sr = _reading(r)
    if sr is None:
        return None
    steps = r["out"]["steps"]
    if abs(sr.span_count(name) - steps) > 1:
        print(f"scopes: {sr.span_count(name)} '{name}' spans in a window "
              f"of {steps} steps", file=sys.stderr)
        return None
    return sr


def idle_ms_per_step(r: dict, name: str):
    """Device idle time per measured step while a ``name`` span is
    open, in ms."""
    sr = span_reading(r, name)
    if sr is None:
        return None
    return sr.idle_under_ns(name) * 1e-6 / r["out"]["steps"]


def roofline_share(r: dict, kernel: str):
    """The share (%) of its roofline that ``kernel`` reaches: the least
    time the cell's chips could take for the kernel's work in the
    window, the larger of its operations over the peak FLOP/s and its
    bytes over the peak bytes/s (``rooflines/<kernel>.py``, a step's
    work from the configuration and traffic, shared evenly by the
    chips), over the busy union of the kernel's device ops (``OPS``, a
    pattern of instruction names).  None where no such op ran."""
    sr = _reading(r)
    if sr is None:
        return None
    work = load_plugin("rooflines", kernel)
    busy_ns = sr.busy_matching(work.OPS)
    if busy_ns <= 0:
        print(f"scopes: no device op in the window matches {kernel}'s "
              f"{work.OPS!r}", file=sys.stderr)
        return None
    cell, peaks = r["cell"], r["peaks"]
    per_chip = r["out"]["steps"] / cell.chips
    least_s = per_chip * max(
        work.flops(cell.config, cell.traffic) / peaks["bf16_flops_per_s"],
        work.bytes(cell.config, cell.traffic) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (busy_ns * 1e-9)
