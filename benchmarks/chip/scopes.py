"""The program's own layer names in a profiler trace: device scopes and
host spans.

The program names its layers with ``jax.named_scope`` (device ops) and
``jax.profiler.TraceAnnotation`` (host spans).  A scope reaches the
trace as the ``tf_op`` stat of each ``XLA Ops`` event's metadata: the
op's name path, e.g. ``jit(step)/transpose(jvp())/while/body/
closed_call/checkpoint/attn/dot_general:``.  ``jax.profiler.ProfileData``
does not expose event-metadata stats, so ``tf_ops`` decodes them from
the ``.xplane.pb``'s protobuf wire format (``XSpace`` -> ``XPlane``
``event_metadata`` and ``stat_metadata`` -> ``XStat``) and the events
are joined to them by their name, the op's HLO text.

Each op belongs to the innermost scope on its path, matched as a whole
path component once the transforms that wrap it are stripped
(``transpose(jvp(lm_head_ce))`` is ``lm_head_ce``; ``attn_bias`` is not
``attn``).  Container ops (``while``, ``call``, ``conditional``) belong
to no scope, since their bodies' ops have events of their own.  Over the
``window`` span, averaged over the chips: each scope's busy union, the
busy time no scoped op covers, and the device's idle time under each of
the program's host spans.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field

import tracereduce as tr

SCOPES = ("attn", "mlp", "lm_head_ce", "adamw")
# the program's host spans, in ``Supervisor.run`` and ``TokenLoader``
PROGRAM_SPANS = ("train_step", "data.block", "ft.sync", "ft.metrics")
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
# scope matching
# --------------------------------------------------------------------------

def scope_of(tf_op: str, scopes=SCOPES):
    """The innermost of ``scopes`` on the op's name path, or None."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    found = None
    for c in path.split("/"):
        while (m := _WRAPPED.fullmatch(c)):
            c = m.group(1)
        if c in scopes:
            found = c
    return found


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@dataclass
class ScopedOp:
    scope: str | None   # None: no scope, or a container
    opcode: str
    start: float        # ns
    end: float          # ns


@dataclass
class ScopeTrace:
    devices: dict       # device index -> [ScopedOp] of the XLA Ops line
    spans: list         # (name, start_ns, end_ns) host spans


@dataclass
class ScopeReading:
    window_ns: float
    n_devices: int
    n_scoped_ops: int   # op events in the window under some scope
    busy_ns: float      # the union of all ops, mean over devices
    scope_busy_ns: dict = field(default_factory=dict)  # mean over devices
    unscoped_ns: float = 0.0  # busy time under no scoped op
    span_ns: dict = field(default_factory=dict)     # total, in the window
    span_count: dict = field(default_factory=dict)  # started in the window
    idle_under_ns: dict = field(default_factory=dict)  # mean over devices


def load(path: str, span_names=PROGRAM_SPANS) -> ScopeTrace:
    """The ops of each TPU device plane with their scopes, and the host
    spans named in ``span_names`` and ``window``."""
    from jax.profiler import ProfileData
    names = tf_ops(path)
    wanted = set(span_names) | {tr.WINDOW_SPAN}
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
                    _, opcode = tr.parse_hlo_event(e.name)
                    scope = (None if opcode in tr.CONTAINERS
                             else scope_of(tf.get(e.name, "")))
                    ops.append(ScopedOp(scope, opcode, e.start_ns,
                                        e.start_ns + e.duration_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return ScopeTrace(devices=devices, spans=spans)


def intersect(a, b) -> list:
    """The parts of the disjoint sorted intervals ``a`` that the
    disjoint sorted intervals ``b`` cover."""
    return tr.subtract(a, tr.subtract(a, b))


def reduce(trace: ScopeTrace, window=None, scopes=SCOPES,
           span_names=PROGRAM_SPANS) -> ScopeReading:
    lo, hi = window if window is not None else tr.window_of(trace)
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    n = len(trace.devices)
    in_window = [(name, s, e) for name, s, e in trace.spans
                 if name in span_names and lo <= s < hi]
    span_unions = {name: tr.union(tr.clip(
        [(s, e) for nm, s, e in in_window if nm == name], lo, hi))
        for name in span_names}
    busy_sum = unscoped_sum = 0.0
    scope_sum = {sc: 0.0 for sc in scopes}
    idle_sum = {name: 0.0 for name in span_names}
    n_scoped = 0
    for ops in trace.devices.values():
        ops = [o for o in ops if o.end > lo and o.start < hi]
        busy = tr.union(tr.clip([(o.start, o.end) for o in ops], lo, hi))
        busy_sum += tr.total(busy)
        scoped = [o for o in ops if o.scope in scopes]
        n_scoped += len(scoped)
        for sc in scopes:
            scope_sum[sc] += tr.total(tr.union(tr.clip(
                [(o.start, o.end) for o in scoped if o.scope == sc],
                lo, hi)))
        covered = tr.union(tr.clip([(o.start, o.end) for o in scoped],
                                   lo, hi))
        unscoped_sum += tr.total(tr.subtract(busy, covered))
        idle = tr.gaps(busy, lo, hi)
        for name in span_names:
            idle_sum[name] += tr.total(intersect(idle, span_unions[name]))
    return ScopeReading(
        window_ns=hi - lo, n_devices=n, n_scoped_ops=n_scoped,
        busy_ns=busy_sum / n,
        scope_busy_ns={sc: v / n for sc, v in scope_sum.items()},
        unscoped_ns=unscoped_sum / n,
        span_ns={name: sum(e - s for nm, s, e in in_window if nm == name)
                 for name in span_names},
        span_count={name: sum(nm == name for nm, _, _ in in_window)
                    for name in span_names},
        idle_under_ns={k: v / n for k, v in idle_sum.items()})


def read(path: str) -> ScopeReading:
    """The reading of the trace at ``path`` over its ``window`` span."""
    return reduce(load(path))


# --------------------------------------------------------------------------
# what the metric readers share
# --------------------------------------------------------------------------

def _reading(r: dict):
    """The run's scope reading, ``r["scopes"]``, which a traced run has."""
    sr = r.get("scopes")
    if sr is None:
        print("scopes: no scope reading (an untraced run)", file=sys.stderr)
    return sr


def scoped_reading(r: dict):
    """The run's scope reading where some op in the window carries a
    scope; else None, and why on standard error."""
    sr = _reading(r)
    if sr is None:
        return None
    if sr.n_scoped_ops == 0:
        print("scopes: no op in the window carries a scope: the program "
              "came from a compile cache keyed without its metadata, or "
              "lost it", file=sys.stderr)
        return None
    return sr


def scope_ms_per_step(r: dict, scope: str):
    """Busy time of the ops under ``scope`` per measured step, in ms."""
    sr = scoped_reading(r)
    if sr is None:
        return None
    return sr.scope_busy_ns[scope] * 1e-6 / r["out"]["steps"]


def span_reading(r: dict, name: str):
    """The run's scope reading where the window holds one ``name`` span
    per measured step, give or take one; else None, and why on standard
    error."""
    sr = _reading(r)
    if sr is None:
        return None
    steps = r["out"]["steps"]
    if abs(sr.span_count[name] - steps) > 1:
        print(f"scopes: {sr.span_count[name]} '{name}' spans in a window "
              f"of {steps} steps", file=sys.stderr)
        return None
    return sr
