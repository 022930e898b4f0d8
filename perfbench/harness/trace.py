"""From the profiler's ``.xplane.pb`` to numbers: device busy time, idle
gaps and who caused them, time per device operation and per named scope.

Read with nothing but jax (``jax.profiler.ProfileData``).  What a v5e
trace looks like (looked at by hand, PERF.md Finding 8): one plane per
chip, ``/device:TPU:<i>``, whose line ``XLA Ops`` holds one event per
executed HLO instruction, the event's name being the instruction's text
(``%fusion.3 = f32[..] fusion(..)``), with ``while`` and ``call`` events
spanning their bodies' events; ``XLA Modules`` holds one event per
executed program.  The host's ``TraceAnnotation``s are events on a line
of the plane ``/host:CPU``.  Device and host clocks differ by about a
millisecond, so a device time is never cut at a host time: the traced
window's length is the host's own span, and everything the device did in
the trace belongs to it, because the harness syncs before it starts the
trace and before it stops it.
"""

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "traced_window"
_INSTRUCTION = re.compile(r"%?([\w.\-]+)")


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


@dataclass
class Trace:
    """``device_ops[plane]``: the leaf events of one chip's ``XLA Ops``;
    ``modules[plane]``: its executed programs; ``host``: the host's
    annotation events by name."""

    device_ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    host: dict = field(default_factory=dict)


def find_xplane(log_dir):
    paths = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(event_name):
    """``%slice_add_fusion.10 = (f32[..]) fusion(..)`` -> ``slice_add_fusion.10``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def leaves(events):
    """The events that hold no other event: a ``while`` or ``call``
    spans its body's events and would count their time twice."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    parents = set()
    stack = []
    for i, e in enumerate(ordered):
        while stack and ordered[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= ordered[stack[-1]].end_ns:
            parents.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(ordered) if i not in parents]


def read_xplane(path, host_spans=()):
    """The parts of one ``.xplane.pb`` the metrics read."""
    from jax.profiler import ProfileData

    wanted = set(host_spans) | {WINDOW_SPAN}
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                ]
                if line.name == OPS_LINE:
                    trace.device_ops[plane.name] = leaves(events)
                else:
                    trace.modules[plane.name] = events
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        trace.host.setdefault(e.name, []).append(
                            Event(e.name, e.start_ns, e.duration_ns))
    return trace


def union_ns(events):
    """Length of the union of the events' intervals."""
    total = 0.0
    end = None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is None or e.start_ns > end:
            total += e.duration_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def gaps(events):
    """The idle intervals between the first and the last event, as
    ``(start_ns, end_ns)``."""
    out = []
    end = None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            out.append((end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return out


def window_s(trace):
    """Length of the traced window: the host's own span round it."""
    spans = trace.host.get(WINDOW_SPAN)
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return sum(e.duration_ns for e in spans) / 1e9


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.device_ops:
        raise ValueError("the trace has no device plane")
    per_chip = [union_ns(evs) for evs in trace.device_ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def idle_share(trace):
    """1 - busy / window, in per cent."""
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def op_seconds(trace):
    """Seconds per device operation (by instruction name), averaged over
    the chips, most expensive first."""
    totals = {}
    for evs in trace.device_ops.values():
        for e in evs:
            key = short_name(e.name)
            totals[key] = totals.get(key, 0.0) + e.duration_ns
    n = max(len(trace.device_ops), 1)
    return sorted(
        ((k, v / n / 1e9) for k, v in totals.items()), key=lambda kv: -kv[1])


def op_count(trace):
    """Device operations executed, averaged over the chips."""
    n = max(len(trace.device_ops), 1)
    return sum(len(evs) for evs in trace.device_ops.values()) / n


_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*op_name=\"([^\"]*)\"", re.M)


def scope_seconds(trace, hlo_texts, prefix="mpi4jax_tpu."):
    """Seconds per ``jax.named_scope`` whose name starts with ``prefix``,
    averaged over the chips.  The trace names an instruction; the
    compiled program's text (``compiled.as_text()``) gives that
    instruction's ``op_name``, which holds the scopes it was traced in."""
    scope_of = {}
    for text in hlo_texts:
        for inst, op_name in _HLO_LINE.findall(text):
            for part in op_name.split("/"):
                if part.startswith(prefix):
                    scope_of[inst] = part
                    break
    totals = {}
    for evs in trace.device_ops.values():
        for e in evs:
            scope = scope_of.get(short_name(e.name))
            if scope:
                totals[scope] = totals.get(scope, 0.0) + e.duration_ns
    n = max(len(trace.device_ops), 1)
    return {k: v / n / 1e9 for k, v in totals.items()}


def idle_gaps(trace, host_spans, top=10):
    """The longest idle gaps of the first chip, each named by the host
    span that overlaps it most.  The two clocks are brought together by
    letting the chip's first operation start with the host's first span:
    the least delay there can be."""
    if not trace.device_ops:
        return []
    plane = sorted(trace.device_ops)[0]
    ops = trace.device_ops[plane]
    spans = [e for name in host_spans for e in trace.host.get(name, ())]
    if not ops or not spans:
        return []
    shift = min(e.start_ns for e in spans) - min(e.start_ns for e in ops)
    out = []
    for g0, g1 in gaps(ops):
        g0, g1 = g0 + shift, g1 + shift
        best, best_overlap = "host_other", 0.0
        for s in spans:
            overlap = min(g1, s.end_ns) - max(g0, s.start_ns)
            if overlap > best_overlap:
                best, best_overlap = s.name, overlap
        out.append((best, (g1 - g0) / 1e9))
    out.sort(key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in out[:top]]


def breakdown(trace, host_spans, top=10):
    return {
        "device_ops": [[k, v] for k, v in op_seconds(trace)[:top]],
        "idle_gaps": idle_gaps(trace, host_spans, top),
    }
