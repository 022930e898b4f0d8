"""Device time by the layer of the program that emitted it.

The program opens ``jax.named_scope("mpi4jax_tpu.<op>")`` round every
public op (``mpi4jax_tpu/ops/_core.py publishes_token``), and a composite
op names its phases inside that (``parallel/halo.py``: ``pack``, ``wire``,
``unpack``).  The scopes reach the compiled program as each instruction's
``metadata={op_name="..." stack_frame_id=N}`` and the device trace as the
event metadata's ``tf_op`` and ``source``.  ``jax.profiler.ProfileData``
(jax 0.9.0) shows an event's own stats and not its metadata's, so the
readers go through the compiled program's text: an event of the trace is
named by its instruction's text, and the text of the program that ran
says where that instruction came from.

Programs are kept apart (two programs may both have a ``copy.52``): every
leaf event is given to the ``XLA Modules`` event of its chip that
contains it, and the module executions of a chip are matched, in order,
with the programs the traced batches ran.  Where trace and programs do
not belong together the reader says so and returns ``None``; it never
guesses, and never reports 0 for "not found".

The same text says the least a program can move: what it is handed and
what it hands back, each once (``signature``).  A floor read there
follows the program; a count written down by hand goes stale with the
first change that moves fewer bytes, and a share of a roofline then
reads over 100.
"""

import bisect
import functools
import math
import re
import typing
from dataclasses import dataclass

from perfbench.harness import files
from perfbench.harness.trace import short_name, union_ns

# the benchmark's own copy of the program's prefix (ops/_core.py
# SCOPE_PREFIX): the parent's programs carry the scopes without exporting it
SCOPE_PREFIX = "mpi4jax_tpu."
MODELS_DIR = "mpi4jax_tpu/models/"
PHASES = ("pack", "wire", "unpack")

OP_SURFACE = "op surface"
PROGRAMS = "programs"
CALLER = "caller"
UNATTRIBUTED = "unattributed"

_COLLECTIVES = frozenset({
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "collective-broadcast", "reduce-scatter"})
_TABLE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n((?:\d+ .*\n)+)",
    re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
# anchored: a kernel call's line has `frontend_attributes={kernel_metadata={}}`
# before its own `metadata={op_name=... stack_frame_id=...}`
_METADATA = re.compile(r'(?<![\w])metadata=\{((?:[^{}"]|"[^"]*")*)\}')
_FIELD = re.compile(r'(\w+)=(?:"([^"]*)"|(\d+))')
_ENTRY = re.compile(r"^ENTRY %?[\w.\-]+ \((.*)\) -> (.*) \{$", re.M)
_ARRAY = re.compile(r"\b(pred|[a-z]+(\d+)\w*)\[([\d,]*)\]")
_COMMENT = re.compile(r"/\*.*?\*/")

KERNEL_CALL = "custom-call"  # the opcode of a Pallas kernel's instruction


@dataclass(frozen=True)
class Origin:
    """Where an instruction came from: its jax ``op_name``; the scope
    segments of it, from the outermost ``mpi4jax_tpu.<op>`` down to (not
    including) the primitive; the ``file:line`` that emitted it, relative
    to the checkout; and that line's callers, innermost first."""

    op_name: str = None
    scopes: tuple = ()
    source: str = None
    callers: tuple = ()


def _fields(text):
    return {k: s if s or not n else int(n) for k, s, n in _FIELD.findall(text)}


def _relative(path, root):
    root = str(root).rstrip("/") + "/"
    return path[len(root):] if path.startswith(root) else path


def _stacks(text, root):
    """``{stack_frame_id: (file:line, ...)}``, the frame's own location
    first, from the module header's tables."""
    tables = {
        name: {int(n): rest for n, rest in
               (line.split(" ", 1) for line in body.splitlines())}
        for name, body in _TABLE.findall(text)}
    names = {i: _relative(v.strip().strip('"'), root)
             for i, v in tables.get("FileNames", {}).items()}
    where = {}
    for i, v in tables.get("FileLocations", {}).items():
        f = _fields(v)
        where[i] = f"{names.get(f.get('file_name_id'), '?')}:{f.get('line')}"
    frames = {i: _fields(v) for i, v in tables.get("StackFrames", {}).items()}
    # jax 0.9.0 prints parent_frame_id one too high (the outermost frame,
    # 1, names itself); a printer that says 0 for "no parent" is read as is
    high = 1 if frames.get(1, {}).get("parent_frame_id") == 1 else 0
    stacks = {}
    for first in frames:
        chain, seen, i = [], set(), first
        while i in frames and i not in seen:
            seen.add(i)
            chain.append(where.get(frames[i].get("file_location_id"), "?"))
            i = frames[i].get("parent_frame_id", 0) - high
        stacks[first] = tuple(chain)
    return stacks


def scopes_of(op_name):
    """``a/b/mpi4jax_tpu.x/wire/mpi4jax_tpu.y/prim`` -> ``(mpi4jax_tpu.x,
    wire, mpi4jax_tpu.y)``; empty where no segment has the prefix."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part.startswith(SCOPE_PREFIX):
            return tuple(parts[i:-1]) or (part,)
    return ()


def origins(compiled_text, root=files.ROOT):
    """``{instruction: Origin}`` for every instruction of one compiled
    program (``compiled.as_text()``), with or without metadata."""
    stacks = _stacks(compiled_text, root)
    table = {}
    for name, rest in _INSTRUCTION.findall(compiled_text):
        found = _METADATA.search(rest)
        meta = _fields(found.group(1)) if found else {}
        op_name = meta.get("op_name")
        stack = stacks.get(meta.get("stack_frame_id"), ())
        if not stack and meta.get("source_file"):  # the form before jax 0.9
            stack = (f"{_relative(meta['source_file'], root)}:"
                     f"{meta.get('source_line')}",)
        table[name] = Origin(
            op_name, scopes_of(op_name) if op_name else (),
            stack[0] if stack else None, stack[1:])
    return table


def layer_of(origin):
    """The one rule: under one of the program's scopes, the op surface;
    else emitted by a line of ``mpi4jax_tpu/models/``, the programs; else
    by any other line, the caller (the benchmark's own chain write, its
    payload code); with neither scope nor line (a copy the compiler put
    in, an instruction whose name stack it dropped), unattributed."""
    if origin.scopes:
        return OP_SURFACE
    if origin.source is None:
        return UNATTRIBUTED
    return PROGRAMS if MODELS_DIR in origin.source else CALLER


def _parts(rest):
    """``(result shape, opcode, operands)`` of what follows an
    instruction's `` = ``: the shape may be a tuple and holds brackets of
    all three kinds; the operands are what the opcode's own brackets
    hold.  ``None`` where nothing follows the shape."""
    depth = 0
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
    else:
        return None
    op, _, tail = rest[i + 1:].partition("(")
    depth = 1
    for j, c in enumerate(tail):
        depth += (c in "([{") - (c in ")]}")
        if depth == 0:
            return rest[:i], op, tail[:j]
    return rest[:i], op, tail


def opcode(event_name):
    """``%psum_invariant.17 = f32[8]{0:T(128)} all-reduce(..)`` ->
    ``all-reduce``: what follows the shape."""
    parts = _parts(event_name.partition(" = ")[2])
    return parts[1] if parts else None


def is_collective(event_name):
    """By the opcode, not by the instruction's name (``psum_invariant.17``
    is an ``all-reduce``; ``all-reduce-start`` and ``-done`` count)."""
    op = opcode(event_name) or ""
    for suffix in ("-start", "-done"):
        op = op.removesuffix(suffix)
    return op in _COLLECTIVES


class Signature(typing.NamedTuple):
    """Logical bytes (shape x element size) of the arrays a program, or
    one instruction of it, is handed and hands back."""

    taken: int
    handed_back: int

    @property
    def bytes(self):
        return self.taken + self.handed_back


def shape_bytes(shape_text):
    """Bytes of every array a printed shape names, a tuple's added up:
    ``(f32[7204,2]{1,0:T(8,128)S(1)}, s32[])`` -> 57636.  A token or an
    opaque value has no element size and counts nothing."""
    return sum(int(bits or 8) * math.prod(int(d) for d in dims.split(",") if d) // 8
               for _name, bits, dims in _ARRAY.findall(shape_text))


def _split_operands(text):
    depth, start = 0, 0
    for i, c in enumerate(text):
        depth += (c in "([{") - (c in ")]}")
        if c == "," and depth == 0:
            yield text[start:i]
            start = i + 1
    yield text[start:]


@functools.lru_cache(maxsize=8)
def _signatures(compiled_text):
    """``{instruction: Signature}`` of one compiled text, the program's
    own under ``None``.  A compiled text names an operand (``%p.1``) and
    the trace's event names print its shape before the name; an operand
    without a shape of its own is the result of the instruction it
    names, and what names none (a parameter's number, a constant's
    value) is no array."""
    lines = [(name, _parts(rest))
             for name, rest in _INSTRUCTION.findall(compiled_text)]
    results = {name: shape_bytes(parts[0]) for name, parts in lines if parts}
    table = {}
    for name, parts in lines:
        if not parts:
            continue
        taken = 0
        for operand in _split_operands(_COMMENT.sub("", parts[2])):
            shape, _, named = operand.strip().rpartition(" ")
            taken += (shape_bytes(shape) if shape
                      else results.get(named.lstrip("%"), 0))
        table[name] = Signature(taken, results[name])
    entry = _ENTRY.search(compiled_text)
    if entry:
        table[None] = Signature(*(shape_bytes(side) for side in entry.groups()))
    return table


def signature(compiled_text, instruction=None):
    """The least ``compiled_text``'s program can move, or one
    ``instruction`` of it: what it is handed (the ``ENTRY`` computation's
    parameters; the instruction's array operands) and what it hands back
    (its results), each once.  A program cannot run in less than it
    takes to read the one and write the other.  Scalars and operands in
    SMEM are counted: they are noise.  An operand's shape overstates
    what an instruction reads where it reads part of it (a ``slice``), so
    only a caller that knows the instruction reads all it is handed
    takes ``bytes``; ``handed_back`` is safe of any.  ``None`` where the
    text has no such instruction, or no ``ENTRY``."""
    return _signatures(compiled_text).get(instruction)


def floor_share(trace, executions, key, text_of, hbm_gbps, noun):
    """Share of the HBM roofline the program ``key`` reaches, in per
    cent: the least time its signature bytes could take at ``hbm_gbps``
    over the device time of one execution of it (the union of its leaf
    events, a mean over the executions ``trace`` holds); ``text_of(key)``
    gives its compiled text, asked for only when the counts match.
    ``None``, with the reason printed, where trace and ``executions`` do
    not belong together."""
    placed = by_execution(trace, executions)
    if placed is None:
        return None
    mine = [events for of_chip in placed.values()
            for k, events in of_chip if k == key]
    seconds = sum(union_ns(events) for events in mine) / len(mine) / 1e9
    least = signature(text_of(key))
    least_s = least.bytes / (hbm_gbps * 1e9)
    print(f"perfbench: {noun} takes {seconds * 1e6:.3f} us of device time, "
          f"the least its {least.taken} bytes in and {least.handed_back} out "
          f"could {least_s * 1e6:.3f} us", flush=True)
    return 100.0 * least_s / seconds


@dataclass(frozen=True)
class Row:
    """Device time of one program's leaf events from one origin,
    averaged over the chips."""

    program: str
    layer: str
    scopes: tuple
    source: str
    instruction: str  # of an unattributed row: its name is all that tells it
    opcode: str
    collective: bool
    seconds: float
    events: float


def _refuse(why):
    print(f"perfbench: scopes: trace and programs do not belong together: "
          f"{why}; nothing is reported", flush=True)


def by_execution(trace, executions):
    """``{plane: [(program key, its leaf events), ...]}``: each chip's
    leaf events by the module execution that contains them, the
    executions matched in order with ``executions`` (the key of the
    program each one ran).  ``None``, with the reason printed, where the
    counts differ or an event lies in no module."""
    out = {}
    for plane, events in trace.device_ops.items():
        modules = sorted(trace.modules.get(plane, ()), key=lambda m: m.start_ns)
        if len(modules) != len(executions):
            return _refuse(f"{plane} executed {len(modules)} programs, the "
                           f"traced batches ran {len(executions)}")
        starts = [m.start_ns for m in modules]
        mine = [(key, []) for key in executions]
        for e in events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= modules[i].end_ns:
                return _refuse(f"{short_name(e.name)} on {plane} lies in no "
                               "program's execution")
            mine[i][1].append(e)
        out[plane] = mine
    if not out:
        return _refuse("the trace has no device plane")
    return out


def attribute(trace, executions, text_of):
    """The trace's device time as ``Row``s, most expensive first.
    ``executions``: the key of the program each module execution ran, in
    order; ``text_of(key)``: that program's compiled text, asked for once
    a program and only when the counts match.  ``None``, with the reason
    printed, where trace and programs do not belong together, or where a
    program's text carries none of the program's scopes."""
    placed = by_execution(trace, executions)
    if placed is None:
        return None
    tables = {key: origins(text_of(key)) for key in dict.fromkeys(executions)}
    for key, table in tables.items():
        if not any(origin.scopes for origin in table.values()):
            print(f"perfbench: scopes: the text of {key!r} carries no "
                  f"{SCOPE_PREFIX}<op> scope: nothing says which instructions "
                  "are the op surface's; nothing is reported", flush=True)
            return None
    totals, known = {}, {}
    for plane, mine in placed.items():
        for key, events in mine:
            for e in events:
                at = known.get((key, e.name))  # a loop runs an event's name often
                if at is None:
                    origin = tables[key].get(short_name(e.name))
                    if origin is None:
                        return _refuse(
                            f"the text of {key!r} has no {short_name(e.name)}, "
                            f"which ran on {plane}")
                    layer = layer_of(origin)
                    at = known[key, e.name] = (
                        key, layer, origin.scopes, origin.source,
                        short_name(e.name) if layer == UNATTRIBUTED else None,
                        opcode(e.name), is_collective(e.name))
                seconds, count = totals.get(at, (0.0, 0))
                totals[at] = (seconds + e.duration_ns / 1e9, count + 1)
    n = len(placed)
    rows = [Row(*at, seconds / n, count / n)
            for at, (seconds, count) in totals.items()]
    return sorted(rows, key=lambda r: -r.seconds)


def phase_of(row):
    """``pack``, ``wire`` or ``unpack`` for a row under such a nested
    scope; ``None`` for any other."""
    return next((s for s in row.scopes[1:2] if s in PHASES), None)


def total(rows):
    return sum(r.seconds for r in rows)


def print_table(title, rows, busy_s, per, unit, top=20):
    """``rows`` merged by (program, layer, scope chain, source, opcode),
    the ``top`` most expensive: seconds and events per ``unit`` (the
    trace holds ``per[program]`` of them) and the share of ``busy_s``."""
    merged = {}
    for r in rows:
        at = (r.program, r.layer, "/".join(r.scopes) or "-",
              r.source or (f"%{r.instruction}" if r.instruction else "-"),
              r.opcode)
        seconds, events = merged.get(at, (0.0, 0.0))
        merged[at] = (seconds + r.seconds, events + r.events)
    print(f"perfbench: {title}: program | layer | scopes | source | opcode | "
          f"us a {unit} | % of busy | events a {unit}", flush=True)
    ranked = sorted(merged.items(), key=lambda kv: -kv[1][0])
    for at, (seconds, events) in ranked[:top]:
        n = per[at[0]]
        print(f"perfbench:   {' | '.join(at)} | {seconds / n * 1e6:.3f} | "
              f"{100 * seconds / busy_s:.3f} | {events / n:g}", flush=True)


def print_layers(title, rows, busy_s):
    """The share of ``busy_s`` by layer, and what the rows leave out."""
    shares = {}
    for r in rows:
        shares[r.layer] = shares.get(r.layer, 0.0) + r.seconds
    line = ", ".join(f"{layer} {100 * seconds / busy_s:.3f} %"
                     for layer, seconds in sorted(shares.items(),
                                                  key=lambda kv: -kv[1]))
    print(f"perfbench: {title}: {line} of {busy_s:.6f} s busy "
          f"(rows sum to {100 * total(rows) / busy_s:.3f} %)", flush=True)
