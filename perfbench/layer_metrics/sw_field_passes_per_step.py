"""Passes over a field that a solver step makes: the bytes the traced
multistep executions moved a step, over the bytes of one padded field a
chip.  The kernel cells' program makes 6 (twelve fields and slabs in,
six out, for two steps).  Repeats exactly: it is read from the program's
text and the count of events, not from their times.

Bytes as ``sw_hbm_roofline_share`` counts them, by its own
``moved_bytes``, loaded by name: a kernel call its whole signature, any
other instruction twice its result.  The array code of the as-written
step needs three rules more, kept here, each for an instruction whose
result's shape says more than it moves; every byte counted has to be a
byte that moved, or a share of the roofline built on the count could
pass 100:

- an in-place ``dynamic-update-slice`` moves its update and not its
  result: a ghost column written into a field is a column read and a
  column written.  So does a fusion that hands back nothing but such
  writes into its own operands (the exchange's lane-tile strips,
  ``parallel/halo.py _place``: XLA puts the strips of up to four
  fields into one fusion whose result is four whole fields).  Where
  the field could not be written where it lies XLA has put a ``copy``
  before the write, and that is counted as the copy it is;
- a fusion that is handed less than it hands back (one field in, its
  two friction gradients out) reads no more than it is handed;
- a ``copy-start`` counts nothing: its result names both ends of a
  copy that its ``copy-done`` counts.
"""

import re

from perfbench.harness import files, scopes, trace

MULTI = "multistep"
DUS = "dynamic-update-slice"
_LINE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = (.*)$", re.M)
_COMPUTATION = re.compile(r"^%([\w.\-]+) \(.*?\) -> .*? \{$(.*?)^\}", re.M | re.S)
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_NAMES = re.compile(r"%([\w.\-]+)")


def _operands(rest):
    """The operand names of what follows an instruction's `` = ``."""
    head = rest.partition("), ")[0]
    return _NAMES.findall(head.partition("(")[2])


def in_place_writes(compiled_text):
    """``{instruction: bytes}`` for every instruction of the text that
    writes into an operand where it lies: a ``dynamic-update-slice``
    (its update's bytes), and a fusion whose computation's results are
    all such writes into the computation's own parameters (the sum of
    their updates')."""
    lines = {name: rest for _, name, rest in _LINE.findall(compiled_text)}

    def opcode_of(name):
        return scopes.opcode(f"%{name} = {lines[name]}")

    def result_bytes(name):
        return scopes.signature(compiled_text, name).handed_back

    def update_of(name):
        """Bytes of the update, where ``name`` is a write into a
        parameter of its computation; else ``None``."""
        if opcode_of(name) != DUS:
            return None
        target, update = _operands(lines[name])[:2]
        if opcode_of(target) != "parameter":
            return None
        return result_bytes(update)

    writes = {name: result_bytes(_operands(rest)[1])
              for name, rest in lines.items() if opcode_of(name) == DUS}
    fused = {}
    for computation, body in _COMPUTATION.findall(compiled_text):
        root = next((name for is_root, name, _ in _LINE.findall(body)
                     if is_root), None)
        if root is None:
            continue
        handed_back = (_operands(lines[root])
                       if opcode_of(root) == "tuple" else [root])
        updates = [update_of(name) for name in handed_back]
        if updates and None not in updates:
            fused[computation] = sum(updates)
    for name, rest in lines.items():
        called = _CALLS.search(rest)
        if called and called[1] in fused and opcode_of(name) == "fusion":
            writes[name] = fused[called[1]]
    return writes


def moved_bytes(events, compiled_text, bench_dir=files.BENCH_DIR):
    """``(bytes, kernel calls)`` of ``events`` by the rules above;
    ``None``, with the reason printed, where the text lacks one."""
    accepted = files.load_module(
        "layer_metrics", "sw_hbm_roofline_share", bench_dir)
    writes = in_place_writes(compiled_text)
    total, others = 0, []
    for e in events:
        name, opcode = trace.short_name(e.name), scopes.opcode(e.name)
        moved = scopes.signature(compiled_text, name)
        if name in writes:
            total += 2 * writes[name]
        elif opcode == "copy-start":
            continue
        elif (opcode == "fusion" and moved is not None
              and moved.taken < moved.handed_back):
            total += moved.taken + moved.handed_back
        else:
            others.append(e)
    rest = accepted.moved_bytes(others, compiled_text)
    if rest is None:
        return None
    return total + rest[0], rest[1]


def traced_steps(view):
    """Steps the traced batches made, counted once a chip."""
    return (sum(view.session.units(s.row) for s in view.traced)
            * len(view.trace.device_ops))


def step_bytes(view):
    """``(bytes a step, kernel calls a step)`` of the traced batches'
    device events, over all chips; ``None`` where there is nothing to
    read."""
    session = view.session
    steps = traced_steps(view)
    if not steps:
        return None
    moved = moved_bytes(
        [e for events in view.trace.device_ops.values() for e in events],
        session.compiled_text(MULTI), session.ctx.bench_dir)
    if moved is None:
        return None
    return moved[0] / steps, moved[1] / steps


def field_bytes(session):
    """Bytes of one of the state's padded fields on a chip."""
    py, px = session.ctx.workload["mesh"]
    G = session.ghost
    itemsize = {"float32": 4, "bfloat16": 2, "float64": 8}[
        session.ctx.config["model"]["dtype"]]
    return (session.ny // py + 2 * G) * (session.nx // px + 2 * G) * itemsize


def read(view):
    moved = step_bytes(view)
    if moved is None:
        return None
    a_field = field_bytes(view.session)
    print(f"perfbench: a step moves {moved[0]:.0f} bytes, {moved[1]:g} kernel "
          f"calls a step; a padded field is {a_field} bytes", flush=True)
    return moved[0] / a_field
