"""Share of the as-written step's device time spent in its twelve halo
exchanges, in per cent: the leaf events of the trace whose instruction
carries a ``sw/exchange.<field>`` scope in the compiled multistep's text
(``models/shallow_water.py STEP_EXCHANGES``: the scope round each
``halo_exchange_2d`` of the step), over the device's busy time.
``op_surface_device_share.sw`` beside it is the same time seen by op.

Prints the split: by exchanged field and by the exchange's ``pack``,
``wire`` and ``unpack`` (which of the twelve costs a pass over the
block and which two columns), by the step's phase (``sw/<phase>``), and
what carries neither scope (the copies the compiler put in) by
instruction.  A fusion is one event under its root's scope: where XLA
writes the ghost columns of several fields in one fusion, the fields
share the first one's name.

``None`` where the program's text carries no such scope (a program
from before the scopes, or another schedule)."""

from perfbench.harness import scopes, trace

MULTI = "multistep"
STEP_SCOPE = "sw"  # models/shallow_water.py STEP_SCOPE
EXCHANGE = "exchange."


def labels(op_name):
    """``(kind, name, phase)`` of an instruction's ``op_name``:
    ``("exchange", field, pack|wire|unpack|None)``, ``("phase", phase,
    None)``, or ``None`` where it carries no ``sw/...`` scope."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts[:-1]):
        if part != STEP_SCOPE:
            continue
        label = parts[i + 1]
        if label.startswith(EXCHANGE):
            inner = next((p for p in parts[i + 2:] if p in scopes.PHASES), None)
            return "exchange", label[len(EXCHANGE):], inner
        return "phase", label, None
    return None


def _table(title, totals, steps, busy):
    print(f"perfbench: {title}: name | us a step | % of busy", flush=True)
    for name, seconds in sorted(totals.items(), key=lambda kv: -kv[1]):
        print(f"perfbench:   {name} | {seconds / steps * 1e6:.3f} | "
              f"{100 * seconds / busy:.3f}", flush=True)


def read(view):
    session = view.session
    executions = [MULTI for s in view.traced
                  for _ in range(session.rows[s.row]["reps"])]
    placed = scopes.by_execution(view.trace, executions)
    if placed is None:
        return None
    table = scopes.origins(session.compiled_text(MULTI))
    known = {name: labels(origin.op_name) for name, origin in table.items()}
    if not any(label and label[0] == "exchange" for label in known.values()):
        print(f"perfbench: the multistep's text carries no "
              f"{STEP_SCOPE}/{EXCHANGE}<field> scope: nothing is reported",
              flush=True)
        return None
    by_field, by_part, by_phase, neither = {}, {}, {}, {}
    for of_chip in placed.values():
        for _key, events in of_chip:
            for e in events:
                name = trace.short_name(e.name)
                label, seconds = known.get(name), e.duration_ns / 1e9
                if label is None:
                    at, into = f"{scopes.opcode(e.name)} %{name}", neither
                elif label[0] == "phase":
                    at, into = label[1], by_phase
                else:
                    at, into = label[1], by_field
                    part = label[2] or "-"
                    by_part[part] = by_part.get(part, 0.0) + seconds
                into[at] = into.get(at, 0.0) + seconds
    chips = len(placed)
    busy = trace.busy_s(view.trace) * chips
    steps = sum(session.units(s.row) for s in view.traced) * chips
    _table("the exchanges' device time by field", by_field, steps, busy)
    _table("the exchanges' device time by part", by_part, steps, busy)
    _table("the step's device time by phase", by_phase, steps, busy)
    _table("device time under neither scope", neither, steps, busy)
    return 100.0 * sum(by_field.values()) / busy
