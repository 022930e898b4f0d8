"""What the library makes the chips do besides the wire, in per cent of
the traced programs' busy time: the leaf events that are neither a
collective (by opcode) nor the caller's (the benchmark's chain write
and loop control), each program's events read against its own text.
Prints the share per row of the table, and splits every row by what its
time is: the collectives, the library's other work by phase (``pack``,
``wire``, ``unpack`` where the op names them), copies the compiler put in
that nothing attributes, and the caller's."""

import time

from perfbench.harness import scopes, trace

NOT_SPLIT = "not split"


def kind_of(row):
    """What a row's time is, for the printed split."""
    if row.layer == scopes.OP_SURFACE:
        phase = scopes.phase_of(row) or NOT_SPLIT
        return f"{phase} (collective)" if row.collective else phase
    return "collective" if row.collective else row.layer


def is_tax(row):
    return not row.collective and row.layer != scopes.CALLER


def read(view):
    t0 = time.perf_counter()
    session = view.session
    rows = scopes.attribute(
        view.trace, [s.row for s in view.traced],
        lambda name: session.programs[name].lower(
            session.inputs[name]).compile().as_text())
    if rows is None:
        return None
    busy = trace.busy_s(view.trace)
    calls = {}
    for s in view.traced:
        calls[s.row] = calls.get(s.row, 0) + session.units(s.row)
    for name in calls:
        mine = [r for r in rows if r.program == name]
        kinds = {}
        for r in mine:
            kinds[kind_of(r)] = kinds.get(kind_of(r), 0.0) + r.seconds
        whole = scopes.total(mine)
        split = ", ".join(f"{k} {100 * v / whole:.3f} %" for k, v in
                          sorted(kinds.items(), key=lambda kv: -kv[1]))
        tax = scopes.total(r for r in mine if is_tax(r))
        print(f"perfbench: op tax, row {name}: {whole / calls[name] * 1e6:.3f} us of "
              f"device time a call, tax {100 * tax / whole:.3f} % of it "
              f"({100 * tax / busy:.3f} % of all busy time); split: {split}",
              flush=True)
    taxed = [r for r in rows if is_tax(r)]
    scopes.print_layers("device time by layer", rows, busy)
    scopes.print_table("the tax by origin", taxed, busy, calls, "call")
    print(f"perfbench: op_tax_device_share.coll: read in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return 100.0 * scopes.total(taxed) / busy
