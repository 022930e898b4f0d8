"""Share of the device's busy time that the job spends watching itself,
in per cent: the device time of the monitor program's executions (the
union of each one's leaf events, over the executions the trace holds
whole; ``models/shallow_water.py make_monitor``: a chip's reductions of
its own block, then the library's ``allreduce`` over the mesh) over the
busy time of the same trace, a mean over the chips.

Prints the program's time a call, and its split by where an operation
came from in the program's compiled text: the local reductions under
the ``sw/monitor`` scope, the ``allreduce``s under their own
``mpi4jax_tpu.allreduce`` inside it (on four chips that is where a chip
waits for the slowest), and what carries neither.

A session whose call has no monitor program, a trace that holds none of
its executions, or a text without the ``sw/monitor`` scope: a printed
reason and nothing."""

from perfbench.harness import scopes, trace

MONITOR = "monitor"
SCOPE = "sw/monitor"  # models/shallow_water.py STEP_SCOPE, MONITOR
ALLREDUCE = scopes.SCOPE_PREFIX + "allreduce"


def executions_of(view):
    """``(whole, executions, placed, mine)``: the trace the session's
    programs are matched with and the program each of its executions
    ran, its events by execution, and the monitor program's executions'
    events, all chips'; ``None``, with the reason printed, where there
    is no such program or no execution of it."""
    session = view.session
    keys = session.programs() if hasattr(session, "programs") else ()
    if MONITOR not in keys:
        print(f"perfbench: a call of this session runs {tuple(keys)}: no "
              "monitor program; nothing is reported", flush=True)
        return None
    whole, executions = session.traced_programs(view.trace, view.traced)
    placed = scopes.by_execution(whole, executions)
    if placed is None:
        return None
    mine = [events for of_chip in placed.values()
            for key, events in of_chip if key == MONITOR]
    if not mine:
        print("perfbench: the trace holds no execution of the monitor "
              "program; nothing is reported", flush=True)
        return None
    return whole, executions, placed, mine


def split(table, events):
    """Seconds of ``events`` by what their instruction's ``op_name``
    carries in ``table`` (``scopes.origins`` of the monitor program)."""
    out = {"local reductions": 0.0, "allreduce": 0.0, "neither": 0.0}
    for e in events:
        origin = table.get(trace.short_name(e.name))
        op_name = (origin and origin.op_name) or ""
        at = ("neither" if SCOPE not in op_name else
              "allreduce" if ALLREDUCE in op_name else "local reductions")
        out[at] += e.duration_ns / 1e9
    return out


def read(view):
    found = executions_of(view)
    if found is None:
        return None
    whole, _executions, placed, mine = found
    table = scopes.origins(view.session.compiled_text(MONITOR))
    if not any(SCOPE in (o.op_name or "") for o in table.values()):
        print(f"perfbench: the monitor program's text carries no {SCOPE} "
              "scope: nothing is reported", flush=True)
        return None
    chips = len(placed)
    seconds = sum(trace.union_ns(events) for events in mine) / 1e9
    by = split(table, [e for events in mine for e in events])
    print(f"perfbench: the monitor program takes "
          f"{seconds / len(mine) * 1e6:.3f} us of device time a call "
          f"({len(mine)} whole executions on {chips} chips): "
          + ", ".join(f"{what} {s / len(mine) * 1e6:.3f} us"
                      for what, s in by.items()), flush=True)
    return 100.0 * seconds / chips / trace.busy_s(whole)
