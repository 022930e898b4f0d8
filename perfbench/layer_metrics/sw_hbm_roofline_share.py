"""Share of the HBM roofline the solver's step reaches, in per cent.

The least a step can move is read from the program, not written down:
the signature bytes (``harness/scopes.py signature``) of the kernel
calls the trace shows running, each handed its operands and handing its
results back once, over the steps the traced batches made.  Since PR 31
that is six fields and six received slabs in and six fields out a step,
12 passes over (ny+2G)(nx+2G) x 4 bytes; a kernel call that advances two
steps halves the count by itself, and one that also writes coarse
fields adds their bytes.  Only a kernel call counts whole: an operand's
shape overstates what a ``slice`` reads (the sent slabs' fusions take a
whole field and read two columns), so any other instruction counts
twice its result.  Over the table's HBM bandwidth, divided by the
device's busy time per step from the trace.  Bound: bandwidth (the step
has about 150 flops a cell, 0.3 ms at the peak where the bytes take 6).

Where the step runs no kernel call (the array code: no cell) nothing
says what its least bytes are, and nothing is reported."""

from perfbench.harness import scopes, trace

MULTI = "multistep"


def moved_bytes(events, compiled_text):
    """``(bytes, kernel calls)`` of ``events``, each read against its
    instruction in ``compiled_text``: a kernel call's whole signature,
    twice the result of any other.  ``None``, with the reason printed,
    where the text has no such instruction."""
    total = kernel_calls = 0
    for e in events:
        moved = scopes.signature(compiled_text, trace.short_name(e.name))
        if moved is None:
            print(f"perfbench: the multistep's text has no "
                  f"{trace.short_name(e.name)}, which ran: nothing is reported",
                  flush=True)
            return None
        if scopes.opcode(e.name) == scopes.KERNEL_CALL:
            kernel_calls += 1
            total += moved.bytes
        else:
            total += 2 * moved.handed_back
    return total, kernel_calls


def least_step_s(events, steps, compiled_text, hbm_gbps):
    """Seconds the bytes of one step could take at the table's
    bandwidth, ``events`` being those of ``steps`` steps; ``None``, with
    the reason printed, where they hold no kernel call."""
    moved = moved_bytes(events, compiled_text)
    if moved is None:
        return None
    total, kernel_calls = moved
    if not kernel_calls:
        print("perfbench: the step's program ran no kernel call: nothing "
              "says what the least it can move is; nothing is reported",
              flush=True)
        return None
    print(f"perfbench: a step moves {total / steps:.0f} bytes at the least, "
          f"{kernel_calls / steps:g} kernel calls a step", flush=True)
    return total / steps / (hbm_gbps * 1e9)


def read(view):
    session = view.session
    chips = len(view.trace.device_ops)
    steps = sum(session.units(s.row) for s in view.traced) * chips
    if not steps:
        return None
    least_s = least_step_s(
        [e for events in view.trace.device_ops.values() for e in events],
        steps, session.compiled_text(MULTI), view.peaks["hbm_gbps"])
    if least_s is None:
        return None
    busy_per_step = trace.busy_s(view.trace) * chips / steps
    return 100.0 * least_s / busy_per_step
