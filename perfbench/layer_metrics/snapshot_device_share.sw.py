"""Share of a call's device time that the job spends because it has
output, in per cent, wherever it spends it: in a snapshot program of its
own, in a longer last step, in a finish after it.

    100 x (call - k x period) / call

``call``: the device time from one multistep's start to the next's,
every program between them (the union of their leaf events, a mean over
the calls the trace holds whole).  ``k``: the kernel calls of one
multistep's execution.  ``period``: the median, over the window's kernel
calls, of the device time from one kernel call's start to the next's
(the last of an execution: to the execution's end).  A step repeats and
output comes once a call, so the median is deaf to the one period that
carries output, and nothing in it asks how many steps a kernel call
advances.  While a call is the multistep and a snapshot program that
holds all of output's work, this is that program's share of the call.

Where the multistep runs no kernel call (the array code: no cell)
there is no period, and nothing is reported.  Prints where all the
device time goes, by layer and by source."""

import collections
import statistics

from perfbench.harness import scopes, trace

MULTI = "multistep"


def periods(events):
    """Device time from each kernel call's start to the next's, the
    last one's to the end of ``events`` (one execution's)."""
    events = sorted(events, key=lambda e: e.start_ns)
    starts = [i for i, e in enumerate(events)
              if scopes.opcode(e.name) == scopes.KERNEL_CALL]
    return [trace.union_ns(events[a:b])
            for a, b in zip(starts, starts[1:] + [len(events)])]


def whole_calls(session, placed):
    """``(by_program, of_kernels)`` from the executions of a traced
    window (``scopes.by_execution`` of ``session.traced_programs``'s
    pair): the device ns of each program of a call (the union of its
    leaf events, a mean over the calls the trace holds whole), by key
    and in the call's order, and every multistep execution's
    ``periods``.  ``None`` for the first, with the reason printed,
    where the trace holds no whole call."""
    keys = session.programs()
    of_programs, of_kernels = [], []
    for of_chip in placed.values():
        starts = [i for i, (key, _) in enumerate(of_chip) if key == MULTI]
        for a, b in zip(starts, starts[1:] + [len(of_chip)]):
            if b - a == len(keys):  # a last call without its cut snapshot is not whole
                of_programs.append([trace.union_ns(events)
                                    for _, events in of_chip[a:b]])
            of_kernels.append(periods(of_chip[a][1]))
    if not of_programs:
        print("perfbench: the trace holds no whole call: nothing is reported",
              flush=True)
        return None, of_kernels
    return ({key: statistics.fmean(ns) for key, ns in zip(keys, zip(*of_programs))},
            of_kernels)


def kernel_period(of_kernels):
    """``(k, period)``: the kernel calls of one multistep execution and
    their median period, in ns; ``None``, with the reason printed, where
    the multistep ran none, or not one number of them."""
    k = {len(found) for found in of_kernels}
    if k == {0}:
        print("perfbench: the multistep ran no kernel call: there is no "
              "period to hold a call against; nothing is reported", flush=True)
        return None
    if len(k) != 1:
        print(f"perfbench: the multistep's executions ran {sorted(k)} kernel "
              "calls, not one number: nothing is reported", flush=True)
        return None
    k, = k
    return k, statistics.median(p for found in of_kernels for p in found)


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    placed = scopes.by_execution(whole, executions)
    if placed is None:
        return None
    rows = scopes.attribute(whole, executions, session.compiled_text)
    if rows is not None:
        per = collections.Counter(executions)
        busy = trace.busy_s(whole)
        scopes.print_layers("device time by layer", rows, busy)
        scopes.print_table("device time by origin", rows, busy, per, "call")
    by_program, of_kernels = whole_calls(session, placed)
    found = by_program and kernel_period(of_kernels)
    if not found:
        return None
    call, (k, period) = sum(by_program.values()), found
    print(f"perfbench: a call takes {call / 1e3:.3f} us of device time, its "
          f"{k} kernel calls' periods {period / 1e3:.3f} us at the median: "
          f"{(call - k * period) / 1e3:.3f} us a call are output's", flush=True)
    return 100.0 * (call - k * period) / call
