"""Device time a call spends in the monitor's all-reduces, in
microseconds a chip: the leaf events of the monitor program's
executions whose instruction is an ``all-reduce`` (by opcode; a
``-start`` and its ``-done`` both count), a mean over the executions the
trace holds whole and over the chips.  On a mesh an all-reduce ends
when the slowest chip has joined it, so the first of a call holds what
the chips' reductions of their own blocks differ by.  Prints their
count a call beside it (``models/shallow_water.py make_monitor``: one a
kind of reduction, three) and each one's share.

Where the program holds no all-reduce (a mesh of one chip: XLA elides
them), the call no monitor program, or the trace none of its
executions: a printed reason and nothing."""

import collections

from perfbench.harness import files, scopes, trace

MONITOR = "monitor"


def read(view):
    session = view.session
    share = files.load_module(
        "layer_metrics", "monitor_device_share.sw", session.ctx.bench_dir)
    found = share.executions_of(view)
    if found is None:
        return None
    mine = found[-1]
    by = collections.defaultdict(lambda: [0.0, 0])
    for events in mine:
        for e in events:
            op = scopes.opcode(e.name) or ""
            if op.removesuffix("-start").removesuffix("-done") == "all-reduce":
                at = by[trace.short_name(e.name)]
                at[0] += e.duration_ns
                at[1] += 1
    if not by:
        print("perfbench: the monitor program ran no all-reduce (on a mesh of "
              "one chip XLA elides them): nothing is reported", flush=True)
        return None
    calls = len(mine)  # executions, all chips': a mean over both
    total = sum(ns for ns, _ in by.values())
    print(f"perfbench: the monitor's all-reduces: "
          f"{sum(n for _, n in by.values()) / calls:g} a call, "
          + ", ".join(f"%{name} {ns / calls / 1e3:.3f} us"
                      for name, (ns, _) in sorted(by.items())), flush=True)
    return total / calls / 1e3
