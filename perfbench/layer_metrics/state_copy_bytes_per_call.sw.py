"""Bytes the multistep program's ``copy`` instructions move in one
call, a chip: 0 where every call donates its input, twice the state
(2 x 6 padded fields) where XLA has to copy a state that the step's
kernel updates in place.  Read from the trace: the leaf events of the
multistep's executions whose opcode is ``copy`` or ``copy-done``, each
counted as its result's bytes read and written."""

import math
import re

from perfbench.harness import scopes

MULTI = "multistep"
COPIES = ("copy", "copy-done")
_RESULT = re.compile(r" = \(?(\w+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def moved_bytes(event_name):
    """Bytes a copy named by its instruction's text reads and writes."""
    dtype, dims = _RESULT.search(event_name).groups()
    return 2 * _BYTES[dtype] * math.prod(int(d) for d in dims.split(",") if d)


def read(view):
    placed = scopes.by_execution(
        *view.session.traced_programs(view.trace, view.traced))
    if placed is None:
        return None
    calls = total = 0
    for of_chip in placed.values():
        for key, events in of_chip:
            if key != MULTI:
                continue
            calls += 1
            total += sum(moved_bytes(e.name) for e in events
                         if scopes.opcode(e.name) in COPIES)
    return total / calls if calls else None
