"""Share of a chip's busy time spent in the step's halo exchange, in
per cent: the leaf events of the multistep's executions whose
instruction the compiled text puts under one of the exchange's scopes
(``mpi4jax_tpu.halo_slabs_2d`` where the step is the kernel,
``mpi4jax_tpu.halo_exchange_2d`` where it is array code;
``parallel/halo.py``: ``pack``, the ``wire`` with its
``collective-permute``s, ``unpack``), and the copies of a whole block
that XLA adds on a mesh to cut a field's column slabs (an instruction
of opcode ``copy`` under no scope that hands back a block's bytes or
more: ``ROADMAP.md`` S16), over the busy time of the same trace; a mean
over the chips.

Prints the split, each line in microseconds a step and per cent of
busy: ``permute start`` and ``permute done`` (a ``done`` holds the wait
for the neighbour), ``wire other`` (what else lies under ``wire``: the
slabs' layout copies, a wall's selects, and whatever the token's fences
leave, which since PR 35 is nothing of their own), ``pack``,
``unpack``, ``block copy``.

Where the multistep's text holds no ``collective-permute`` (a mesh of
one chip: XLA elides the exchange) or the trace and the session's
programs do not belong together: a printed reason and nothing."""

from perfbench.harness import scopes, trace

MULTI = "multistep"
HALO = scopes.SCOPE_PREFIX + "halo_"  # halo_slabs_2d, halo_exchange_2d
LINES = ("permute start", "permute done", "wire other", "pack", "unpack",
         "block copy")


def line_of(origin, op, handed_back, block_bytes):
    """Which of ``LINES`` an instruction is booked under; ``None`` for
    one that is no part of the exchange."""
    if origin.scopes and origin.scopes[0].startswith(HALO):
        phase = next((s for s in origin.scopes[1:2] if s in scopes.PHASES), None)
        if phase != "wire":
            return phase  # pack, unpack; under the op's scope alone: nothing
        if op == "collective-permute-start":
            return "permute start"
        if op == "collective-permute-done":
            return "permute done"
        return "wire other"
    if not origin.scopes and op == "copy" and handed_back >= block_bytes:
        return "block copy"
    return None


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    placed = scopes.by_execution(whole, executions)
    if placed is None:
        return None
    text = session.compiled_text(MULTI)
    if "collective-permute" not in text:
        print("perfbench: the multistep's text holds no collective-permute "
              "(on a mesh of one chip XLA elides the exchange): nothing is "
              "reported", flush=True)
        return None
    table = scopes.origins(text)
    chips = len(placed)
    block_bytes = 4 * view.facts["cells"] // chips  # a chip's interior, float32
    known, by = {}, dict.fromkeys(LINES, 0.0)
    for of_chip in placed.values():
        for key, events in of_chip:
            if key != MULTI:
                continue
            for e in events:
                if e.name not in known:
                    name = trace.short_name(e.name)
                    found = scopes.signature(text, name)
                    known[e.name] = line_of(
                        table.get(name, scopes.Origin()), scopes.opcode(e.name),
                        found.handed_back if found else 0, block_bytes)
                if known[e.name]:
                    by[known[e.name]] += e.duration_ns / 1e9
    busy = trace.busy_s(whole) * chips
    steps = sum(key == MULTI for key in executions) * chips * (
        view.facts["steps_per_call"])
    print("perfbench: the halo exchange's device time: line | us a step | "
          "% of busy", flush=True)
    for what in LINES:
        print(f"perfbench:   {what} | {by[what] / steps * 1e6:.3f} | "
              f"{100 * by[what] / busy:.3f}", flush=True)
    return 100.0 * sum(by.values()) / busy
