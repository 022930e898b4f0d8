"""Share of the differentiated run's device time spent in halo
exchanges, forward and transposed, in per cent: the leaf events of the
trace whose instruction lies under a ``mpi4jax_tpu.halo_*`` scope in the
compiled programs' text (the forward sweep's, the backward sweep's, the
descent step's), over the device's busy time.

Prints the split by op, by direction (the exchange as it runs forwards:
``.../mpi4jax_tpu.halo_exchange_2d/pack``; the adjoint exchange of a
backward sweep, ``parallel/halo.py _adjoint``:
``...halo_exchange_2d))/transpose/pack``) and by ``pack``, ``wire`` and
``unpack``.  On one chip the permutes are elided and ``wire`` is empty.
A fusion is one event under its root's scope: where XLA fuses a ghost
write into a stencil's fusion the exchange's time is the stencil's.
The scopes are read by ``drivers/shallow_water_adjoint.py exchange_of``.

``None`` where the session has no such programs or their text carries
no such scope."""

from perfbench.harness import files, trace


def read(view):
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    driver = files.load_module(
        "drivers", "shallow_water_adjoint", session.ctx.bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    by = {}
    for _key, e, op_name in events:
        found = driver.exchange_of(op_name)
        if found is not None:
            by[found] = by.get(found, 0.0) + e.duration_ns / 1e9
    if not by:
        print("perfbench: the programs' text carries no "
              "mpi4jax_tpu.halo_* scope: nothing is reported", flush=True)
        return None
    chips = len(view.trace.device_ops)
    busy = trace.busy_s(view.trace) * chips
    steps = sum(session.units(s.row) for s in view.traced) * chips
    print("perfbench: the exchanges' device time: op | direction | part | "
          "us a window step | % of busy", flush=True)
    for (op, transposed, part), seconds in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"perfbench:   {op} | {'transposed' if transposed else 'forward'} "
              f"| {part} | {seconds / steps * 1e6:.3f} | "
              f"{100 * seconds / busy:.3f}", flush=True)
    return 100.0 * sum(by.values()) / busy
