"""Share of the HBM roofline that output's work reaches, in per cent,
wherever a call does it: in a snapshot program of its own, in a last
step that writes coarse fields, in both.

Bytes: what a call moves because it has output.  The signature bytes
(``harness/scopes.py signature``: what a program is handed and what it
hands back, each once, read from its compiled text) of every program of
a call but the multistep, plus what the multistep hands back beyond what
it is handed (a last step that writes coarse fields beside the state).
As the job makes it since PR 34 that is the snapshot program's: each
field's padded array in and its coarse field out, fields x
((ny+2G)(nx+2G) + (ny/c)(nx/c)) x 4 bytes; a program that is handed
coarse sums has their bytes, and the multistep that made them theirs
once more.  The three fields a kernel call reads anyway are the step's,
not output's: every byte counted here moved because of output.

Time: the device time of the programs those bytes were counted in, as
far as it is output's, by ``snapshot_device_share.sw``'s own functions,
loaded by name: a program that is all output's, its whole device time
(the union of its leaf events, a mean over the whole calls of the
trace); the multistep, where it hands back more than it is handed, its
device time beyond its ``k`` kernel calls' median periods.  Bytes and
time are taken from the same programs, so the share cannot pass 100
while the bytes took the time they are held against.
(``snapshot_device_share.sw``'s ``call - k x period`` is all a call
spends because it has output: it also holds what the snapshot's copy to
the host costs the kernel calls that run beside it, 79-86 us a call in
the cells, which is no HBM traffic of output's and is not counted here.)

Share: those bytes at the table's HBM bandwidth over that time.  Bound:
bandwidth (a mean of c x c cells is one addition a cell).

Where output adds no bytes to a call, or a program's bytes have no time
of their own (sums made in every kernel call, so that the median period
holds them), there is nothing to divide: the reader says which and
reports nothing."""

from perfbench.harness import files, scopes

MULTI = "multistep"


def output_bytes(session):
    """``[(key, what, bytes)]``: what each program of a call moves
    because the call has output."""
    found = []
    for key in session.programs():
        least = scopes.signature(session.compiled_text(key))
        if key != MULTI:
            found.append((key, f"the {key} program ({least.taken} in, "
                          f"{least.handed_back} out)", least.bytes))
        elif least.handed_back > least.taken:
            found.append((key, "the multistep's results beyond its operands",
                          least.handed_back - least.taken))
    return found


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    placed = scopes.by_execution(whole, executions)
    if placed is None:
        return None
    time_of = files.load_module(
        "layer_metrics", "snapshot_device_share.sw", session.ctx.bench_dir)
    by_program, of_kernels = time_of.whole_calls(session, placed)
    if by_program is None:
        return None
    moved = output_bytes(session)
    if not moved:
        print("perfbench: no program of a call is output's and the multistep "
              "hands back no more than it is handed: output adds no bytes to "
              "a call; nothing is reported", flush=True)
        return None
    total_ns = 0.0
    for key, what, _ in moved:
        ns = by_program[key]
        if key == MULTI:  # of the step's program, what is beyond its steps
            found = time_of.kernel_period(of_kernels)
            if found is None:
                return None
            ns -= found[0] * found[1]
        if ns <= 0:
            print(f"perfbench: {what} have no device time of their own (the "
                  "work is in every kernel call's period): there is nothing "
                  "to hold output's bytes against; nothing is reported",
                  flush=True)
            return None
        total_ns += ns
    total = sum(nbytes for _, _, nbytes in moved)
    least_s = total / (view.peaks["hbm_gbps"] * 1e9)
    print("perfbench: output's bytes a call: "
          + "; ".join(f"{what} {nbytes} bytes" for _, what, nbytes in moved)
          + f": the least they could take {least_s * 1e6:.3f} us, and they "
          f"took {total_ns / 1e3:.3f} us of device time", flush=True)
    return 100.0 * least_s / (total_ns / 1e9)
