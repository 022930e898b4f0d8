"""Share of a chip's memory that a gradient needs at its peak, in per
cent: the fuller of its two compiled programs (the forward sweep, the
backward sweep) by the program's own buffer assignment
(``compiled.memory_analysis().peak_memory_in_bytes``: arguments, results
and temporaries at their fullest) over the table's ``hbm_bytes``.  A
window's trajectory is what fills a chip in this deployment, and the
checkpointing is what holds it under one: the first program of this
repository whose peak is its design question.  Prints beside it what
the two levels hold by shapes (``Descent.stats()``' ``trajectory_bytes``).
(The line's ``memory_peak_bytes`` is the allocator's peak of
arrays, which on a TPU leaves a program's temporaries out: it sees the
first level, handed from one program to the other, and not the
second.)  Repeats exactly.

``None`` where the session has no such program, or this jax's analysis
no peak."""


def read(view):
    session = view.session
    if not hasattr(session, "compiled"):
        return None
    peaks = {}
    for key in ("forward", "backward"):
        analysis = session.compiled(key).memory_analysis()
        peaks[key] = getattr(analysis, "peak_memory_in_bytes", None)
        if not peaks[key]:
            print(f"perfbench: the compiled {key} sweep's memory analysis "
                  "gives no peak: nothing is reported", flush=True)
            return None
        print(f"perfbench: the {key} sweep peaks at {peaks[key]} bytes "
              f"(temporaries {analysis.temp_size_in_bytes}, arguments "
              f"{analysis.argument_size_in_bytes}, results "
              f"{analysis.output_size_in_bytes})", flush=True)
    kept = view.facts.get("adjoint", {}).get("trajectory_bytes")
    print(f"perfbench: the two checkpoint levels hold {kept} bytes by "
          f"shapes; a chip has {view.peaks['hbm_bytes']}", flush=True)
    return 100.0 * max(peaks.values()) / view.peaks["hbm_bytes"]
