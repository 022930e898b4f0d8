"""Share of a chip's memory that an inner-loop iteration needs at its
peak, in per cent: the fullest of its three compiled programs (the
tangent-linear sweep, the adjoint sweep, the vector updates) by the
program's own buffer assignment
(``compiled.memory_analysis().peak_memory_in_bytes``: arguments, results
and temporaries at their fullest), plus the arrays held between the
programs that are no argument of that program, over the table's
``hbm_bytes``.  Held between the programs: the first guess, the
trajectory's first level (the state each call starts from, the outer
loop's, kept across the inner loop), the observations and the loop's
vectors; a sweep takes the first guess and the trajectory as arguments,
and the tangent sweep a direction, so what is added to a sweep's peak is
the rest.  Prints each program's peak and what the session holds by
shapes (``InnerLoop.stats()``' ``trajectory_bytes`` and
``vector_bytes``).  Repeats exactly.

``None`` where the session has no such program, or this jax's analysis
no peak."""

PROGRAMS = ("tangent", "adjoint", "update")


def read(view):
    session = view.session
    if not hasattr(session, "compiled") or not hasattr(session, "held_bytes"):
        return None
    held = session.held_bytes()
    fullest = {}
    for key in PROGRAMS:
        analysis = session.compiled(key).memory_analysis()
        peak = getattr(analysis, "peak_memory_in_bytes", None)
        if not peak:
            print(f"perfbench: the compiled {key} program's memory analysis "
                  "gives no peak: nothing is reported", flush=True)
            return None
        # what the session holds beside this program's own arguments
        beside = max(held - analysis.argument_size_in_bytes, 0)
        fullest[key] = peak + beside
        print(f"perfbench: the {key} program peaks at {peak} bytes "
              f"(temporaries {analysis.temp_size_in_bytes}, arguments "
              f"{analysis.argument_size_in_bytes}, results "
              f"{analysis.output_size_in_bytes}), {beside} bytes held beside "
              "it", flush=True)
    counted = view.facts.get("incremental", {})
    print(f"perfbench: the session holds {held} bytes between the programs; "
          f"the two checkpoint levels {counted.get('trajectory_bytes')} and "
          f"the loop's vectors {counted.get('vector_bytes')} by shapes; a "
          f"chip has {view.peaks['hbm_bytes']}", flush=True)
    return 100.0 * max(fullest.values()) / view.peaks["hbm_bytes"]
