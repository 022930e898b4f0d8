"""Share of the HBM roofline a save's staging program reaches, in per
cent.  The least it can move: the state read once and its pieces
written once, 2 x the state's bytes, over the table's HBM bandwidth,
divided by the device time of one execution of the staging program from
the trace (the union of its leaf events, a mean over the executions the
trace holds).  Bound: bandwidth (a piece is a copy)."""

from perfbench.harness import scopes, trace

STAGE = "stage"


def least_bytes_per_save(state_bytes):
    return 2 * state_bytes


def read(view):
    placed = scopes.by_execution(
        *view.session.traced_programs(view.trace, view.traced))
    if placed is None:
        return None
    mine = [events for of_chip in placed.values()
            for key, events in of_chip if key == STAGE]
    if not mine:
        return None
    per_save = sum(trace.union_ns(events) for events in mine) / len(mine) / 1e9
    least_s = (least_bytes_per_save(view.facts["state_bytes"])
               / (view.peaks["hbm_gbps"] * 1e9))
    print(f"perfbench: a save's staging takes {per_save * 1e6:.3f} us of "
          f"device time, the least its bytes could {least_s * 1e6:.3f} us",
          flush=True)
    return 100.0 * least_s / per_save
