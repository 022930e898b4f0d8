"""Share of the HBM roofline the snapshot program reaches, in per cent.

The least a snapshot can move: each of its fields' padded arrays read
once (the interior's blocks lie in every tile of it) and the coarse
field written once.  fields x ((ny+2G)(nx+2G) + (ny/c)(nx/c)) x 4 bytes
over the table's HBM bandwidth, divided by the device time of one
execution of the snapshot program from the trace (the union of its leaf
events, a mean over the executions the trace holds whole).  Bound:
bandwidth (a mean of c x c cells is one addition a
cell)."""

from perfbench.harness import scopes, trace

SNAPSHOT = "snapshot"


def least_bytes_per_snapshot(fields, padded_field_bytes, coarse_field_bytes):
    return fields * (padded_field_bytes + coarse_field_bytes)


def read(view):
    placed = scopes.by_execution(
        *view.session.traced_programs(view.trace, view.traced))
    if placed is None:
        return None
    mine = [events for of_chip in placed.values()
            for key, events in of_chip if key == SNAPSHOT]
    if not mine:
        return None
    per_snapshot = sum(trace.union_ns(events) for events in mine) / len(mine) / 1e9
    facts = view.facts
    least_s = least_bytes_per_snapshot(
        facts["snapshot_fields"], facts["padded_field_bytes"],
        facts["coarse_field_bytes"]) / (view.peaks["hbm_gbps"] * 1e9)
    print(f"perfbench: a snapshot takes {per_snapshot * 1e6:.3f} us of device "
          f"time, the least its bytes could {least_s * 1e6:.3f} us", flush=True)
    return 100.0 * least_s / per_snapshot
