"""What the library's call of one row of the table costs over the same
call written by hand (``drivers/collectives.py plain_op``): seconds a
call over seconds a call, both in the same chained program, batches of
each taken in turn after the traced window.  1.0 is the floor: what is
over it is what a ``perf_opt`` PR can win on this row."""

ROW = "bcast-4MiB"


def read(view):
    pair = view.probe.get("rows", {}).get(ROW)
    return pair["library"] / pair["plain"] if pair else None
