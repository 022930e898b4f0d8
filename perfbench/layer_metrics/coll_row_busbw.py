"""The lowest bus bandwidth over the table's rows of 4 MiB and more,
from the window's batches (every row's own value is printed by the
driver on an earlier line)."""

FLOOR_BYTES = 4 << 20


def read(view):
    session = view.session
    rates = []
    for name in session.rows:
        if session.payload_bytes(name) < FLOOR_BYTES:
            continue
        per_call = session.per_call(view.samples + view.traced, name)
        if per_call:
            rates.append(session.busbw(name, per_call))
    return min(rates) if rates else None
