"""The room a ``perf_opt`` PR has on ``coll_table_geomean_us``: the
geometric mean of the library's seconds a call over that of the plain
programs', over the rows of ``roles.table``, from the probe after the
traced window (every row's own ratio is ``row_tax.<row>``).  A row
whose plain program gave no times leaves the metric out."""

import statistics


def read(view):
    rows = view.facts["roles"].get("table")
    pairs = [view.probe.get("rows", {}).get(row) for row in rows or ()]
    if not pairs or not all(pairs):
        return None
    return (statistics.geometric_mean(p["library"] for p in pairs)
            / statistics.geometric_mean(p["plain"] for p in pairs))
