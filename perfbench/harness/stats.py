"""The arithmetic of the metrics: quantiles, spread, bus bandwidth, the seeded schedule."""

import random
import statistics

def percentile(values, p):
    """The ``p``-th percentile (0-100) by linear interpolation between
    the sorted samples, the rule of ``numpy.percentile``'s default."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    k = (len(s) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``: the spread the
    benchmark's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# nccl-tests' bus-bandwidth factors (doc/PERFORMANCE.md there): what the
# busiest link carries per byte of the per-rank payload, the payload being
# what a rank sends (`drivers/collectives.py payload_bytes`).  nccl-tests
# counts an allgather by the n shards a rank holds after it and multiplies
# them by (n - 1) / n; by the one shard it sends, that is n - 1.
BUSBW_FACTOR = {
    "allreduce": lambda n: 2 * (n - 1) / n,
    "allgather": lambda n: n - 1,
    "alltoall": lambda n: (n - 1) / n,
    "bcast": lambda n: 1.0,
    "sendrecv": lambda n: 1.0,
    "halo": lambda n: 1.0,
}


def busbw_gbps(op, payload_bytes, n_ranks, seconds_per_call):
    """Bus bandwidth in GB/s (1e9 bytes a second) of one call that moves
    ``payload_bytes`` per rank in ``seconds_per_call``."""
    return payload_bytes * BUSBW_FACTOR[op](n_ranks) / seconds_per_call / 1e9


def schedule(rows, seed):
    """The order of one cycle of batches: every row ``slots`` times, in
    an order drawn from the seed.  Every seed gets the same multiset, so
    a seed changes the order of the work and never its amount."""
    cycle = [row["name"] for row in rows for _ in range(int(row["slots"]))]
    if not cycle:
        raise ValueError("a workload needs at least one row with a slot")
    random.Random(int(seed)).shuffle(cycle)
    return cycle
