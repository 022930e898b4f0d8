"""Metrics registry: counters + fixed-bucket histograms with p50/p99.

The pure-Python twin of the native metrics table (telemetry.h): rows
keyed by (comm, op, plane) holding counts, byte totals and log2
latency/size histograms.  Used two ways:

* hydrated from a native snapshot (``MetricsRegistry.from_snapshot``,
  via ``runtime.metrics_snapshot()``) — the benchmark/`t4j-top` path;
* fed directly (``observe``) — the same bucketing math, so the
  percentile derivation is testable without the native bridge
  (tests/test_telemetry.py runs on old-jax containers).

Percentiles come from the histograms: the value at quantile q is the
geometric midpoint of the bucket where the cumulative count crosses
q * total, clamped to the observed min/max — a <= 2x-per-bucket
estimator, which is what fixed-bucket histograms buy (the native side
cannot afford per-sample reservoirs on the op path).

Import-free of jax (stdlib only), like the rest of this package.
"""

from .schema import (
    SCHEMA_VERSION,
    SchemaError,
    kind_name,
    parse_snapshot,
    plane_name,
)

# native defaults (telemetry.h); from_snapshot overrides from the header
LAT_BUCKETS = 24
LAT_BASE_LOG2 = 10
SIZE_BUCKETS = 20
SIZE_BASE_LOG2 = 6


def log2_bucket(value, base_log2, nbuckets):
    """The native ``tel::log2_bucket``, bit for bit: bucket i covers
    [2^(base+i), 2^(base+i+1)), everything below the base lands in
    bucket 0, everything at or above the top in the last bucket."""
    v = int(value) >> base_log2
    if v == 0:
        return 0
    b = 0
    while v > 1 and b < nbuckets - 1:
        v >>= 1
        b += 1
    return b


class Histogram:
    """Fixed log2-bucket histogram with quantile estimation."""

    def __init__(self, base_log2, nbuckets, counts=None):
        self.base_log2 = int(base_log2)
        self.counts = list(counts) if counts is not None else [0] * nbuckets
        if counts is not None and len(self.counts) != nbuckets:
            raise SchemaError(
                f"histogram has {len(self.counts)} buckets, want {nbuckets}"
            )

    @property
    def total(self):
        return sum(self.counts)

    def add(self, value):
        self.counts[
            log2_bucket(value, self.base_log2, len(self.counts))
        ] += 1

    def merge(self, other):
        if (other.base_log2 != self.base_log2
                or len(other.counts) != len(self.counts)):
            raise SchemaError("cannot merge histograms of different shape")
        for i, c in enumerate(other.counts):
            self.counts[i] += c

    def bucket_bounds(self, i):
        lo = 1 << (self.base_log2 + i)
        hi = 1 << (self.base_log2 + i + 1)
        if i == 0:
            lo = 0
        return lo, hi

    def quantile(self, q):
        """Estimated value at quantile ``q`` in [0, 1], or ``None`` when
        empty: the geometric midpoint of the crossing bucket."""
        total = self.total
        if total == 0:
            return None
        want = q * total
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= want and c:
                lo, hi = self.bucket_bounds(i)
                return ((max(lo, 1)) * hi) ** 0.5
        return None  # unreachable with total > 0


class Row:
    __slots__ = ("count", "bytes", "sum_ns", "min_ns", "max_ns", "lat",
                 "size")

    def __init__(self, lat_base=LAT_BASE_LOG2, lat_n=LAT_BUCKETS,
                 size_base=SIZE_BASE_LOG2, size_n=SIZE_BUCKETS):
        self.count = 0
        self.bytes = 0
        self.sum_ns = 0
        self.min_ns = 0  # 0 = unset, matching the native table
        self.max_ns = 0
        self.lat = Histogram(lat_base, lat_n)
        self.size = Histogram(size_base, size_n)

    def observe(self, nbytes, dur_ns):
        self.count += 1
        self.bytes += int(nbytes)
        self.sum_ns += int(dur_ns)
        if self.min_ns == 0 or dur_ns < self.min_ns:
            self.min_ns = int(dur_ns)
        if dur_ns > self.max_ns:
            self.max_ns = int(dur_ns)
        self.lat.add(dur_ns)
        self.size.add(nbytes)

    def merge(self, other):
        self.count += other.count
        self.bytes += other.bytes
        self.sum_ns += other.sum_ns
        if other.min_ns and (self.min_ns == 0 or other.min_ns < self.min_ns):
            self.min_ns = other.min_ns
        if other.max_ns > self.max_ns:
            self.max_ns = other.max_ns
        self.lat.merge(other.lat)
        self.size.merge(other.size)

    def latency_ns(self, q):
        """Quantile estimate clamped to the exact observed extremes."""
        v = self.lat.quantile(q)
        if v is None:
            return None
        if self.min_ns:
            v = max(v, self.min_ns)
        if self.max_ns:
            v = min(v, self.max_ns)
        return v

    def stats(self):
        return {
            "count": self.count,
            "bytes": self.bytes,
            "mean_ms": (self.sum_ns / self.count / 1e6) if self.count
            else None,
            "min_ms": self.min_ns / 1e6 if self.min_ns else None,
            "max_ms": self.max_ns / 1e6 if self.max_ns else None,
            "p50_ms": (lambda v: v / 1e6 if v else None)(
                self.latency_ns(0.50)),
            "p99_ms": (lambda v: v / 1e6 if v else None)(
                self.latency_ns(0.99)),
        }


class MetricsRegistry:
    """Rows keyed by (comm, op name, plane name); see module docstring."""

    def __init__(self, lat_base=LAT_BASE_LOG2, lat_n=LAT_BUCKETS,
                 size_base=SIZE_BASE_LOG2, size_n=SIZE_BUCKETS):
        self._shape = (lat_base, lat_n, size_base, size_n)
        self.rows = {}
        self.version = SCHEMA_VERSION

    def _row(self, comm, op, plane):
        key = (int(comm), str(op), str(plane))
        row = self.rows.get(key)
        if row is None:
            row = self.rows[key] = Row(*self._shape)
        return row

    def observe(self, comm, op, plane, nbytes, dur_ns):
        self._row(comm, op, plane).observe(nbytes, dur_ns)

    @classmethod
    def from_snapshot(cls, words):
        """Hydrate from a native u64-word snapshot (or an already
        ``parse_snapshot``-ed dict)."""
        snap = words if isinstance(words, dict) else parse_snapshot(words)
        first = snap["rows"][0] if snap["rows"] else None
        reg = cls(
            snap["lat_base_log2"],
            len(first["lat"]) if first else LAT_BUCKETS,
            snap["size_base_log2"],
            len(first["size"]) if first else SIZE_BUCKETS,
        )
        for r in snap["rows"]:
            row = reg._row(r["comm"], kind_name(r["kind"]),
                           plane_name(r["plane"]))
            row.count += r["count"]
            row.bytes += r["bytes"]
            row.sum_ns += r["sum_ns"]
            row.min_ns = r["min_ns"]
            row.max_ns = r["max_ns"]
            row.lat.merge(Histogram(snap["lat_base_log2"], len(r["lat"]),
                                    r["lat"]))
            row.size.merge(Histogram(snap["size_base_log2"],
                                     len(r["size"]), r["size"]))
        return reg

    def merge(self, other):
        """Fold another registry in (cross-rank aggregation)."""
        for key, row in other.rows.items():
            mine = self.rows.get(key)
            if mine is None:
                mine = self.rows[key] = Row(*self._shape)
            mine.merge(row)
        return self

    def diff(self, prev):
        """Window delta: this registry minus ``prev`` (both cumulative
        native snapshots).  Counters and histogram buckets subtract;
        min/max are reset to unset — the native table tracks them over
        the whole process, so the window extremes are unknowable and a
        stale clamp would distort the window's percentiles.  Benchmarks
        use this to attribute latencies to ONE timed phase instead of
        everything since init."""
        out = MetricsRegistry(*self._shape)
        for key, row in self.rows.items():
            base = prev.rows.get(key)
            d = out._row(*key)
            d.count = row.count - (base.count if base else 0)
            d.bytes = row.bytes - (base.bytes if base else 0)
            d.sum_ns = row.sum_ns - (base.sum_ns if base else 0)
            for i, c in enumerate(row.lat.counts):
                d.lat.counts[i] = c - (base.lat.counts[i] if base else 0)
            for i, c in enumerate(row.size.counts):
                d.size.counts[i] = c - (base.size.counts[i] if base else 0)
            if d.count <= 0:
                del out.rows[(int(key[0]), str(key[1]), str(key[2]))]
        return out

    def aggregate(self, op=None, plane=None, comm=None):
        """One merged :class:`Row` over every row matching the filters
        (``None`` = any), or ``None`` when nothing matches."""
        out = None
        for (c, o, p), row in self.rows.items():
            if op is not None and o != op:
                continue
            if plane is not None and p != plane:
                continue
            if comm is not None and c != int(comm):
                continue
            if out is None:
                out = Row(*self._shape)
            out.merge(row)
        return out

    def op_latency(self, op, plane=None, comm=None):
        """{count, bytes, mean_ms, min_ms, max_ms, p50_ms, p99_ms} for
        one op (optionally one plane/comm), or ``None``."""
        row = self.aggregate(op=op, plane=plane, comm=comm)
        return row.stats() if row is not None else None

    def bytes_by_plane(self):
        """Total payload bytes per data plane over the op rows."""
        out = {}
        for (_c, _o, plane), row in self.rows.items():
            out[plane] = out.get(plane, 0) + row.bytes
        return out

    def ops(self):
        return sorted({o for (_c, o, _p) in self.rows})
