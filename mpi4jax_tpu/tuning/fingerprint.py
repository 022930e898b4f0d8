"""Topology fingerprint for the on-disk tuning cache.

A cached knob vector is only valid for the fabric it was measured on:
the same process count, the same host layout (hosts x locals-per-host
— the inputs the hierarchical-plane selection is built from), and the
same knob schema (a knob added or re-interpreted invalidates every
older cache).  The fingerprint hashes exactly those inputs; anything
else (link health, other jobs' load) is deliberately NOT covered — see
docs/sharp-bits.md "stale tuning caches" for why a cache can go stale
without the fingerprint changing.

stdlib only: the pure-core tests (tests/test_tuning.py) load this on
old-jax containers through the package-stub loader.
"""

import hashlib
import json

__all__ = ["KNOB_SCHEMA_VERSION", "topology_fingerprint"]

# Bump whenever the knob vector's meaning changes (a knob added,
# removed, or re-interpreted): caches written under another schema are
# ignored wholesale rather than half-applied.
# v2: the `stripes` knob joined the vector (striped multi-connection
# links, docs/performance.md "striped links and the zero-copy path").
# v3: the `wire_dtype` knob joined the vector (compressed collectives,
# docs/performance.md "Compressed collectives").
# v4: the `wire_backend` knob joined the vector (io_uring data plane,
# docs/performance.md "io_uring wire backend").
KNOB_SCHEMA_VERSION = 4


def topology_fingerprint(topology, world_size,
                         schema_version=KNOB_SCHEMA_VERSION):
    """Stable hex fingerprint of (host layout, nprocs, knob schema).

    ``topology`` is the bridge's bootstrap map (``runtime.topology()``:
    ``{"n_hosts", ...}``) or ``None``/``{}`` for a single-host world
    with no native topology.  Only rank-invariant fields participate:
    per-rank fields (``host_id``, ``local_rank``, ``leader_rank``)
    would make ranks disagree on the fingerprint, and so would the
    raw ``local_size`` on an UNEVEN host layout (a 6+2 split gives
    different values per host) — locals-per-host is therefore derived
    as ``ceil(nprocs / n_hosts)``, which every rank computes
    identically.
    """
    topo = topology or {}
    n_hosts = int(topo.get("n_hosts", 1) or 1)
    basis = {
        "schema": int(schema_version),
        "nprocs": int(world_size),
        "n_hosts": n_hosts,
        "locals_per_host": -(-int(world_size) // max(n_hosts, 1)),
    }
    digest = hashlib.sha256(
        json.dumps(basis, sort_keys=True).encode("utf-8")
    ).hexdigest()
    return digest[:16]
