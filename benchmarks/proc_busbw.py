"""DCN-bridge bus bandwidth: allreduce over N OS processes (the proc
tier — one process per rank, data over the native C++ transport in
native/src/dcn.cc).

This is the loopback analog of the reference's ``mpirun -np N`` tier,
where libmpi's shm BTL moves intra-host traffic through shared memory
(the reference gets that for free: mpi_xla_bridge.pyx:149-167 just
calls MPI_Allreduce).  Run under the launcher:

    python -m mpi4jax_tpu.launch -np 8 benchmarks/proc_busbw.py \
        [--mb 64] [--reps 10] [--op allreduce] [--sweep] [--pairs]

Rank 0 prints one JSON line: NCCL-convention bus bandwidth
(``bytes * 2*(n-1)/n / t`` for allreduce).  ``--sweep`` prints one
JSON line per payload size from 1 KB up to ``--mb``, covering both
sides of the tree->ring switchover (``T4J_RING_MIN_BYTES``, see
docs/performance.md "TCP-tier algorithm selection"); every record
carries the chosen data plane (``tree|ring|hier|shm``) plus the
local/leader world sizes and active knob values, so a reader of
the records can attribute wins.  ``--pairs`` (with ``T4J_EMU_LOCAL=k`` to emulate
multiple nodes on one host) measures hier-vs-flat interleaved
same-conditions pairs (docs/performance.md "hierarchical
collectives").  To measure the TCP tier on one host, disable the
same-host shm arena with ``T4J_NO_SHM=1`` — otherwise collectives
ride shared memory and never touch the wire algorithms.
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _fence(comm, tok):
    """Barrier that actually blocks the PYTHON thread: jax dispatch is
    asynchronous, so an unforced ``m.barrier`` lets the caller sail on
    (into buffer setup or a timing window) while the collective is
    still in flight.  Forcing the token stamp makes the fence real."""
    import jax

    import mpi4jax_tpu as m

    tok = m.barrier(comm=comm, token=tok)
    jax.block_until_ready(tok.stamp)
    return tok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=64.0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--op", default="allreduce",
                    choices=["allreduce", "allgather", "alltoall",
                             "reduce_scatter", "halo"])
    ap.add_argument(
        "--sweep", action="store_true",
        help="one JSON line per payload size, 1 KB -> --mb in x4 steps: "
        "both sides of the tree->ring switchover",
    )
    ap.add_argument(
        "--pairs", action="store_true",
        help="interleaved same-conditions hier-vs-flat allreduce pairs "
        "at --mb: each timed batch alternates the hierarchical plane "
        "off/on so phase noise hits both sides equally; one JSON "
        "record per side plus the ratio (run with T4J_EMU_LOCAL=k to "
        "emulate multiple nodes on one host)",
    )
    ap.add_argument(
        "--inflight", type=int, default=0, metavar="N",
        help="issue-depth scaling (docs/async.md): split --mb into N "
        "chunks submitted as N overlapping iallreduce requests "
        "(waitall at the end) vs the same chunks through blocking "
        "allreduces, interleaved same-conditions batches; one JSON "
        "record per arm plus the depth-speedup ratio",
    )
    ap.add_argument(
        "--calibrate", action="store_true",
        help="run the autotuner's calibration rounds (tree/ring per "
        "size, segment candidates, hier when the topology allows, "
        "fused/unfused coalescing pairs) measured via the telemetry "
        "metrics table, and emit one JSON record per arm x size — the "
        "per-size records mpi4jax_tpu.tuning.calibrate.fit_records "
        "consumes — plus the fitted knob vector",
    )
    ap.add_argument(
        "--autotune-pair", action="store_true",
        help="interleaved same-conditions allreduce at --mb under a "
        "deliberately mis-defaulted T4J_SEG_BYTES (16K), the "
        "autotuner's in-run fitted segment, and the hand-tuned default "
        "(1M): one record per arm plus autotuned-vs-misdefault and "
        "autotuned-vs-hand ratios (run with T4J_NO_SHM=1 so the ring "
        "plane, which T4J_SEG_BYTES governs, actually serves)",
    )
    ap.add_argument(
        "--stripes", default=None, metavar="LIST",
        help="striped-wire arms (docs/performance.md \"striped links "
        "and the zero-copy path\"): comma list of dealing widths "
        "(e.g. 1,2,4) A/B'd INTERLEAVED inside one world — launch "
        "with T4J_STRIPES set to the largest width so the connections "
        "exist, and T4J_EMU_FLOW_BPS to emulate the per-flow "
        "bottleneck real NICs impose; one record per width plus "
        "striped-vs-single ratios.  With T4J_ZEROCOPY_MIN_BYTES also "
        "set, a zerocopy-off arm rides along and a "
        "zerocopy_vs_copy ratio is emitted",
    )
    ap.add_argument(
        "--wire-dtype", default=None, metavar="LIST", dest="wire_dtype",
        help="compressed-collective arms (docs/performance.md "
        "\"Compressed collectives\"): comma list of wire dtypes "
        "(off,bf16,fp8) A/B'd INTERLEAVED inside one world via "
        "runtime.set_wire_dtype.  Compression only engages on "
        "cross-host hops, so on a loopback box launch with "
        "T4J_NO_SHM=1 T4J_EMU_LOCAL=1 (every rank its own emulated "
        "host) and T4J_EMU_FLOW_BPS to emulate the NIC bottleneck "
        "that makes the byte saving a time saving; composes with "
        "--stripes (the compressed segments ride the striped wire).  "
        "One record per arm plus a compress_vs_f32 ratio record",
    )
    ap.add_argument(
        "--wire-backend", default=None, metavar="LIST",
        dest="wire_backend",
        help="wire data-plane arms (docs/performance.md \"io_uring "
        "wire backend\"): comma list of backends (sendmsg,uring) "
        "A/B'd INTERLEAVED inside one world via "
        "runtime.set_wire_backend — both backends put identical "
        "bytes on the wire, so the arms are always safe.  Composes "
        "with --stripes (arms run at that dealing width) and "
        "--wire-dtype (first listed mode applies to every arm).  One "
        "record per backend carrying the native tx/rx syscall-counter "
        "deltas as evidence, plus a uring_vs_sendmsg ratio record; a "
        "kernel without io_uring drops the uring arm with an explicit "
        "record instead of silently measuring sendmsg twice",
    )
    ap.add_argument(
        "--widths", default="1,4,16",
        help="halo widths for --op halo (comma list)",
    )
    ap.add_argument(
        "--fields", type=int, default=3,
        help="field count per halo exchange (--op halo); the per-"
        "direction slabs of all fields ride one fused frame when "
        "coalescing is on",
    )
    ap.add_argument(
        "--halo-base", type=int, default=64,
        help="interior cells per side of the local halo block",
    )
    ap.add_argument(
        "--copy-gauntlet", action="store_true",
        help="measure the aggregate plain-memcpy rate of N timesharing "
        "ranks (no collective logic): the scheduler bound the arena's "
        "ceiling model assumes perfect",
    )
    ap.add_argument(
        "--two-tier", action="store_true",
        help="composed ICI+DCN path: each launcher process runs an "
        "8-device virtual mesh, parallel.distributed.two_tier_allreduce "
        "end to end (VERDICT r4 #6)",
    )
    args = ap.parse_args()

    if args.two_tier:
        return _two_tier_main(args)
    if args.copy_gauntlet:
        return _copy_gauntlet_main(args)

    import jax

    jax.config.update("jax_platforms", "cpu")

    import mpi4jax_tpu as m

    comm = m.get_default_comm()
    assert comm.backend == "proc", "run under python -m mpi4jax_tpu.launch"
    n = comm.size
    rank = comm.rank()

    if args.calibrate:
        return _calibrate_main(args, comm)

    if args.autotune_pair:
        return _autotune_pair_main(args, comm)

    if args.op == "halo":
        return _halo_main(args, comm)

    if args.wire_backend:
        return _wire_backend_main(args, comm)

    if args.wire_dtype:
        return _wire_dtype_main(args, comm)

    if args.stripes:
        return _stripes_main(args, comm)

    if args.pairs:
        return _pairs_main(args, comm)

    if args.inflight:
        return _inflight_main(args, comm)

    if args.sweep:
        # 1 KB -> --mb in x4 steps, straddling T4J_RING_MIN_BYTES so
        # the records show both the tree and ring sides per op
        sizes_mb, s = [], 1024.0 / 1e6
        while s < args.mb:
            sizes_mb.append(s)
            s *= 4
        sizes_mb.append(float(args.mb))
        for mb in sizes_mb:
            rec, _bw, _tok = _measure(args, comm, mb)
            if rank == 0:
                print(json.dumps(rec), flush=True)
        return

    rec, busbw, tok = _measure(args, comm, args.mb)
    factor = _busbw_factor(args.op, n)
    if args.op == "allreduce":
        # In-run machine-relative ceiling: the shm arena must move
        # (5n+1)*S bytes of memory traffic per S-byte allreduce
        # (n stage-in copies, an (n+1)-stream fold, n copy-outs — see
        # docs/performance.md), and every byte moves through however
        # many cores the host gives the job.  With C = measured
        # single-core copy rate (payload bytes/s, i.e. traffic/2) and
        # k = cores available, ceiling busbw = 2C*k*factor/(5n+1).
        #
        # That C is measured SOLO — but the arena's copies run on N
        # timesharing ranks, and the r5 copy gauntlet measured N-rank
        # aggregate copy throughput at ~50 % of solo on this box (OS
        # scheduler + VM bandwidth throttling, --copy-gauntlet mode).
        # The scheduler-ADJUSTED ceiling below re-runs that mini
        # gauntlet in-run (every rank copies between barriers) so the
        # pct-of-ceiling is judged against what N processes can
        # actually move, not what one process could.
        # fence the SOLO probe: peers BLOCK at the second fence while
        # rank 0 measures (otherwise their gauntlet buffer setup
        # timeshares the core and deflates the baseline; the fences
        # force the token — async dispatch would let peers sail on)
        tok = _fence(comm, tok)
        copy_gbps = _copy_rate_gbps() if rank == 0 else 0.0
        tok = _fence(comm, tok)
        agg_gbps = _gauntlet_rate_gbps(comm, tok)
        if rank == 0:
            cores = _cores()
            ceiling = 2 * copy_gbps * min(cores, n) * factor / (5 * n + 1)
            adj_ceiling = 2 * agg_gbps * factor / (5 * n + 1)
            rec["single_core_copy_gbps"] = round(copy_gbps, 2)
            rec["gauntlet_agg_copy_gbps"] = round(agg_gbps, 2)
            rec["cores_available"] = cores
            rec["ceiling_gbps"] = round(ceiling, 3)
            rec["pct_of_ceiling"] = round(100 * busbw / 1e9 / ceiling, 1)
            rec["ceiling_sched_adjusted_gbps"] = round(adj_ceiling, 3)
            rec["pct_of_sched_adjusted"] = round(
                100 * busbw / 1e9 / adj_ceiling, 1
            )
    if rank == 0:
        print(json.dumps(rec), flush=True)


def _busbw_factor(op, n):
    """NCCL-tests algorithmic factors relative to the PER-RANK payload
    buffer: allgather receives n-1 peer blocks per rank, so its busbw
    is send_bytes*(n-1)/t; alltoall and reduce_scatter ship (n-1)/n of
    the local buffer."""
    return {
        "allreduce": 2 * (n - 1) / n,
        "allgather": float(n - 1),
        "alltoall": (n - 1) / n,
        "reduce_scatter": (n - 1) / n,
    }[op]


def _telemetry_registry():
    """Cumulative metrics registry from the native snapshot, or ``None``
    when telemetry is off (docs/observability.md).  The LIVE runtime
    mode is authoritative — benchmark modes flip counters on in-process
    (runtime.set_telemetry), which the env-derived config cannot see."""
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.telemetry.registry import MetricsRegistry

    if runtime.telemetry_mode_name() == "off":
        return None
    words = runtime.metrics_snapshot()
    return MetricsRegistry.from_snapshot(words) if words else None


def _telemetry_keys(op, before):
    """Latency + per-plane byte keys for one timed window, sourced from
    the telemetry snapshot delta (``before`` = the registry captured
    when the window opened).  These are MEASURED per-op latencies from
    the native histograms — the numbers trace-guided autotuning
    (ROADMAP item 4) and serving SLOs (item 5) consume — not wall-clock
    reps/total arithmetic."""
    after = _telemetry_registry()
    if after is None:
        return {}
    window = after.diff(before) if before is not None else after
    stats = window.aggregate(op=op)
    if stats is None or stats.count == 0:
        return {}
    s = stats.stats()
    keys = {
        "lat_source": "telemetry",
        "op_count": s["count"],
        "p50_ms": round(s["p50_ms"], 4) if s["p50_ms"] else None,
        "p99_ms": round(s["p99_ms"], 4) if s["p99_ms"] else None,
        "mean_ms": round(s["mean_ms"], 4) if s["mean_ms"] else None,
    }
    for plane, nbytes in sorted(window.bytes_by_plane().items()):
        keys[f"bytes_{plane}"] = nbytes
    return keys


def _measure(args, comm, mb):
    """Time ``args.op`` at one payload size.

    Returns ``(record, busbw, token)`` — ``busbw`` is the unrounded
    bytes/s figure (the record's ``value`` is rounded for display; the
    ceiling percentages must divide the exact measurement)."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.utils import config

    n = comm.size
    per = max(int(mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4

    def call(v, tok):
        if args.op == "allreduce":
            return m.allreduce(v, m.SUM, comm=comm, token=tok)
        if args.op == "allgather":
            y, tok = m.allgather(v, comm=comm, token=tok)
            return y[0], tok
        if args.op == "reduce_scatter":
            return m.reduce_scatter(v.reshape(n, -1), m.SUM, comm=comm,
                                    token=tok)
        blk = v.reshape(n, -1)
        y, tok = m.alltoall(blk, comm=comm, token=tok)
        return y.reshape(v.shape), tok

    # warm (compile + first-touch of transport buffers)
    tok = m.create_token()
    y, tok = call(x, tok)
    np.asarray(y)

    # telemetry window opens AFTER warmup: the snapshot delta then
    # attributes latencies to the timed reps only
    tel_before = _telemetry_registry()
    best = float("inf")
    for _ in range(3):
        tok = _fence(comm, tok)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            y, tok = call(x, tok)
        np.asarray(y)  # materialise: all reps done
        dt = (time.perf_counter() - t0) / args.reps
        best = min(best, dt)

    busbw = nbytes * _busbw_factor(args.op, n) / best
    tel_keys = _telemetry_keys(args.op, tel_before)

    algo, topo = _data_plane(args.op, comm, nbytes)
    rec = {
        "metric": f"{args.op}_busbw_proc{n}",
        "value": round(busbw / 1e9, 3),
        "unit": "GB/s",
        "nprocs": n,
        "payload_mb": nbytes / 1e6,
        "payload_bytes": nbytes,
        "sec_per_call": round(best, 6),
        "data_plane": algo,
        "local_world": topo["local_size"],
        "leader_world": topo["n_hosts"],
        "ring_min_bytes": config.ring_min_bytes(),
        "seg_bytes": config.seg_bytes(),
        "leader_ring_min_bytes": config.leader_ring_min_bytes(),
    }
    rec.update(tel_keys)
    return rec, busbw, tok


def _data_plane(op, comm, nbytes):
    """(chosen algorithm, topology) for one op at one size — mirrors
    the native selection predicates (dcn.cc: the same-host arena gate,
    use_hier, use_ring), so sweep records can attribute wins to the
    plane that actually served them.  The hier answer comes from the
    native bridge itself (``runtime.hier_would_select``), not a
    re-derivation, so the label cannot drift from the selection."""
    import os

    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    topo = proc_topology(comm)
    shm_on = os.environ.get("T4J_NO_SHM", "").strip() in ("", "0")
    if shm_on and topo["n_hosts"] == 1 and n > 1:
        return "shm", topo
    total = nbytes * n if op == "allgather" else nbytes
    if op != "alltoall" and runtime.hier_would_select(
        runtime.comm_handle(comm), total
    ):
        return "hier", topo
    if op == "alltoall":
        return "pairwise", topo
    return ("ring" if total >= config.ring_min_bytes() else "tree"), topo


def _pairs_main(args, comm):
    """Interleaved same-conditions hier-vs-flat allreduce pairs.

    Each timed batch runs the flat plane (``set_hier("off")``) and the
    hierarchical plane (``set_hier("on")``) back to back, alternating
    across batches, so slow drift of the machine hits both sides equally —
    the measurement convention of the PR-2 tree/ring comparison.  Rank
    0 prints one record per side plus a ratio record."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    per = max(int(args.mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4
    factor = _busbw_factor("allreduce", n)

    tok = m.create_token()
    best = {"off": float("inf"), "on": float("inf")}
    for mode in ("off", "on"):  # warm both planes (compile + negotiate)
        runtime.set_hier(mode=mode)
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
    for _ in range(3):
        for mode in ("off", "on"):
            runtime.set_hier(mode=mode)
            tok = _fence(comm, tok)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
            np.asarray(y)
            best[mode] = min(
                best[mode], (time.perf_counter() - t0) / args.reps
            )
    runtime.set_hier(mode="auto")
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    flat = "ring" if nbytes >= config.ring_min_bytes() else "tree"
    vals = {}
    for mode, plane in (("off", flat), ("on", "hier")):
        busbw = nbytes * factor / best[mode]
        vals[plane] = busbw
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}",
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "payload_bytes": nbytes,
            "sec_per_call": round(best[mode], 6),
            "data_plane": plane,
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "seg_bytes": config.seg_bytes(),
            "interleaved_pairs": True,
        }), flush=True)
    print(json.dumps({
        "metric": f"allreduce_hier_vs_flat_proc{n}",
        "value": round(vals["hier"] / vals[flat], 2),
        "unit": "x",
        "nprocs": n,
        "payload_mb": nbytes / 1e6,
        "flat_plane": flat,
        "local_world": topo["local_size"],
        "leader_world": topo["n_hosts"],
    }), flush=True)


def _stripes_main(args, comm):
    """Interleaved striped-wire arms (docs/performance.md "striped
    links and the zero-copy path").

    One world, built at the LAUNCHED ``T4J_STRIPES`` width; each timed
    batch rotates through the requested dealing widths back to back
    (``runtime.set_wire(stripes=w)`` is a runtime knob up to the built
    width), so phase noise hits every arm equally — the same
    interleaving convention as the hier/flat and coalescing pairs.
    Run under ``T4J_EMU_FLOW_BPS`` to emulate the per-flow bottleneck
    real NIC-bound fabrics impose (one memory bus cannot otherwise
    show the multi-NIC-queue win — docs/performance.md states the
    loopback caveat).  With ``T4J_ZEROCOPY_MIN_BYTES`` set, a
    zerocopy-off arm at the widest width rides along.  Rank 0 prints
    one record per arm plus ``striped_vs_single`` (and
    ``zerocopy_vs_copy``) ratio records."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    widths = sorted({int(w) for w in str(args.stripes).split(",") if w})
    info = runtime.wire_info() or {}
    built = int(info.get("stripes_built", 1) or 1)
    usable = [w for w in widths if 1 <= w <= built]
    dropped = [w for w in widths if w not in usable]
    if comm.rank() == 0 and dropped:
        print(json.dumps({
            "metric": f"stripes_arms_dropped_proc{n}",
            "value": dropped,
            "reason": f"built width is {built} (launch with "
                      f"T4J_STRIPES={max(widths)} to build the "
                      "connections)",
        }), flush=True)
    if not usable:
        usable = [built]
    per = max(int(args.mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4
    factor = _busbw_factor("allreduce", n)
    zc_req = int(info.get("zerocopy_min_bytes", 0) or 0)
    zc_armed = bool(info.get("zerocopy")) and zc_req > 0
    arms = [("stripes", w, None) for w in usable]
    if zc_armed:
        # zerocopy-off comparison arm at the widest width: same wire,
        # copy path forced (T4J_ZEROCOPY_MIN_BYTES=0 at runtime)
        arms.append(("zerocopy_off", max(usable), 0))

    tok = m.create_token()
    best = {}
    for name, w, zc in arms:  # warm every arm (compile + dealing)
        runtime.set_wire(stripes=w,
                         zerocopy_min_bytes=zc if zc is not None
                         else zc_req)
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
    for _ in range(3):
        for name, w, zc in arms:
            runtime.set_wire(stripes=w,
                             zerocopy_min_bytes=zc if zc is not None
                             else zc_req)
            tok = _fence(comm, tok)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
            np.asarray(y)
            key = (name, w)
            dt = (time.perf_counter() - t0) / args.reps
            best[key] = min(best.get(key, float("inf")), dt)
    runtime.set_wire(stripes=built, zerocopy_min_bytes=zc_req)
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    vals = {}
    for name, w, zc in arms:
        busbw = nbytes * factor / best[(name, w)]
        vals[(name, w)] = busbw
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}",
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "payload_bytes": nbytes,
            "sec_per_call": round(best[(name, w)], 6),
            "data_plane": "ring" if nbytes >= config.ring_min_bytes()
            else "tree",
            "stripes": w,
            "stripes_built": built,
            "zerocopy": bool(zc_armed and zc is None),
            "emu_flow_bps": int(info.get("emu_flow_bps", 0) or 0),
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "seg_bytes": config.seg_bytes(),
            "interleaved_pairs": True,
        }), flush=True)
    widest = max(usable)
    if 1 in usable and widest > 1:
        print(json.dumps({
            "metric": f"allreduce_striped_vs_single_proc{n}",
            "value": round(
                vals[("stripes", widest)] / vals[("stripes", 1)], 2),
            "unit": "x",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "stripes": widest,
            "emu_flow_bps": int(info.get("emu_flow_bps", 0) or 0),
        }), flush=True)
    if zc_armed:
        print(json.dumps({
            "metric": f"allreduce_zerocopy_vs_copy_proc{n}",
            "value": round(
                vals[("stripes", widest)]
                / vals[("zerocopy_off", widest)], 2),
            "unit": "x",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "stripes": widest,
            "zerocopy_min_bytes": zc_req,
        }), flush=True)


def _wire_dtype_main(args, comm):
    """Interleaved compressed-collective arms (docs/performance.md
    "Compressed collectives").

    One world; each timed batch rotates through the requested wire
    dtypes back to back (``runtime.set_wire_dtype(mode)`` is a pure
    runtime knob — no rebuild, no renegotiation), so phase noise hits
    every arm equally — the same interleaving convention as the
    hier/flat and striped pairs.  Compression engages only when every
    ring hop is cross-host, so a loopback box must launch with
    ``T4J_NO_SHM=1 T4J_EMU_LOCAL=1`` (each rank its own emulated
    host); ``T4J_EMU_FLOW_BPS`` then makes the byte saving a TIME
    saving the way a NIC-bound fabric would.  Per-arm wire byte
    counters (``runtime.wire_dtype_info`` deltas) ride each record as
    proof the arm actually compressed — a record whose
    ``wire_bytes_delta`` is 0 for a compressed mode measured the f32
    path and says so via ``compressed_engaged``.  With ``--stripes N``
    the arms run at that dealing width (compressed segments ride the
    striped wire).  Rank 0 prints one record per arm plus a
    ``compress_vs_f32`` ratio record per compressed mode."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    modes = []
    for tokn in str(args.wire_dtype).split(","):
        tokn = tokn.strip().lower()
        if not tokn:
            continue
        if tokn not in runtime.WIRE_DTYPE_CODES:
            raise SystemExit(
                f"--wire-dtype: unknown mode {tokn!r} "
                f"(want {'|'.join(runtime.WIRE_DTYPE_CODES)})"
            )
        if tokn not in modes:
            modes.append(tokn)
    if "off" not in modes:
        modes.insert(0, "off")  # the f32 baseline every ratio divides by

    info0 = runtime.wire_dtype_info() or {}
    launched = info0.get("wire_dtype", "off")
    winfo = runtime.wire_info() or {}
    stripes = None
    if args.stripes:
        built = int(winfo.get("stripes_built", 1) or 1)
        stripes = min(max(int(w) for w in str(args.stripes).split(",")
                          if w), built)
        runtime.set_wire(stripes=stripes)

    per = max(int(args.mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4
    factor = _busbw_factor("allreduce", n)

    tok = m.create_token()
    for mode in modes:  # warm every arm (compile + staging buffers)
        runtime.set_wire_dtype(mode)
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
    best = {}
    wire_delta = {}
    for _ in range(3):
        for mode in modes:
            runtime.set_wire_dtype(mode)
            tok = _fence(comm, tok)
            before = runtime.wire_dtype_info() or {}
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
            np.asarray(y)
            dt = (time.perf_counter() - t0) / args.reps
            best[mode] = min(best.get(mode, float("inf")), dt)
            after = runtime.wire_dtype_info() or {}
            wire_delta[mode] = {
                k: int(after.get(k, 0)) - int(before.get(k, 0))
                for k in ("wire_logical_bytes", "wire_bytes")
            }
    runtime.set_wire_dtype(launched)
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    vals = {}
    for mode in modes:
        busbw = nbytes * factor / best[mode]
        vals[mode] = busbw
        delta = wire_delta.get(mode, {})
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}",
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "payload_bytes": nbytes,
            "sec_per_call": round(best[mode], 6),
            "data_plane": "ring" if nbytes >= config.ring_min_bytes()
            else "tree",
            "wire_dtype": mode,
            "compressed_engaged": bool(delta.get("wire_bytes", 0) > 0),
            "wire_logical_bytes_delta": delta.get(
                "wire_logical_bytes", 0),
            "wire_bytes_delta": delta.get("wire_bytes", 0),
            "stripes": stripes,
            "emu_flow_bps": int(winfo.get("emu_flow_bps", 0) or 0),
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "seg_bytes": config.seg_bytes(),
            "interleaved_pairs": True,
        }), flush=True)
    for mode in modes:
        if mode == "off":
            continue
        print(json.dumps({
            "metric": f"allreduce_compress_vs_f32_proc{n}",
            "value": round(vals[mode] / vals["off"], 2),
            "unit": "x",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "wire_dtype": mode,
            "compressed_engaged": bool(
                wire_delta.get(mode, {}).get("wire_bytes", 0) > 0),
            "emu_flow_bps": int(winfo.get("emu_flow_bps", 0) or 0),
        }), flush=True)


def _wire_backend_main(args, comm):
    """Interleaved wire data-plane arms (docs/performance.md "io_uring
    wire backend").

    One world; each timed batch rotates through the requested backends
    back to back (``runtime.set_wire_backend(b)`` is a pure runtime
    knob — both backends put identical bytes on the wire, so no
    renegotiation), the same interleaving convention as the hier/flat,
    striped and compressed pairs.  The claim under test is
    syscall-bound small-frame latency, so each record carries a
    per-call p50 AND the native per-link syscall-counter deltas
    (``runtime.link_stats`` ``tx_syscalls``/``rx_syscalls``) — the
    evidence is the measured kernel-crossing count dropping per call,
    never a hand-derived estimate.  Composes with ``--stripes`` (arms
    run at that dealing width) and ``--wire-dtype`` (the first listed
    mode applies to every arm).  A kernel without io_uring drops the
    uring arm with an explicit ``wire_backend_arms_dropped`` record.
    Rank 0 prints one record per backend plus a ``uring_vs_sendmsg``
    ratio record when both arms ran."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    backends = []
    for tokn in str(args.wire_backend).split(","):
        tokn = tokn.strip().lower()
        if not tokn:
            continue
        if tokn not in ("sendmsg", "uring"):
            raise SystemExit(
                f"--wire-backend: unknown backend {tokn!r} "
                "(want sendmsg|uring)"
            )
        if tokn not in backends:
            backends.append(tokn)
    if "sendmsg" not in backends:
        backends.insert(0, "sendmsg")  # the baseline every ratio needs

    binfo = runtime.wire_backend_info() or {}
    launched = binfo.get("wire_backend", "auto")
    if "uring" in backends and not binfo.get("uring_supported"):
        # explicit skip record: the output must show the arm was
        # dropped for a reason, not silently measure sendmsg twice
        if comm.rank() == 0:
            print(json.dumps({
                "metric": f"wire_backend_arms_dropped_proc{n}",
                "dropped": ["uring"],
                "reason": "no usable io_uring on this kernel",
                "nprocs": n,
            }), flush=True)
        backends = [b for b in backends if b != "uring"]

    winfo = runtime.wire_info() or {}
    stripes = None
    if args.stripes:
        built = int(winfo.get("stripes_built", 1) or 1)
        stripes = min(max(int(w) for w in str(args.stripes).split(",")
                          if w), built)
        runtime.set_wire(stripes=stripes)
    wdtype = None
    launched_dtype = (runtime.wire_dtype_info()
                      or {}).get("wire_dtype", "off")
    if args.wire_dtype:
        wdtype = str(args.wire_dtype).split(",")[0].strip().lower()
        runtime.set_wire_dtype(wdtype)

    per = max(int(args.mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4
    factor = _busbw_factor("allreduce", n)

    tok = m.create_token()
    for b in backends:  # warm every arm (ring setup, buffer regs)
        runtime.set_wire_backend(b)
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
    times = {b: [] for b in backends}
    sys_delta = {b: [0, 0] for b in backends}
    calls = {b: 0 for b in backends}
    for _ in range(3):
        for b in backends:
            runtime.set_wire_backend(b)
            tok = _fence(comm, tok)
            before = runtime.link_stats() or {}
            for _ in range(args.reps):
                t0 = time.perf_counter()
                y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
                np.asarray(y)
                times[b].append(time.perf_counter() - t0)
            after = runtime.link_stats() or {}
            sys_delta[b][0] += (int(after.get("tx_syscalls", 0))
                                - int(before.get("tx_syscalls", 0)))
            sys_delta[b][1] += (int(after.get("rx_syscalls", 0))
                                - int(before.get("rx_syscalls", 0)))
            calls[b] += args.reps
    runtime.set_wire_backend(launched)
    if wdtype is not None:
        runtime.set_wire_dtype(launched_dtype)
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    p50 = {b: sorted(ts)[len(ts) // 2] for b, ts in times.items()}
    best = {b: min(ts) for b, ts in times.items()}
    spc = {b: (sys_delta[b][0] / calls[b] if calls[b] else None)
           for b in backends}
    for b in backends:
        busbw = nbytes * factor / best[b]
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}",
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "payload_bytes": nbytes,
            "sec_per_call": round(best[b], 6),
            "p50_ms": round(p50[b] * 1e3, 4),
            "data_plane": "ring" if nbytes >= config.ring_min_bytes()
            else "tree",
            "wire_backend": b,
            "tx_syscalls_delta": sys_delta[b][0],
            "rx_syscalls_delta": sys_delta[b][1],
            "tx_syscalls_per_call": (round(spc[b], 2)
                                     if spc[b] is not None else None),
            "stripes": stripes,
            "wire_dtype": wdtype,
            "emu_flow_bps": int(winfo.get("emu_flow_bps", 0) or 0),
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "seg_bytes": config.seg_bytes(),
            "interleaved_pairs": True,
        }), flush=True)
    if "uring" in backends and "sendmsg" in backends:
        print(json.dumps({
            "metric": f"allreduce_uring_vs_sendmsg_proc{n}",
            "value": round(best["sendmsg"] / best["uring"], 2),
            "unit": "x",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "p50_ratio": round(p50["sendmsg"] / p50["uring"], 2),
            "syscall_ratio": (
                round(spc["sendmsg"] / spc["uring"], 2)
                if spc.get("uring") and spc.get("sendmsg") else None
            ),
            "stripes": stripes,
            "wire_dtype": wdtype,
        }), flush=True)


def _inflight_main(args, comm):
    """Issue-depth scaling of the async progress engine
    (docs/async.md): the --mb payload split into ``--inflight`` chunks,
    either submitted as overlapping ``iallreduce`` requests reaped by
    one ``waitall`` (depth N on the engine) or pushed through blocking
    allreduces one at a time (depth 1).  Interleaved same-conditions
    batches, one record per arm plus the ratio — the microbenchmark
    behind the bucket-size guidance in docs/async.md ("smaller buckets
    start overlapping earlier but pay more per-op latency")."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.utils import config

    n = comm.size
    depth = max(1, args.inflight)
    per = max(int(args.mb * 1e6 / 4) // depth, n)
    per -= per % max(n, 1)
    xs = [jnp.full((per,), float(k + 1), jnp.float32)
          for k in range(depth)]
    nbytes = per * 4 * depth  # total payload per rep, both arms
    factor = _busbw_factor("allreduce", n)

    def rep_deep(tok):
        reqs = []
        for x in xs:
            r, tok = m.iallreduce(x, m.SUM, comm=comm, token=tok)
            reqs.append(r)
        outs, tok = m.waitall(reqs, token=tok)
        return outs[-1], tok

    def rep_serial(tok):
        y = None
        for x in xs:
            y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        return y, tok

    tok = m.create_token()
    for fn in (rep_serial, rep_deep):  # warm (compile + transport)
        y, tok = fn(tok)
        np.asarray(y)

    best = {"serial": float("inf"), "deep": float("inf")}
    for _ in range(3):
        for mode, fn in (("serial", rep_serial), ("deep", rep_deep)):
            tok = _fence(comm, tok)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, tok = fn(tok)
            np.asarray(y)
            best[mode] = min(
                best[mode], (time.perf_counter() - t0) / args.reps
            )
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    algo, _ = _data_plane("allreduce", comm, per * 4)
    for mode, d in (("serial", 1), ("deep", depth)):
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}_inflight{d}",
            "value": round(nbytes * factor / best[mode] / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "inflight": d,
            "chunk_mb": per * 4 / 1e6,
            "payload_mb": nbytes / 1e6,
            "sec_per_rep": round(best[mode], 6),
            "data_plane": algo,
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "seg_bytes": config.seg_bytes(),
            "interleaved_pairs": True,
        }), flush=True)
    print(json.dumps({
        "metric": f"inflight_speedup_proc{n}",
        "value": round(best["serial"] / best["deep"], 3),
        "unit": "x",
        "nprocs": n,
        "inflight": depth,
        "chunk_mb": per * 4 / 1e6,
        "data_plane": algo,
    }), flush=True)


def _calibrate_main(args, comm):
    """The autotuner's calibration rounds as a standalone mode: emits
    one JSON record per arm x size — the records
    ``mpi4jax_tpu.tuning.calibrate.fit_records`` consumes — plus the
    fitted knob vector, so a fleet can calibrate once offline and ship
    the cache (docs/performance.md "trace-guided autotuning")."""
    from mpi4jax_tpu import tuning
    from mpi4jax_tpu.ops._proc import proc_topology

    n = comm.size
    knobs, measurements = tuning.calibrate.autotune(reps=max(args.reps, 3))
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    for rec in measurements:
        print(json.dumps({
            "metric": "calibrate",
            "nprocs": n,
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            **rec,
        }), flush=True)
    refit = tuning.calibrate.fit_records(measurements)
    print(json.dumps({
        "metric": "calibrate_fit",
        "nprocs": n,
        "knobs": knobs,
        "refit_from_records": refit,  # fit_records on the emitted JSON
        "fingerprint": tuning.topology_fingerprint(topo, n),
    }), flush=True)


def _autotune_pair_main(args, comm):
    """Mis-default recovery: interleaved same-conditions allreduce
    batches at --mb under three segment sizes — a deliberately
    mis-defaulted 16K, the autotuner's in-run fit, and the hand-tuned
    1M default — so the records show the autotuner clawing back
    what a wrong shipped default costs.  Run with T4J_NO_SHM=1:
    T4J_SEG_BYTES governs the segmented ring, and on a same-host arena
    comm the knob never serves."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu import tuning
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology

    n = comm.size
    per = max(int(args.mb * 1e6 / 4), n)
    per -= per % max(n, 1)
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4
    factor = _busbw_factor("allreduce", n)
    runtime.set_tuning(ring_min_bytes=0)  # the knob under test serves

    # in-run fit: measure the segment candidates once, pick the best
    # (the same fitter the cache-producing calibration uses)
    if runtime.telemetry_mode_name() == "off":
        runtime.set_telemetry(mode="counters")
    tok = m.create_token()
    seg_pts = []
    for seg in tuning.calibrate.SEG_CANDIDATES:
        runtime.set_tuning(seg_bytes=seg)
        tok = _fence(comm, tok)
        t0 = time.perf_counter()
        for _ in range(max(args.reps // 2, 2)):
            y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
        dt = (time.perf_counter() - t0) / max(args.reps // 2, 2)
        # MAX across ranks so every rank picks the same segment
        dt_max, tok = m.allreduce(
            jnp.float32(dt), op=m.MAX, comm=comm, token=tok
        )
        seg_pts.append((seg, float(dt_max) * 1e3))
    fitted = tuning.calibrate.fit_seg(seg_pts)

    arms = {
        "misdefault": 16 << 10,
        "autotuned": fitted,
        "hand": 1 << 20,
    }
    best = {a: float("inf") for a in arms}
    for arm, seg in arms.items():  # warm every arm
        runtime.set_tuning(seg_bytes=seg)
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        np.asarray(y)
    for _ in range(3):
        for arm, seg in arms.items():
            runtime.set_tuning(seg_bytes=seg)
            tok = _fence(comm, tok)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
            np.asarray(y)
            best[arm] = min(
                best[arm], (time.perf_counter() - t0) / args.reps
            )
    if comm.rank() != 0:
        return
    topo = proc_topology(comm)
    vals = {}
    for arm, seg in arms.items():
        busbw = nbytes * factor / best[arm]
        vals[arm] = busbw
        print(json.dumps({
            "metric": f"allreduce_busbw_proc{n}_seg_{arm}",
            "value": round(busbw / 1e9, 3),
            "unit": "GB/s",
            "nprocs": n,
            "payload_mb": nbytes / 1e6,
            "sec_per_call": round(best[arm], 6),
            "seg_bytes": seg,
            "data_plane": "ring",
            "local_world": topo["local_size"],
            "leader_world": topo["n_hosts"],
            "interleaved_pairs": True,
        }), flush=True)
    print(json.dumps({
        "metric": f"autotune_vs_default_proc{n}",
        "value": round(vals["autotuned"] / vals["misdefault"], 3),
        "unit": "x",
        "nprocs": n,
        "autotuned_seg_bytes": fitted,
        "misdefault_seg_bytes": 16 << 10,
        "autotuned_vs_hand": round(vals["autotuned"] / vals["hand"], 3),
    }), flush=True)


def _halo_main(args, comm):
    """Small-message latency microbench: p50/p99 of a full 2-D halo
    exchange (``--fields`` fields, all four directions) at each
    ``--widths`` width, coalescing on vs off in interleaved pairs.
    The per-op evidence (p2p op count + mean over each timed window,
    sendrecv/send/recv kinds merged) comes from the counters-mode
    telemetry snapshot delta, so the records show the op-count
    collapse (2*4*fields one-sided ops -> 4 fused exchanges) alongside
    the wall latency."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu import tuning
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.ops._proc import proc_topology
    from mpi4jax_tpu.parallel import grid_comm
    from mpi4jax_tpu.parallel.halo import halo_exchange_2d_batch

    n = comm.size
    rank = comm.rank()
    ny = 1
    for cand in range(int(n ** 0.5), 0, -1):
        if n % cand == 0:
            ny = cand
            break
    grid = grid_comm((ny, n // ny))
    if runtime.telemetry_mode_name() == "off":
        runtime.set_telemetry(mode="counters")
    topo = proc_topology(comm)
    widths = [int(w) for w in str(args.widths).split(",") if w.strip()]
    reps = max(args.reps, 10)
    rng = np.random.default_rng(11 + 3 * rank)

    for w in widths:
        side = args.halo_base + 2 * w
        fields = [
            jnp.asarray(rng.standard_normal((side, side), np.float64)
                        .astype(np.float32))
            for _ in range(args.fields)
        ]
        slab_bytes = 4 * args.fields * w * side  # one direction's frame

        def exchange():
            outs, _tok = halo_exchange_2d_batch(
                fields, grid, periodic=(True, True), width=w
            )
            np.asarray(outs[-1])  # materialise: the exchange is done

        times = {"off": [], "on": []}
        telw = {"off": None, "on": None}
        for mode, threshold in (("off", 0), ("on", 1 << 30)):
            tuning.override_coalesce(threshold)
            exchange()  # warm (compile + channel negotiation)
        tok = m.create_token()
        for _round in range(3):
            for mode, threshold in (("off", 0), ("on", 1 << 30)):
                tuning.override_coalesce(threshold)
                tok = _fence(comm, tok)
                before = _telemetry_registry()
                for _ in range(reps):
                    t0 = time.perf_counter()
                    exchange()
                    times[mode].append(time.perf_counter() - t0)
                after = _telemetry_registry()
                if after is not None:
                    window = (after.diff(before) if before is not None
                              else after)
                    # the fused path records kSendrecv (kSend/kRecv on
                    # one-sided edges); the unfused loop records kSend
                    # + kRecv per part — merge all three kinds so BOTH
                    # arms produce the op-count evidence
                    count, total_ms = 0, 0.0
                    for opname in ("sendrecv", "send", "recv"):
                        row = window.aggregate(op=opname)
                        if row is not None and row.count:
                            s = row.stats()
                            count += s["count"]
                            if s["mean_ms"]:
                                total_ms += s["mean_ms"] * s["count"]
                    telw[mode] = (count, total_ms)
        tuning.override_coalesce(None)
        if rank != 0:
            continue
        p = {}
        for mode, ts in times.items():
            ts = sorted(ts)
            p[mode] = {
                "p50": ts[len(ts) // 2] * 1e3,
                "p99": ts[min(len(ts) - 1, int(len(ts) * 0.99))] * 1e3,
            }
            rec = {
                "metric": f"halo_p50_ms_proc{n}_w{w}",
                "value": round(p[mode]["p50"], 4),
                "unit": "ms",
                "coalesce": mode,
                "p99_ms": round(p[mode]["p99"], 4),
                "nprocs": n,
                "grid": [ny, n // ny],
                "width": w,
                "fields": args.fields,
                "direction_frame_bytes": slab_bytes,
                "local_world": topo["local_size"],
                "leader_world": topo["n_hosts"],
                "coalesce_bytes": 0 if mode == "off" else 1 << 30,
                "interleaved_pairs": True,
            }
            if telw[mode] is not None and telw[mode][0]:
                count, total_ms = telw[mode]
                rec["p2p_ops_per_window"] = count
                rec["p2p_op_mean_ms"] = round(total_ms / count, 4)
            print(json.dumps(rec), flush=True)
        print(json.dumps({
            "metric": f"halo_coalesce_speedup_proc{n}_w{w}",
            "value": round(p["off"]["p50"] / p["on"]["p50"], 3),
            "unit": "x",
            "nprocs": n,
            "width": w,
            "fields": args.fields,
            "p99_speedup": round(p["off"]["p99"] / p["on"]["p99"], 3),
        }), flush=True)


def _gauntlet_rate_gbps(comm, tok, mb=16, reps=4):
    """Aggregate N-rank copy payload rate (GB/s), barrier-fenced — the
    multi-process analog of :func:`_copy_rate_gbps` and the measured
    input to the scheduler-adjusted arena ceiling.  The single
    implementation of this protocol: the standalone --copy-gauntlet
    mode and the allreduce leg's in-run adjusted ceiling both call it."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m

    src = np.random.default_rng(comm.rank()).random(
        int(mb * (1 << 20)) // 8
    )
    dst = np.empty_like(src)
    np.copyto(dst, src)
    best = float("inf")
    for _ in range(3):
        tok = _fence(comm, tok)
        t0 = time.perf_counter()
        for _ in range(reps):
            np.copyto(dst, src)
        dt = time.perf_counter() - t0
        dt_max, tok = m.allreduce(
            jnp.float32(dt), op=m.MAX, comm=comm, token=tok
        )
        best = min(best, float(dt_max))
    return comm.size * src.nbytes * reps / best / 1e9


def _copy_gauntlet_main(args):
    """The arena ceiling's falsifiable assumption, measured: N ranks
    timesharing the core should sustain the single-core copy rate in
    AGGREGATE (streaming copies have no cache state to lose).  Each
    rank memcpys a private --mb buffer --reps times between barriers
    (:func:`_gauntlet_rate_gbps` — the same protocol the allreduce
    leg's adjusted ceiling replays); rank 0 reports the aggregate
    payload rate vs a TRULY solo probe (rank 0 measures while the
    peers wait at a barrier).  If aggregate << solo, the gap is the OS
    scheduler + DRAM contention — a bound on ANY shared-memory
    collective on this box, not on the arena's design."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import mpi4jax_tpu as m

    comm = m.get_default_comm()
    assert comm.backend == "proc", "run under python -m mpi4jax_tpu.launch"
    n, rank = comm.size, comm.rank()

    # solo baseline: peers BLOCK at the second fence while rank 0
    # probes (forced — async dispatch would let them sail on)
    tok = _fence(comm, m.create_token())
    single = _copy_rate_gbps() if rank == 0 else 0.0
    tok = _fence(comm, tok)

    agg = _gauntlet_rate_gbps(comm, tok, mb=args.mb, reps=args.reps)
    if rank == 0:
        print(
            json.dumps(
                {
                    "metric": f"copy_gauntlet_proc{n}",
                    "value": round(agg, 2),
                    "unit": "GB/s aggregate payload",
                    "nprocs": n,
                    "payload_mb": args.mb,
                    "single_core_copy_gbps": round(single, 2),
                    "aggregate_vs_single_pct": round(100 * agg / single, 1),
                }
            ),
            flush=True,
        )


def _two_tier_main(args):
    """End-to-end timing of the composed ICI+DCN allreduce
    (parallel/distributed.two_tier_allreduce): per launcher process an
    8-device virtual mesh reduces over its "slice", one block rides
    the proc wire across processes, and the result is re-broadcast
    over the mesh.  Run under the launcher:

        python -m mpi4jax_tpu.launch -np 2 benchmarks/proc_busbw.py \\
            --two-tier [--mb 32]

    Rank 0 prints algorithmic GB/s (global payload bytes / wall) plus
    the DCN-hop busbw (the per-process block over the proc tier).
    """
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.parallel.distributed import two_tier_allreduce

    inter = m.get_default_comm()
    assert inter.backend == "proc", "run under python -m mpi4jax_tpu.launch"
    n = inter.size
    mesh = jax.make_mesh(
        (8,), ("d",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    intra = m.MeshComm.from_mesh(mesh)

    per = int(args.mb * 1e6 / 4)
    per -= per % 8
    x = jnp.ones((per,), jnp.float32)
    nbytes = per * 4

    y, _ = two_tier_allreduce(x, m.SUM, intra, inter)  # warm both tiers
    np.asarray(y)

    best = float("inf")
    tok = m.create_token()
    for _ in range(3):
        tok = _fence(inter, tok)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            y, _ = two_tier_allreduce(x, m.SUM, intra, inter)
        np.asarray(y)
        best = min(best, (time.perf_counter() - t0) / args.reps)

    # the DCN hop measured ALONE: the same reduced block (1/8 of the
    # payload) over the proc tier, without the virtual-ICI reduction
    # around it — on this box the end-to-end number is floored by the
    # ICI tier, and this separates the two
    block = np.ones((per // 8,), np.float32)
    block_bytes = block.nbytes
    y2, tok2 = m.allreduce(block, m.SUM, comm=inter)
    np.asarray(y2)
    dcn_best = float("inf")
    for _ in range(3):
        tok2 = _fence(inter, tok2)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            y2, tok2 = m.allreduce(block, m.SUM, comm=inter, token=tok2)
        np.asarray(y2)
        dcn_best = min(dcn_best, (time.perf_counter() - t0) / args.reps)

    rec = {
        "metric": f"two_tier_allreduce_proc{n}x8",
        "value": round(nbytes / best / 1e9, 3),
        "unit": "GB/s",
        "nprocs": n,
        "devices_per_proc": 8,
        "payload_mb": nbytes / 1e6,
        "sec_per_call": round(best, 6),
        "dcn_block_mb": block_bytes / 1e6,
        "dcn_busbw_gbps": round(
            block_bytes * 2 * (n - 1) / n / dcn_best / 1e9, 3
        ),
    }
    if inter.rank() == 0:
        print(json.dumps(rec), flush=True)


def _cores():
    import os

    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def _copy_rate_gbps():
    """Measured copy payload rate (GB/s) of one core, cold-ish buffers
    — the primitive every arena phase is built from."""
    import numpy as np

    src = np.random.default_rng(0).random((16 << 20) // 8)  # 16 MB of f64
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm page tables
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return src.nbytes / best / 1e9


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()
