"""Python side of the native DCN bridge: ctypes control plane + FFI
target registration.

Counterpart of the reference's bridge registration
(mpi4jax/_src/xla_bridge/__init__.py:26-31): loads the shared library,
hands the 12 typed-FFI handler symbols to XLA for the "cpu" platform,
and exposes the process-world control API (init/rank/size/comms) that
mpi4py provides in the reference.
"""

import atexit
import ctypes
import os

__all__ = [
    "available",
    "is_initialized",
    "ensure_initialized",
    "world_rank",
    "world_size",
    "comm_handle",
    "set_logging",
    "finalize",
    "check_health",
    "notify_abort",
    "last_error",
    "set_timeouts",
    "set_tuning",
    "set_wire",
    "wire_info",
    "set_wire_dtype",
    "wire_dtype_info",
    "set_wire_backend",
    "wire_backend_info",
    "set_coalesce",
    "coalesce_bytes",
    "set_hier",
    "set_resilience",
    "set_elastic",
    "world_info",
    "alive_ranks",
    "resize_wait",
    "refresh_after_resize",
    "WorldResized",
    "set_telemetry",
    "set_flight",
    "flight_info",
    "annotate_step",
    "telemetry_mode_name",
    "telemetry_drain",
    "telemetry_last",
    "telemetry_anchor",
    "telemetry_dropped",
    "metrics_snapshot",
    "link_stats",
    "topology",
    "hier_would_select",
    "hier_active",
    "host_iallreduce",
    "host_ireduce_scatter",
    "host_isend",
    "host_irecv",
    "host_wait",
    "host_test",
    "async_inflight",
    "async_pending",
    "async_assert_drained",
    "BridgeError",
    "HANDLER_NAMES",
]


class BridgeError(RuntimeError):
    """A DCN bridge call failed (transport error, deadline expiry, or a
    peer's abort broadcast).  The message carries rank/peer/op context
    from the native layer.  The bridge is faulted afterwards: every
    further proc-tier op raises until the job restarts."""


class WorldResized(RuntimeError):
    """The world membership changed under an elastic resize
    (docs/failure-semantics.md "elastic membership").

    Raised at the NEXT proc-tier op after a resize committed (and by
    :func:`check_health` directly).  Unlike :class:`BridgeError` this
    is recoverable: the transport is already rebuilt over the new
    membership — user code must drop its pre-resize communicators,
    rebuild them over ``new_world``, redistribute state (e.g. via
    ``utils/checkpoint.py``), and continue.  ``models/train.py``'s
    elastic loop does exactly that.

    Attributes:
        old_world: tuple of world ranks before the resize.
        new_world: tuple of world ranks after it.
        epoch: the committed world epoch (bumps by 1 per resize).
    """

    def __init__(self, old_world, new_world, epoch):
        self.old_world = tuple(old_world)
        self.new_world = tuple(new_world)
        self.epoch = int(epoch)
        joined = ",".join(str(r) for r in self.new_world)
        super().__init__(
            f"world resized at epoch {self.epoch}: "
            f"{len(self.old_world)} -> {len(self.new_world)} member(s) "
            f"(now [{joined}]) — rebuild communicators over the new "
            "world and redistribute state "
            "(docs/failure-semantics.md \"elastic membership\")"
        )

HANDLER_NAMES = [
    "t4j_allreduce",
    "t4j_hier_allreduce",
    "t4j_reduce",
    "t4j_reduce_scatter",
    "t4j_scan",
    "t4j_send",
    "t4j_recv",
    "t4j_sendrecv",
    "t4j_sendrecv_fused",
    "t4j_alltoall_fused",
    "t4j_barrier",
    "t4j_bcast",
    "t4j_allgather",
    "t4j_gather",
    "t4j_scatter",
    "t4j_alltoall",
    # async progress engine (docs/async.md): in-jit submit/wait fast
    # path — submits hand the operand to the engine's owned-buffer API
    # and return a u64 request id; wait/test consume it as data
    "t4j_iallreduce_submit",
    "t4j_ireduce_scatter_submit",
    "t4j_isend_submit",
    "t4j_irecv_submit",
    "t4j_async_wait",
    "t4j_async_test",
]

_state = {"lib": None, "registered": False, "comm_cache": {}}


def _load():
    if _state["lib"] is not None:
        return _state["lib"]
    from mpi4jax_tpu.native.build import ensure_built

    lib = ctypes.CDLL(str(ensure_built()))
    lib.t4j_init.restype = ctypes.c_int
    lib.t4j_initialized.restype = ctypes.c_int
    lib.t4j_world_rank.restype = ctypes.c_int
    lib.t4j_world_size.restype = ctypes.c_int
    lib.t4j_comm_create.restype = ctypes.c_int
    lib.t4j_comm_create.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
        ctypes.c_int32,
    ]
    lib.t4j_comm_rank.restype = ctypes.c_int
    lib.t4j_comm_rank.argtypes = [ctypes.c_int32]
    lib.t4j_comm_size.restype = ctypes.c_int
    lib.t4j_comm_size.argtypes = [ctypes.c_int32]
    lib.t4j_set_logging.argtypes = [ctypes.c_int]
    # robustness control surface (docs/failure-semantics.md)
    lib.t4j_last_error.restype = ctypes.c_char_p
    lib.t4j_health.restype = ctypes.c_int
    lib.t4j_fault_msg.restype = ctypes.c_char_p
    lib.t4j_set_timeouts.argtypes = [ctypes.c_double, ctypes.c_double]
    lib.t4j_set_tuning.argtypes = [ctypes.c_int64, ctypes.c_int64]
    lib.t4j_set_coalesce.argtypes = [ctypes.c_int64]
    lib.t4j_coalesce_bytes.restype = ctypes.c_int64
    lib.t4j_set_hier.argtypes = [ctypes.c_int32, ctypes.c_int64]
    lib.t4j_set_resilience.argtypes = [
        ctypes.c_int32, ctypes.c_double, ctypes.c_double, ctypes.c_int64,
    ]
    lib.t4j_set_elastic.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_double,
    ]
    lib.t4j_world_info.argtypes = [
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.t4j_world_info.restype = ctypes.c_int32
    lib.t4j_resize_wait.argtypes = [ctypes.c_double]
    lib.t4j_resize_wait.restype = ctypes.c_int32
    lib.t4j_link_stats.argtypes = [
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.t4j_link_stats.restype = ctypes.c_int32
    lib.t4j_link_stripe_stats.argtypes = [
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.t4j_link_stripe_stats.restype = ctypes.c_int32
    lib.t4j_set_wire.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
    ]
    lib.t4j_wire_info.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.t4j_wire_info.restype = ctypes.c_int32
    lib.t4j_set_wire_dtype.argtypes = [ctypes.c_int32]
    lib.t4j_wire_dtype_info.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.t4j_wire_dtype_info.restype = ctypes.c_int32
    lib.t4j_set_wire_backend.argtypes = [ctypes.c_int32]
    lib.t4j_wire_backend_info.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.t4j_wire_backend_info.restype = ctypes.c_int32
    lib.t4j_topo.argtypes = [ctypes.POINTER(ctypes.c_int32)] * 5
    lib.t4j_topo.restype = ctypes.c_int32
    lib.t4j_hier_would_select.argtypes = [ctypes.c_int32, ctypes.c_uint64]
    lib.t4j_hier_would_select.restype = ctypes.c_int32
    lib.t4j_hier_active.argtypes = [ctypes.c_int32]
    lib.t4j_hier_active.restype = ctypes.c_int32
    lib.t4j_abort_notify.argtypes = [ctypes.c_char_p]
    # telemetry surface (docs/observability.md)
    lib.t4j_set_telemetry.argtypes = [ctypes.c_int32, ctypes.c_int64]
    lib.t4j_telemetry_mode.restype = ctypes.c_int32
    lib.t4j_telemetry_drain.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.t4j_telemetry_drain.restype = ctypes.c_int64
    lib.t4j_telemetry_peek_last.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.t4j_telemetry_peek_last.restype = ctypes.c_int64
    lib.t4j_telemetry_dropped.restype = ctypes.c_uint64
    lib.t4j_set_flight.argtypes = [ctypes.c_int32, ctypes.c_char_p]
    lib.t4j_flight_info.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.t4j_flight_info.restype = ctypes.c_int32
    lib.t4j_telemetry_anchor.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.t4j_telemetry_anchor.restype = ctypes.c_int32
    lib.t4j_metrics_snapshot.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
    ]
    lib.t4j_metrics_snapshot.restype = ctypes.c_int64
    lib.t4j_annotate_step.argtypes = [ctypes.c_int64, ctypes.c_int32]
    # data plane for the host-callback tier (TPU staging path); every
    # call returns a status: 0 ok, nonzero = failed with t4j_last_error
    i32, u64, vp = ctypes.c_int32, ctypes.c_uint64, ctypes.c_void_p
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.t4j_c_hier_allreduce.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_c_send.argtypes = [i32, vp, u64, i32, i32]
    lib.t4j_c_recv.argtypes = [i32, vp, u64, i32, i32, i32p, i32p]
    lib.t4j_c_sendrecv.argtypes = [i32, vp, u64, vp, u64, i32, i32, i32,
                                   i32, i32p, i32p]
    # fused multi-part p2p (small-message coalescing): pointer-array
    # iovec surface, sizes as u64[]
    vpp = ctypes.POINTER(ctypes.c_void_p)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.t4j_c_sendrecv_fused.argtypes = [
        i32, vpp, u64p, i32, vpp, u64p, i32, i32, i32, i32, i32, i32p,
        i32p,
    ]
    lib.t4j_c_alltoall_fused.argtypes = [i32, vpp, vpp, u64p, i32]
    lib.t4j_c_barrier.argtypes = [i32]
    lib.t4j_c_bcast.argtypes = [i32, vp, u64, i32]
    lib.t4j_c_allreduce.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_c_reduce.argtypes = [i32, vp, vp, u64, i32, i32, i32]
    lib.t4j_c_scan.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_c_reduce_scatter.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_c_allgather.argtypes = [i32, vp, vp, u64]
    lib.t4j_c_gather.argtypes = [i32, vp, vp, u64, i32]
    lib.t4j_c_scatter.argtypes = [i32, vp, vp, u64, i32]
    lib.t4j_c_alltoall.argtypes = [i32, vp, vp, u64]
    # async progress engine (docs/async.md): nonblocking submits return
    # a request id (0 = failure, message via t4j_last_error)
    lib.t4j_iallreduce.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_iallreduce.restype = u64
    lib.t4j_ireduce_scatter.argtypes = [i32, vp, vp, u64, i32, i32]
    lib.t4j_ireduce_scatter.restype = u64
    lib.t4j_isend.argtypes = [i32, vp, u64, i32, i32]
    lib.t4j_isend.restype = u64
    lib.t4j_irecv.argtypes = [i32, vp, u64, i32, i32]
    lib.t4j_irecv.restype = u64
    lib.t4j_wait.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(i32), ctypes.POINTER(i32),
    ]
    lib.t4j_wait.restype = i32
    lib.t4j_test.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(i32), ctypes.POINTER(i32),
        ctypes.POINTER(i32),
    ]
    lib.t4j_test.restype = i32
    lib.t4j_waitall.argtypes = [ctypes.POINTER(ctypes.c_uint64), i32]
    lib.t4j_waitall.restype = i32
    lib.t4j_async_inflight.restype = i32
    lib.t4j_async_pending.restype = i32
    for name in (
        "t4j_c_send", "t4j_c_recv", "t4j_c_sendrecv", "t4j_c_barrier",
        "t4j_c_bcast", "t4j_c_allreduce", "t4j_c_hier_allreduce",
        "t4j_c_reduce", "t4j_c_scan",
        "t4j_c_reduce_scatter", "t4j_c_allgather", "t4j_c_gather",
        "t4j_c_scatter", "t4j_c_alltoall", "t4j_c_sendrecv_fused",
        "t4j_c_alltoall_fused",
    ):
        getattr(lib, name).restype = ctypes.c_int32
    _state["lib"] = lib
    return lib


def last_error():
    """Contextual message of the last failed native call on this
    thread (empty string when nothing failed)."""
    lib = _state["lib"]
    if lib is None:
        return ""
    raw = lib.t4j_last_error()
    return raw.decode("utf-8", "replace") if raw else ""


def _check(status):
    """Map a native status code to BridgeError with the bridge's own
    rank/peer/op context."""
    if status:
        raise BridgeError(
            last_error() or "native bridge call failed (no detail)"
        )


def check_health():
    """Raise BridgeError if the bridge posted a fault (a peer died, an
    op timed out, or an abort broadcast arrived).  Called from the op
    tier before dispatch so post-fault calls fail fast instead of
    feeding a dead transport.  When the self-healing layer saw action
    before the fault, the message carries the reconnect/replay
    counters — a job that died AFTER surviving drops usually points at
    a flaky fabric, and the counters make that visible in the
    post-mortem."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return
    # elastic membership first: a committed resize surfaces as the
    # recoverable WorldResized (the transport is already rebuilt), not
    # as a fault — and an in-flight resize is waited out so the caller
    # sees the verdict
    _check_world_epoch(lib)
    if lib.t4j_health():
        raw = lib.t4j_fault_msg()
        msg = raw.decode("utf-8", "replace") if raw else "bridge faulted"
        stats = link_stats()
        if stats and stats["reconnects"]:
            msg += (
                " [self-healing before the fault: "
                f"{stats['reconnects']} reconnect(s), "
                f"{stats['replayed_frames']} frame(s) / "
                f"{stats['replayed_bytes']} bytes replayed — "
                "docs/failure-semantics.md]"
            )
        # the ring tail shows WHAT the rank was doing when it died
        # (T4J_TELEMETRY=counters records the control-plane events,
        # trace adds the op/frame context — docs/observability.md)
        try:
            tail = _format_recent_events(telemetry_last(8))
        except Exception:
            tail = ""
        if tail:
            msg += f" [last telemetry events: {tail}]"
        raise BridgeError(msg)


def _link_stats_one(lib, peer):
    rec = ctypes.c_uint64(0)
    frames = ctypes.c_uint64(0)
    nbytes = ctypes.c_uint64(0)
    txsc = ctypes.c_uint64(0)
    rxsc = ctypes.c_uint64(0)
    state = ctypes.c_int32(0)
    ok = lib.t4j_link_stats(
        int(peer),
        ctypes.byref(rec), ctypes.byref(frames), ctypes.byref(nbytes),
        ctypes.byref(txsc), ctypes.byref(rxsc),
        ctypes.byref(state),
    )
    if not ok:
        return None
    return {
        "reconnects": rec.value,
        "replayed_frames": frames.value,
        "replayed_bytes": nbytes.value,
        "tx_syscalls": txsc.value,
        "rx_syscalls": rxsc.value,
        "state": state.value,
    }


def _stripe_stats_one(lib, peer, stripe):
    rec = ctypes.c_uint64(0)
    frames = ctypes.c_uint64(0)
    nbytes = ctypes.c_uint64(0)
    txsc = ctypes.c_uint64(0)
    rxsc = ctypes.c_uint64(0)
    state = ctypes.c_int32(0)
    ok = lib.t4j_link_stripe_stats(
        int(peer), int(stripe),
        ctypes.byref(rec), ctypes.byref(frames), ctypes.byref(nbytes),
        ctypes.byref(txsc), ctypes.byref(rxsc),
        ctypes.byref(state),
    )
    if not ok:
        return None
    return {
        "reconnects": rec.value,
        "replayed_frames": frames.value,
        "replayed_bytes": nbytes.value,
        "tx_syscalls": txsc.value,
        "rx_syscalls": rxsc.value,
        "state": state.value,
    }


def set_wire(stripes=None, zerocopy_min_bytes=None, sendmsg_batch=None,
             emu_flow_bps=None):
    """Runtime override of the wire-path knobs (docs/performance.md
    "striped links and the zero-copy path").

    ``stripes`` sets the DEALING width (clamped to the built width
    after init); before init it also fixes the number of connections
    bootstrap builds per link.  ``None`` keeps each current value;
    ``zerocopy_min_bytes=0`` disables MSG_ZEROCOPY;
    ``emu_flow_bps=0`` disables the per-connection test throttle.
    Must be uniform across ranks (the launcher propagates
    ``T4J_STRIPES`` / ``T4J_ZEROCOPY_MIN_BYTES`` /
    ``T4J_SENDMSG_BATCH`` / ``T4J_EMU_FLOW_BPS``): both ends of a
    link must agree on the stripe count, and the receivers reorder by
    the same dealing discipline the senders use."""
    lib = _load()
    lib.t4j_set_wire(
        0 if stripes is None else int(stripes),
        -1 if zerocopy_min_bytes is None else int(zerocopy_min_bytes),
        0 if sendmsg_batch is None else int(sendmsg_batch),
        -1 if emu_flow_bps is None else int(emu_flow_bps),
    )


def wire_info():
    """Effective wire-path state: ``{"stripes_built",
    "stripes_active", "zerocopy_min_bytes", "sendmsg_batch",
    "emu_flow_bps", "zerocopy"}`` — ``zerocopy`` is True only when
    requested AND the kernel honours SO_ZEROCOPY.  ``None`` when the
    native library was never loaded."""
    lib = _state["lib"]
    if lib is None:
        return None
    sb = ctypes.c_int32(0)
    sa = ctypes.c_int32(0)
    zmin = ctypes.c_int64(0)
    batch = ctypes.c_int32(0)
    flow = ctypes.c_int64(0)
    zc = ctypes.c_int32(0)
    zc_done = ctypes.c_uint64(0)
    zc_copied = ctypes.c_uint64(0)
    lib.t4j_wire_info(
        ctypes.byref(sb), ctypes.byref(sa), ctypes.byref(zmin),
        ctypes.byref(batch), ctypes.byref(flow), ctypes.byref(zc),
        ctypes.byref(zc_done), ctypes.byref(zc_copied),
    )
    info = {
        "stripes_built": int(sb.value),
        "stripes_active": int(sa.value),
        "zerocopy_min_bytes": int(zmin.value),
        "sendmsg_batch": int(batch.value),
        "emu_flow_bps": int(flow.value),
        "zerocopy": bool(zc.value),
        # completion diagnostics: copied ~= completions means the
        # fabric (loopback always) fell back to copying — pin overhead
        # with no copy saved (docs/performance.md)
        "zc_completions": int(zc_done.value),
        "zc_copied": int(zc_copied.value),
    }
    info.update(wire_dtype_info() or {})
    info.update(wire_backend_info() or {})
    return info


WIRE_DTYPE_CODES = {"off": 0, "bf16": 1, "fp8": 2}
WIRE_DTYPE_NAMES = {v: k for k, v in WIRE_DTYPE_CODES.items()}


def set_wire_dtype(mode=None):
    """Runtime override of the compressed-collective wire dtype
    (docs/performance.md "Compressed collectives"): ``"off"`` /
    ``"bf16"`` / ``"fp8"`` or the native code 0/1/2; ``None`` keeps
    the current value.  Runtime-changeable like the dealing width (the
    calibrator and the interleaved benchmark arms A/B it inside one
    world), but must stay uniform across ranks — divergent wire
    dtypes exchange mismatched frame sizes and deadlock (t4j-lint rule
    T4J009 names the divergence)."""
    lib = _load()
    if mode is None:
        code = -1
    elif isinstance(mode, str):
        try:
            code = WIRE_DTYPE_CODES[mode.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown wire dtype {mode!r} "
                f"(want {'|'.join(WIRE_DTYPE_CODES)})"
            ) from None
    else:
        code = int(mode)
    lib.t4j_set_wire_dtype(code)


def wire_dtype_info():
    """Effective compressed-collective state: ``{"wire_dtype",
    "wire_logical_bytes", "wire_bytes"}`` — the byte counters
    accumulate over the compressed send path only (0 while the mode is
    off), so ``wire_bytes / wire_logical_bytes`` is the provable wire
    saving.  ``None`` when the native library was never loaded."""
    lib = _state["lib"]
    if lib is None:
        return None
    mode = ctypes.c_int32(0)
    logical = ctypes.c_uint64(0)
    wire = ctypes.c_uint64(0)
    lib.t4j_wire_dtype_info(
        ctypes.byref(mode), ctypes.byref(logical), ctypes.byref(wire)
    )
    return {
        "wire_dtype": WIRE_DTYPE_NAMES.get(int(mode.value), "off"),
        "wire_logical_bytes": int(logical.value),
        "wire_bytes": int(wire.value),
    }


WIRE_BACKEND_CODES = {"sendmsg": 0, "uring": 1, "auto": 2}
WIRE_BACKEND_NAMES = {v: k for k, v in WIRE_BACKEND_CODES.items()}


def set_wire_backend(mode=None):
    """Runtime override of the wire data-plane backend
    (docs/performance.md "io_uring wire backend"): ``"sendmsg"`` /
    ``"uring"`` / ``"auto"`` or the native code 0/1/2; ``None`` keeps
    the current value.  Runtime-changeable between collectives (the
    calibrator and the interleaved benchmark arms A/B it inside one
    world) because both backends put identical bytes on the wire; it
    does NOT need to be uniform across ranks, but the launcher
    propagates ``T4J_WIRE_BACKEND`` so benchmarks compare like with
    like.  On a kernel without io_uring ``"uring"`` degrades loudly to
    sendmsg (one stderr line per process)."""
    lib = _load()
    if mode is None:
        code = -1
    elif isinstance(mode, str):
        try:
            code = WIRE_BACKEND_CODES[mode.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown wire backend {mode!r} "
                f"(want {'|'.join(WIRE_BACKEND_CODES)})"
            ) from None
    else:
        code = int(mode)
    lib.t4j_set_wire_backend(code)


def wire_backend_info():
    """Effective wire-backend state: ``{"wire_backend",
    "uring_supported", "wire_backend_active"}`` — ``wire_backend`` is
    the requested mode, ``uring_supported`` whether the kernel's
    io_uring probe succeeded, ``wire_backend_active`` the backend the
    stripe threads actually use (``"uring"`` only when requested AND
    supported; ``"auto"`` resolves to sendmsg until the calibrator
    learns otherwise).  Valid pre-init — ``ensure_initialized`` uses
    it to reject an explicit uring request on a kernel without
    io_uring.  ``None`` when the native library was never loaded."""
    lib = _state["lib"]
    if lib is None:
        return None
    mode = ctypes.c_int32(0)
    supported = ctypes.c_int32(0)
    active = ctypes.c_int32(0)
    lib.t4j_wire_backend_info(
        ctypes.byref(mode), ctypes.byref(supported), ctypes.byref(active)
    )
    return {
        "wire_backend": WIRE_BACKEND_NAMES.get(int(mode.value), "auto"),
        "uring_supported": bool(supported.value),
        "wire_backend_active": "uring" if active.value else "sendmsg",
    }


def link_stats(peer=None):
    """Self-healing transport counters (docs/failure-semantics.md
    "self-healing transport"), or ``None`` before init.

    ``peer=None`` aggregates every link: ``{"reconnects",
    "replayed_frames", "replayed_bytes", "state"}`` with ``state`` the
    worst link state (0 up, 1 broken/repairing, 2 dead) — plus the
    per-peer MAXIMA (``"worst_peer"``, ``"max_reconnects"``,
    ``"max_replayed_frames"``, ``"max_replayed_bytes"``), because sums
    hide a single flaky link behind healthy ones and serving admission
    control sheds load by the WORST link, not the average
    (ROADMAP item 5).  ``worst_peer`` is the rank with the most
    reconnects (ties broken by replayed bytes, then by worse state);
    ``None`` when no link has any counter.  An integer ``peer``
    selects that world rank's link (``None`` for self or
    out-of-range)."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return None
    if peer is not None:
        s = _link_stats_one(lib, peer)
        if s is None:
            return None
        # per-stripe breakdown (docs/performance.md "striped links"):
        # one dict per stripe, so t4j-top and the proc tests can see
        # WHICH flow repaired/replayed instead of just the link sum
        stripes = []
        si = 0
        while True:
            ss = _stripe_stats_one(lib, peer, si)
            if ss is None:
                break
            stripes.append(ss)
            si += 1
        if stripes:
            s["stripes"] = stripes
        return s
    agg = _link_stats_one(lib, -1)
    if agg is None:
        return None
    agg.update(
        worst_peer=None,
        max_reconnects=0,
        max_replayed_frames=0,
        max_replayed_bytes=0,
    )
    worst_key = (0, 0, 0)
    for r in range(int(lib.t4j_world_size())):
        s = _link_stats_one(lib, r)
        if s is None:
            continue
        agg["max_reconnects"] = max(agg["max_reconnects"],
                                    s["reconnects"])
        agg["max_replayed_frames"] = max(agg["max_replayed_frames"],
                                         s["replayed_frames"])
        agg["max_replayed_bytes"] = max(agg["max_replayed_bytes"],
                                        s["replayed_bytes"])
        key = (s["reconnects"], s["replayed_bytes"], s["state"])
        if key > worst_key and any(key):
            worst_key = key
            agg["worst_peer"] = r
    return agg


def set_resilience(retry_max=None, backoff_base_s=None, backoff_max_s=None,
                   replay_bytes=None):
    """Runtime override of the self-healing transport knobs.

    ``None`` keeps the current value; ``retry_max=0`` disables
    self-healing (the first transport error fails the job).  Must be
    set before init and uniformly across ranks (the launcher
    propagates ``T4J_RETRY_MAX`` / ``T4J_BACKOFF_BASE`` /
    ``T4J_BACKOFF_MAX`` / ``T4J_REPLAY_BYTES``): the reconnect
    listener is wired at bootstrap, and one side healing while the
    other fail-stops would turn every transient drop into an abort."""
    lib = _load()
    lib.t4j_set_resilience(
        -1 if retry_max is None else int(retry_max),
        -1.0 if backoff_base_s is None else float(backoff_base_s),
        -1.0 if backoff_max_s is None else float(backoff_max_s),
        -1 if replay_bytes is None else int(replay_bytes),
    )


_ELASTIC_MODES = {"off": 0, "shrink": 1, "rejoin": 2}


def set_elastic(mode=None, min_world=None, resize_timeout_s=None):
    """Runtime override of the elastic-membership knobs
    (docs/failure-semantics.md "elastic membership").

    ``mode`` is ``"off"`` (a dead rank aborts the whole job, the
    default), ``"shrink"`` (survivors agree on a reduced world and
    continue) or ``"rejoin"`` (shrink, plus rank 0 keeps the bootstrap
    coordinator port open for relaunched replacements); ``None`` keeps
    the current setting.  Must be set before init and uniformly across
    ranks (the launcher propagates ``T4J_ELASTIC`` / ``T4J_MIN_WORLD``
    / ``T4J_RESIZE_TIMEOUT``)."""
    lib = _load()
    if mode is not None and str(mode) not in _ELASTIC_MODES:
        raise ValueError(
            f"cannot interpret elastic mode {mode!r} "
            "(want off|shrink|rejoin)"
        )
    code = -1 if mode is None else _ELASTIC_MODES[str(mode)]
    lib.t4j_set_elastic(
        code,
        0 if min_world is None else int(min_world),
        -1.0 if resize_timeout_s is None else float(resize_timeout_s),
    )


def world_info():
    """Live membership view, or ``None`` before init.

    Returns ``{"epoch", "boot_size", "alive_count", "alive_mask",
    "resizing", "stale_frames", "epoch_transitions"}`` — ``epoch`` 0
    is the bootstrap world and bumps once per committed elastic
    resize; ``alive_mask`` bit r means world rank r is a member;
    ``resizing`` is True while a membership agreement/rebuild is in
    flight; ``stale_frames`` counts frames dropped for carrying a
    pre-resize epoch (diagnostic); ``epoch_transitions`` counts the
    resize epochs THIS process has observed via the health path — the
    exporter's per-epoch transition counter (a rejoined replacement
    starts at 0 even though the world epoch it joins is higher)."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return None
    epoch = ctypes.c_uint32(0)
    alive = ctypes.c_int32(0)
    mask = ctypes.c_uint64(0)
    resizing = ctypes.c_int32(0)
    stale = ctypes.c_uint64(0)
    if not lib.t4j_world_info(
        ctypes.byref(epoch), ctypes.byref(alive), ctypes.byref(mask),
        ctypes.byref(resizing), ctypes.byref(stale),
    ):
        return None
    return {
        "epoch": int(epoch.value),
        "boot_size": int(lib.t4j_world_size()),
        "alive_count": int(alive.value),
        "alive_mask": int(mask.value),
        "resizing": bool(resizing.value),
        "stale_frames": int(stale.value),
        "epoch_transitions": int(_state.get("epoch_transitions", 0)),
    }


def _mask_ranks(mask, boot_size):
    if boot_size > 64:
        return tuple(range(boot_size))
    return tuple(r for r in range(boot_size) if (mask >> r) & 1)


def alive_ranks():
    """The current members as a sorted tuple of world ranks (the full
    bootstrap range before init or outside elastic jobs)."""
    info = world_info()
    if info is None:
        return None
    return _mask_ranks(info["alive_mask"], info["boot_size"])


def effective_world_size():
    """Current member count (= :func:`world_size` until a resize
    shrinks the membership).  The tuning layer keys its topology
    fingerprint off this, so a resize re-resolves the knobs."""
    info = world_info()
    if info is None:
        return world_size()
    return info["alive_count"]


def resize_wait(timeout_s=None):
    """Block until no elastic resize is in progress (True when
    settled).  ``None`` uses twice the configured T4J_RESIZE_TIMEOUT
    plus slack — a resize that cannot finish inside that posts a fault
    anyway."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return True
    if timeout_s is None:
        from mpi4jax_tpu.utils import config

        timeout_s = 2 * config.resize_timeout() + 10.0
    return bool(lib.t4j_resize_wait(float(timeout_s)))


def _check_world_epoch(lib):
    """Raise :class:`WorldResized` when the membership changed since
    the last check (clearing the stale comm-handle cache first); wait
    out an in-flight resize so the caller sees the verdict, not the
    turbulence."""
    info = world_info()
    if info is None:
        return
    if info["resizing"]:
        resize_wait()
        info = world_info()
        if info is None:
            return
    last = _state.get("world_view")
    if last is None:
        _state["world_view"] = info
        return
    if info["epoch"] != last["epoch"]:
        _state["world_view"] = info
        _state["comm_cache"].clear()  # pre-resize handles are stale
        _state["epoch_transitions"] = (
            _state.get("epoch_transitions", 0) + 1
        )
        raise WorldResized(
            _mask_ranks(last["alive_mask"], info["boot_size"]),
            _mask_ranks(info["alive_mask"], info["boot_size"]),
            info["epoch"],
        )


def refresh_after_resize(progress=None):
    """Re-resolve the substrate for the resized world: drop the stale
    comm-handle cache and re-run the tuning resolution against the NEW
    topology fingerprint (docs/performance.md "trace-guided
    autotuning").  COLLECTIVE — every surviving member must call it
    (the elastic training loop does, right after catching
    :class:`WorldResized`; a rejoined replacement runs the same
    resolution inside its own ``ensure_initialized``)."""
    _state["comm_cache"].clear()
    try:
        from mpi4jax_tpu import tuning

        return tuning.startup(progress=progress)
    except BridgeError:
        raise
    except Exception as e:  # noqa: BLE001 — cache trouble must not kill
        import sys as _sys

        print(
            "t4j: tuning re-resolution after resize skipped: "
            f"{type(e).__name__}: {e}",
            file=_sys.stderr,
            flush=True,
        )
        return None


_TEL_MODES = {"off": 0, "counters": 1, "trace": 2}
_TEL_MODE_NAMES = {v: k for k, v in _TEL_MODES.items()}


def set_telemetry(mode=None, ring_bytes=None):
    """Runtime override of the telemetry knobs (docs/observability.md).

    ``mode`` is ``"off"`` (zero-cost no-op, the default), ``"counters"``
    (metrics table + control-plane events) or ``"trace"`` (plus
    per-event records — the Perfetto feed); ``None`` keeps the current
    setting.  ``ring_bytes`` bounds the per-rank event ring.  Must be
    set before the first instrumented call: the ring is sized on first
    use and never re-sized."""
    lib = _load()
    code = -1 if mode is None else _TEL_MODES[str(mode)]
    lib.t4j_set_telemetry(
        code, -1 if ring_bytes is None else int(ring_bytes)
    )
    if mode is not None:
        # keep the Python-lane recorder in lockstep: it caches the env
        # mode on first use, and a runtime override that only reached
        # the native ring would silently drop the python timeline lane
        from mpi4jax_tpu.telemetry import recorder

        recorder.set_mode(str(mode))


def telemetry_mode_name():
    """The active telemetry mode as a string (``off`` before load)."""
    lib = _state["lib"]
    if lib is None:
        return "off"
    return _TEL_MODE_NAMES.get(int(lib.t4j_telemetry_mode()), "off")


def set_flight(enabled=None, directory=None):
    """Pre-init override of the flight-recorder knobs
    (docs/observability.md "flight recorder"): ``enabled`` True/False
    (None keeps), ``directory`` the file location (None keeps).  Must
    run before :func:`ensure_initialized` — the mmap'd arena is
    created once during bridge init."""
    lib = _load()
    code = -1 if enabled is None else (1 if enabled else 0)
    lib.t4j_set_flight(
        code, None if directory is None else str(directory).encode()
    )


def flight_info():
    """Live status of this rank's flight recorder, or ``None`` when it
    is off / the bridge never initialized: ``{"path", "file_bytes",
    "heartbeat_ns" (CLOCK_MONOTONIC), "heartbeat_count", "epoch",
    "heartbeat_age_s"}``."""
    lib = _state["lib"]
    if lib is None:
        return None
    path = ctypes.create_string_buffer(4096)
    fb = ctypes.c_uint64(0)
    hb = ctypes.c_uint64(0)
    hc = ctypes.c_uint64(0)
    ep = ctypes.c_uint64(0)
    if not lib.t4j_flight_info(path, len(path), ctypes.byref(fb),
                               ctypes.byref(hb), ctypes.byref(hc),
                               ctypes.byref(ep)):
        return None
    import time as _time

    now = _time.clock_gettime_ns(_time.CLOCK_MONOTONIC)
    return {
        "path": path.value.decode(errors="replace"),
        "file_bytes": int(fb.value),
        "heartbeat_ns": int(hb.value),
        "heartbeat_count": int(hc.value),
        "epoch": int(ep.value),
        "heartbeat_age_s": max(0.0, (now - int(hb.value)) / 1e9)
        if hb.value else None,
    }


def annotate_step(index, phase):
    """Emit a step-boundary event into the native ring (``phase`` 1 =
    begin, 2 = end; ``index`` is the caller-assigned step number).
    The public surface is :func:`mpi4jax_tpu.ops.step.annotate_step` —
    this is the plumbing.  No-op (returns False) when the native
    library was never loaded: single-process mesh/self jobs still get
    the python-lane step record from the recorder, they just have no
    native ring to mark.  Never loads or builds the library."""
    lib = _state["lib"]
    if lib is None:
        return False
    lib.t4j_annotate_step(int(index), int(phase))
    return True


def _decode_event_buffer(buf, nbytes):
    from mpi4jax_tpu.telemetry import schema as _schema

    return _schema.decode_events(bytes(buf[: int(nbytes)]))


def telemetry_drain(max_events=1 << 20):
    """Consume the native event ring (oldest first) into a list of
    :class:`telemetry.schema.Event`.  Empty list when telemetry is off
    or the library was never loaded.  The ring outlives finalize, so
    exit-path drains also carry teardown events."""
    lib = _state["lib"]
    if lib is None:
        return []
    out = []
    chunk = ctypes.create_string_buffer(32 * 4096)
    remaining = int(max_events)
    while remaining > 0:
        got = lib.t4j_telemetry_drain(
            chunk, min(remaining, 4096) * 32
        )
        if got <= 0:
            break
        events = _decode_event_buffer(chunk.raw, got)
        out.extend(events)
        remaining -= len(events)
    return out


def telemetry_last(n=16):
    """The newest ``n`` native events WITHOUT consuming them (the
    check_health post-mortem peek)."""
    lib = _state["lib"]
    if lib is None or n <= 0:
        return []
    buf = ctypes.create_string_buffer(32 * int(n))
    got = lib.t4j_telemetry_peek_last(buf, len(buf))
    return _decode_event_buffer(buf.raw, got)


def telemetry_dropped():
    lib = _state["lib"]
    return int(lib.t4j_telemetry_dropped()) if lib is not None else 0


def telemetry_anchor():
    """(mono_ns, unix_ns) clock anchor captured right after the
    bootstrap join barrier (docs/observability.md "clock alignment");
    captured lazily for single-process runs."""
    lib = _load()
    mono = ctypes.c_uint64(0)
    unix = ctypes.c_uint64(0)
    lib.t4j_telemetry_anchor(ctypes.byref(mono), ctypes.byref(unix))
    return mono.value, unix.value


def metrics_snapshot():
    """The native metrics table as a list of u64 words (parse with
    ``telemetry.schema.parse_snapshot`` / feed to
    ``telemetry.registry.MetricsRegistry.from_snapshot``).  Empty list
    when the library was never loaded or nothing was counted."""
    lib = _state["lib"]
    if lib is None:
        return []
    need = lib.t4j_metrics_snapshot(None, 0)
    if need <= 0:
        return []
    # sizing/fill race: a concurrent op can add a table row between
    # the two calls, making the fill call return the NEW required size
    # without writing (the native side never overruns the buffer).
    # Retry with the fresh size; the table has finitely many rows, so
    # this converges — the bound is just a backstop.
    for _ in range(4):
        buf = (ctypes.c_uint64 * int(need))()
        got = lib.t4j_metrics_snapshot(buf, need)
        if got <= need:
            return list(buf[: int(got)])
        need = got
    return []


def _format_recent_events(events):
    """Compact post-mortem rendering of the ring tail — delegates to
    the shared :func:`telemetry.schema.format_recent_events` so
    check_health, the launcher's first-failure report, and the
    exporter's one-shot export all render the tail identically."""
    from mpi4jax_tpu.telemetry import schema as _schema

    return _schema.format_recent_events(events)


def notify_abort(why):
    """Best-effort MPI_Abort analog: tell every peer this process is
    going down so their blocked collectives raise instead of hanging
    until the launcher's external kill."""
    lib = _state["lib"]
    if lib is not None and lib.t4j_initialized():
        lib.t4j_abort_notify(str(why).encode("utf-8", "replace"))


def set_tuning(ring_min_bytes=None, seg_bytes=None):
    """Runtime override of the TCP-tier collective tuning, in bytes.

    ``None`` keeps the current value; ``ring_min_bytes=0`` forces the
    segmented ring path for every message size.  Must be set uniformly
    across ranks (the launcher propagates ``T4J_RING_MIN_BYTES`` /
    ``T4J_SEG_BYTES``): ranks disagreeing on the switchover would run
    mismatched algorithms and deadlock."""
    lib = _load()
    lib.t4j_set_tuning(
        -1 if ring_min_bytes is None else int(ring_min_bytes),
        0 if seg_bytes is None else int(seg_bytes),
    )


def set_coalesce(bytes_threshold=None):
    """Runtime override of the small-message coalescing threshold
    (docs/performance.md "small-message coalescing"), in bytes.

    ``None`` keeps the current value; 0 disables fusion entirely (the
    exact pre-coalescing wire behaviour).  Must be uniform across
    ranks: both sides of a fused exchange must agree to fuse."""
    lib = _load()
    lib.t4j_set_coalesce(
        -1 if bytes_threshold is None else int(bytes_threshold)
    )


def coalesce_bytes():
    """The native layer's effective coalescing threshold in bytes."""
    lib = _load()
    return int(lib.t4j_coalesce_bytes())


_HIER_MODES = {"auto": 0, "on": 1, "off": 2}


def set_hier(mode=None, leader_ring_min_bytes=None):
    """Runtime override of the hierarchical-collective selection.

    ``mode`` is ``"auto"`` (size threshold), ``"on"`` (force wherever
    the topology allows) or ``"off"``; ``None`` keeps the current
    setting.  ``leader_ring_min_bytes`` is auto mode's switchover.
    Must be set uniformly across ranks (the launcher propagates
    ``T4J_HIER`` / ``T4J_LEADER_RING_MIN_BYTES``): ranks disagreeing
    on the selection would run mismatched algorithms and deadlock."""
    lib = _load()
    code = -1 if mode is None else _HIER_MODES[str(mode)]
    lib.t4j_set_hier(
        code,
        -1 if leader_ring_min_bytes is None else int(leader_ring_min_bytes),
    )


def topology():
    """Bootstrap topology of this rank, or ``None`` before init.

    Returns ``{"host_id", "local_rank", "local_size", "leader_rank",
    "n_hosts"}`` — host ordinals in first-occurrence order over world
    ranks, the leader being the lowest world rank on the host.  This
    is the map the hierarchical collectives are built on
    (docs/performance.md "hierarchical collectives")."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return None
    vals = [ctypes.c_int32(0) for _ in range(5)]
    if not lib.t4j_topo(*[ctypes.byref(v) for v in vals]):
        return None
    keys = ("host_id", "local_rank", "local_size", "leader_rank", "n_hosts")
    return dict(zip(keys, (v.value for v in vals)))


def hier_would_select(handle, total_bytes):
    """Would a collective of ``total_bytes`` on this comm handle take
    the hierarchical path right now?  Pure query — never communicates
    (benchmarks use it to label records)."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return False
    return lib.t4j_hier_would_select(int(handle), int(total_bytes)) == 1


def hier_active(handle):
    """True once the comm's hierarchical layer has been negotiated and
    is live (passive read)."""
    lib = _state["lib"]
    if lib is None or not lib.t4j_initialized():
        return False
    return lib.t4j_hier_active(int(handle)) == 1


def host_hier_allreduce(handle, x, opcode):
    """Explicitly hierarchical allreduce (raises when the topology is
    ineligible) — the auto-selected path is :func:`host_allreduce`."""
    import numpy as np

    x = _contig(x)
    out = np.empty_like(x)
    _check(_state["lib"].t4j_c_hier_allreduce(
        handle, _ptr(x), _ptr(out), x.size, dtype_code(x.dtype), opcode
    ))
    return out


def set_timeouts(op_s=None, connect_s=None):
    """Runtime override of the bridge deadlines, in seconds.

    ``None`` keeps the current value; ``op_s=0`` disables the per-op
    deadline.  Useful to arm a tight deadline only after warmup
    (startup skew and first-call compiles legitimately exceed
    sub-second deadlines)."""
    lib = _load()
    lib.t4j_set_timeouts(
        -1.0 if op_s is None else float(op_s),
        -1.0 if connect_s is None else float(connect_s),
    )


# numpy dtype -> native DType enum (dcn.h; the reference's 14-entry
# dtype table, mpi4jax/_src/utils.py:43-71, plus bf16)
_DTYPE_CODES = {
    "float32": 0,
    "float64": 1,
    "int8": 2,
    "int16": 3,
    "int32": 4,
    "int64": 5,
    "uint8": 6,
    "uint16": 7,
    "uint32": 8,
    "uint64": 9,
    "bool": 10,
    "complex64": 11,
    "complex128": 12,
    "float16": 13,
    "bfloat16": 14,
}


def dtype_code(np_dtype):
    name = str(np_dtype)
    try:
        return _DTYPE_CODES[name]
    except KeyError:
        raise ValueError(f"unsupported dtype for the native bridge: {name}")


def _ptr(arr):
    return arr.ctypes.data_as(ctypes.c_void_p)


def _contig(x):
    import numpy as np

    return np.ascontiguousarray(x)


# -- numpy-level op wrappers (host-callback data plane) --------------------


def host_allreduce(handle, x, opcode):
    import numpy as np

    x = _contig(x)
    out = np.empty_like(x)
    _check(_state["lib"].t4j_c_allreduce(
        handle, _ptr(x), _ptr(out), x.size, dtype_code(x.dtype), opcode
    ))
    return out


def host_reduce(handle, x, opcode, root):
    import numpy as np

    x = _contig(x)
    out = np.empty_like(x)
    _check(_state["lib"].t4j_c_reduce(
        handle, _ptr(x), _ptr(out), x.size, dtype_code(x.dtype), opcode, root
    ))
    if _state["lib"].t4j_comm_rank(handle) != root:
        return x  # off-root output is the input passthrough (wrapper contract)
    return out


def host_reduce_scatter(handle, x, opcode):
    """``x`` has shape ``(comm_size, *rest)``; returns the reduction of
    row ``rank`` (MPI_Reduce_scatter_block over the segmented ring)."""
    import numpy as np

    x = _contig(x)
    out = np.empty(x.shape[1:], x.dtype)
    _check(_state["lib"].t4j_c_reduce_scatter(
        handle, _ptr(x), _ptr(out), out.size, dtype_code(x.dtype), opcode
    ))
    return out


def host_scan(handle, x, opcode):
    import numpy as np

    x = _contig(x)
    out = np.empty_like(x)
    _check(_state["lib"].t4j_c_scan(
        handle, _ptr(x), _ptr(out), x.size, dtype_code(x.dtype), opcode
    ))
    return out


def host_barrier(handle):
    _check(_state["lib"].t4j_c_barrier(handle))


def host_bcast(handle, x, root):
    import numpy as np

    x = np.array(x, order="C")  # one writable contiguous copy
    _check(_state["lib"].t4j_c_bcast(handle, _ptr(x), x.nbytes, root))
    return x


def host_allgather(handle, x):
    import numpy as np

    x = _contig(x)
    n = _state["lib"].t4j_comm_size(handle)
    out = np.empty((n, *x.shape), x.dtype)
    _check(_state["lib"].t4j_c_allgather(handle, _ptr(x), _ptr(out), x.nbytes))
    return out


def host_gather(handle, x, root):
    import numpy as np

    x = _contig(x)
    n = _state["lib"].t4j_comm_size(handle)
    out = np.empty((n, *x.shape), x.dtype)
    _check(_state["lib"].t4j_c_gather(handle, _ptr(x), _ptr(out), x.nbytes, root))
    return out


def host_scatter(handle, x, root):
    import numpy as np

    x = _contig(x)
    lib = _state["lib"]
    if lib.t4j_comm_rank(handle) == root:
        out = np.empty(x.shape[1:], x.dtype)
        nbytes_each = out.nbytes
    else:
        out = np.empty(x.shape, x.dtype)
        nbytes_each = out.nbytes
    _check(lib.t4j_c_scatter(handle, _ptr(x), _ptr(out), nbytes_each, root))
    return out


def host_alltoall(handle, x):
    import numpy as np

    x = _contig(x)
    n = _state["lib"].t4j_comm_size(handle)
    out = np.empty_like(x)
    _check(_state["lib"].t4j_c_alltoall(handle, _ptr(x), _ptr(out), x.nbytes // n))
    return out


def host_send(handle, x, dest, tag):
    x = _contig(x)
    _check(_state["lib"].t4j_c_send(handle, _ptr(x), x.nbytes, dest, tag))


def host_recv(handle, shape, dtype, source, tag):
    import numpy as np

    out = np.empty(shape, dtype)
    src = ctypes.c_int32(0)
    tg = ctypes.c_int32(0)
    _check(_state["lib"].t4j_c_recv(
        handle, _ptr(out), out.nbytes, source, tag,
        ctypes.byref(src), ctypes.byref(tg),
    ))
    return out, np.int32(src.value), np.int32(tg.value)


def _ptr_array(arrays):
    arr = (ctypes.c_void_p * max(len(arrays), 1))()
    for i, a in enumerate(arrays):
        arr[i] = a.ctypes.data
    return arr


def _u64_array(sizes):
    return (ctypes.c_uint64 * max(len(sizes), 1))(*sizes)


def host_sendrecv_fused(handle, send_arrays, recv_templates, source, dest,
                        sendtag, recvtag):
    """Fused multi-part sendrecv (docs/performance.md "small-message
    coalescing"): every part in ``send_arrays`` travels in ONE wire
    frame to ``dest``, and one frame from ``source`` is scattered into
    arrays shaped like ``recv_templates`` (anything with ``.shape`` /
    ``.dtype`` — ShapeDtypeStructs included, so callers need not
    materialise template arrays).  Empty ``send_arrays`` /
    ``recv_templates`` select the one-sided halves.  Returns
    ``(outs, src, tag)``."""
    import numpy as np

    sends = [_contig(a) for a in send_arrays]
    outs = [np.empty(tuple(t.shape), t.dtype) for t in recv_templates]
    src = ctypes.c_int32(-1)
    tg = ctypes.c_int32(-1)
    _check(_state["lib"].t4j_c_sendrecv_fused(
        handle, _ptr_array(sends),
        _u64_array([a.nbytes for a in sends]), len(sends),
        _ptr_array(outs), _u64_array([o.nbytes for o in outs]),
        len(outs), source, dest, sendtag, recvtag,
        ctypes.byref(src), ctypes.byref(tg),
    ))
    return outs, np.int32(src.value), np.int32(tg.value)


def host_alltoall_fused(handle, parts):
    """Fused multi-part alltoall: part i has shape ``(comm_size,
    *rest_i)``; each peer receives ONE frame carrying its slice of
    every part (bit-identical to per-part ``host_alltoall``).  Returns
    the output parts."""
    import numpy as np

    parts = [_contig(p) for p in parts]
    outs = [np.empty_like(p) for p in parts]
    n = _state["lib"].t4j_comm_size(handle)
    _check(_state["lib"].t4j_c_alltoall_fused(
        handle, _ptr_array(parts), _ptr_array(outs),
        _u64_array([p.nbytes // n for p in parts]), len(parts),
    ))
    return outs


def host_sendrecv(handle, sendbuf, recvbuf, source, dest, sendtag, recvtag):
    import numpy as np

    sendbuf = _contig(sendbuf)
    out = np.empty(recvbuf.shape, recvbuf.dtype)
    src = ctypes.c_int32(0)
    tg = ctypes.c_int32(0)
    _check(_state["lib"].t4j_c_sendrecv(
        handle, _ptr(sendbuf), sendbuf.nbytes, _ptr(out), out.nbytes,
        source, dest, sendtag, recvtag, ctypes.byref(src), ctypes.byref(tg),
    ))
    return out, np.int32(src.value), np.int32(tg.value)


# -- async request layer (docs/async.md) ----------------------------------
#
# Nonblocking submits hand the native progress engine RAW buffer
# pointers, so the numpy arrays MUST outlive the request: the registry
# below pins (input, output) per request id until the matching
# host_wait/host_test-done consumes it.  Never-waited entries are the
# request leaks reported at finalize (and statically by t4j-lint rule
# T4J008, docs/static-analysis.md).

_async_reqs = {}  # rid -> {"kind", "out", "keep"}


def _async_submit(kind, rid, out, keep):
    if not rid:
        raise BridgeError(
            last_error() or f"native {kind} submit failed (no detail)"
        )
    _async_reqs[int(rid)] = {"kind": kind, "out": out, "keep": keep}
    return int(rid)


def host_iallreduce(handle, x, opcode):
    """Submit a nonblocking allreduce; returns the request id.  The
    result array is produced by :func:`host_wait` on that id."""
    import numpy as np

    x = _contig(x)
    out = np.empty_like(x)
    rid = _state["lib"].t4j_iallreduce(
        handle, _ptr(x), _ptr(out), x.size, dtype_code(x.dtype), opcode
    )
    return _async_submit("iallreduce", rid, out, (x,))


def host_ireduce_scatter(handle, x, opcode):
    """Nonblocking MPI_Reduce_scatter_block submit: ``x`` has shape
    ``(comm_size, *rest)``; wait returns the reduction of row rank."""
    import numpy as np

    x = _contig(x)
    out = np.empty(x.shape[1:], x.dtype)
    rid = _state["lib"].t4j_ireduce_scatter(
        handle, _ptr(x), _ptr(out), out.size, dtype_code(x.dtype), opcode
    )
    return _async_submit("ireduce_scatter", rid, out, (x,))


def host_isend(handle, x, dest, tag):
    x = _contig(x)
    rid = _state["lib"].t4j_isend(
        handle, _ptr(x), x.nbytes, int(dest), int(tag)
    )
    return _async_submit("isend", rid, None, (x,))


def host_irecv(handle, shape, dtype, source, tag):
    import numpy as np

    out = np.empty(shape, dtype)
    rid = _state["lib"].t4j_irecv(
        handle, _ptr(out), out.nbytes, int(source), int(tag)
    )
    return _async_submit("irecv", rid, out, ())


def host_wait(rid):
    """Block until request ``rid`` completes; consumes it.

    Returns ``(out, src, tag)`` — ``out`` is the result array (``None``
    for isend), ``src``/``tag`` the matched envelope for irecv (-1
    otherwise).  Raises BridgeError with the engine-side context when
    the op failed, and on a second wait of the same request."""
    rec = _async_reqs.pop(int(rid), None)
    src = ctypes.c_int32(-1)
    tag = ctypes.c_int32(-1)
    status = _state["lib"].t4j_wait(
        ctypes.c_uint64(int(rid)), ctypes.byref(src), ctypes.byref(tag)
    )
    if status:
        raise BridgeError(
            last_error() or "native wait failed (no detail)"
        )
    if rec is None:
        # the native layer accepted the wait (double-bookkeeping drift:
        # should be unreachable — native is the source of truth)
        return None, src.value, tag.value
    return rec["out"], src.value, tag.value


def host_test(rid):
    """Nonblocking completion probe: True when request ``rid`` is
    complete (it is NOT consumed — call :func:`host_wait` to fetch the
    result and release it).  A failed op raises here, consuming it."""
    done = ctypes.c_int32(0)
    status = _state["lib"].t4j_test(
        ctypes.c_uint64(int(rid)), ctypes.byref(done), None, None
    )
    if status:
        _async_reqs.pop(int(rid), None)
        raise BridgeError(last_error() or "native test failed (no detail)")
    return bool(done.value)


def async_inflight():
    """Progress-engine gauge: requests submitted but not yet complete
    (queued + running + parked).  0 when idle or before load."""
    lib = _state["lib"]
    return int(lib.t4j_async_inflight()) if lib is not None else 0


def async_pending():
    """Requests this process never consumed with wait (leak gauge).

    The native engine is authoritative: requests submitted through the
    in-jit FFI fast path never enter the Python-side registry, but
    every request (FFI or callback path) lives in the engine's inflight
    table until waited."""
    lib = _state["lib"]
    if lib is not None and lib.t4j_initialized():
        return int(lib.t4j_async_pending())
    return len(_async_reqs)


def async_assert_drained():
    """Raise if any async request was submitted but never waited — the
    runtime counterpart of ``Token.assert_drained`` (t4j-lint reports
    the same statically as rule T4J008)."""
    n = async_pending()
    if n:
        kinds = ", ".join(
            f"{rec['kind']} (req {rid})"
            for rid, rec in list(_async_reqs.items())[:8]
        ) or "submitted via the in-jit fast path"
        raise BridgeError(
            f"{n} async request(s) never waited: {kinds}"
            " — every iallreduce/isend/irecv must be completed by "
            "wait/waitall exactly once (docs/async.md)"
        )


def available():
    """True when this process is part of a multi-process job (launched
    via mpi4jax_tpu.launch or with T4J_RANK/T4J_SIZE set)."""
    return "T4J_RANK" in os.environ and "T4J_SIZE" in os.environ


def is_initialized():
    lib = _state["lib"]
    return bool(lib and lib.t4j_initialized())


def _ffi_module():
    import jax.ffi

    return jax.ffi


def _register_ffi_targets(lib):
    if _state["registered"]:
        return
    ffi = _ffi_module()

    for name in HANDLER_NAMES:
        fn = getattr(lib, name)
        ffi.register_ffi_target(name, ffi.pycapsule(fn), platform="cpu")
    _state["registered"] = True


def ensure_initialized():
    """Bootstrap the process world (idempotent).

    The analog of the reference's import-time ``from mpi4py import MPI``
    (mpi4jax/_src/__init__.py:3), made lazy/explicit: connects the TCP
    mesh, registers the XLA FFI targets, and installs the exit hook.
    """
    if is_initialized():
        return True
    if not available():
        return False
    # utils/config.py owns deadline validation: a bad T4J_OP_TIMEOUT /
    # T4J_CONNECT_TIMEOUT raises ValueError here, before the native
    # library is even built/loaded
    from mpi4jax_tpu.utils import config

    op_s, connect_s = config.op_timeout(), config.connect_timeout()
    ring_min, seg = config.ring_min_bytes(), config.seg_bytes()
    coalesce = config.coalesce_bytes()
    # wire-path knobs (docs/performance.md "striped links and the
    # zero-copy path"): validated loudly here, threaded before init —
    # the stripe count decides how many connections bootstrap builds.
    # "auto" stays native-default (one flow) until the tuning layer
    # resolves a calibrated width post-init.
    wire_stripes = config.stripes()
    zc_min = config.zerocopy_min_bytes()
    batch = config.sendmsg_batch()
    flow = config.emu_flow_bps()
    # compressed-collective wire dtype (docs/performance.md
    # "Compressed collectives"): a typo'd T4J_WIRE_DTYPE raises HERE,
    # before init — silently running uncompressed would fake the
    # benchmark the operator asked for.  Note the eligibility rule is
    # per-collective in the native layer (f32 SUM only; integer and
    # MIN/MAX payloads have no defined cast and always travel exact),
    # so fp8/bf16 is a policy cap, not a promise.
    wdtype = config.wire_dtype()
    # wire data-plane backend (docs/performance.md "io_uring wire
    # backend"): a typo'd T4J_WIRE_BACKEND raises HERE, before init.
    # An EXPLICIT uring request on a kernel whose io_uring probe fails
    # is also rejected below (after the library loads) — the managed
    # path fails loud rather than silently benchmarking sendmsg under
    # a uring label; standalone ctypes users get the native layer's
    # loud one-line degrade instead.
    wbackend = config.wire_backend()
    if zc_min > 0 and zc_min < 4096:
        raise ValueError(
            f"T4J_ZEROCOPY_MIN_BYTES={zc_min} is below the page floor "
            "(4096): MSG_ZEROCOPY pins whole pages per send, and "
            "sub-page frames pay the pin/completion round-trip for "
            "no copy saved — use 0 (off) or >= 4096 "
            "(docs/performance.md \"striped links and the zero-copy "
            "path\")"
        )
    config.autotune_enabled()  # loud validation; the flag acts post-init
    hier, hier_min = config.hier_mode(), config.leader_ring_min_bytes()
    retry = config.retry_max()
    boff_base, boff_max = config.backoff_base(), config.backoff_max()
    replay = config.replay_bytes()
    elastic = config.elastic_mode()
    world_floor = config.min_world()
    resize_s = config.resize_timeout()
    if elastic != "off" and retry == 0:
        raise ValueError(
            "T4J_ELASTIC="
            f"{elastic} requires T4J_RETRY_MAX > 0: the elastic rung "
            "triggers when the self-healing ladder's escalation "
            "declares a rank unrecoverable, and T4J_RETRY_MAX=0 "
            "disables that ladder entirely "
            "(docs/failure-semantics.md \"elastic membership\")"
        )
    # serving knobs (docs/serving.md): validated loudly here like the
    # deadlines — they act in the Python serving tier post-init
    serve_slo = config.slo_ms()
    config.max_batch()
    serve_admit = config.admit_mode()
    if serve_slo > 0 and serve_admit == "off":
        raise ValueError(
            f"T4J_SLO_MS={serve_slo:g} with T4J_ADMIT=off: an SLO "
            "with admission control off cannot be enforced, only "
            "missed — set T4J_ADMIT=on (shed to hold the deadline) "
            "or drop the SLO (docs/serving.md \"admission control\")"
        )
    autoscale = config.autoscale_mode()
    config.scale_up_windows()
    config.scale_down_occ()
    config.scale_down_windows()
    config.scale_cooldown_windows()
    if autoscale == "on" and elastic != "rejoin":
        raise ValueError(
            f"T4J_AUTOSCALE=on with T4J_ELASTIC={elastic}: growing "
            "the world admits a relaunched rank through the kept-open "
            "coordinator port, which only the rejoin mode provides — "
            "set T4J_ELASTIC=rejoin (docs/serving.md \"Autoscaling\")"
        )
    tel_mode, tel_bytes = config.telemetry_mode(), config.telemetry_bytes()
    tel_dir = config.telemetry_dir()
    flight = config.flight_enabled()
    fdir = config.flight_dir() or tel_dir
    lib = _load()
    lib.t4j_set_timeouts(op_s, connect_s)
    lib.t4j_set_tuning(ring_min, seg)
    lib.t4j_set_coalesce(coalesce)
    lib.t4j_set_wire(
        0 if wire_stripes == "auto" else int(wire_stripes),
        zc_min, batch, flow,
    )
    lib.t4j_set_wire_dtype(WIRE_DTYPE_CODES[wdtype])
    lib.t4j_set_wire_backend(WIRE_BACKEND_CODES[wbackend])
    if wbackend == "uring":
        binfo = wire_backend_info()
        if binfo is not None and not binfo["uring_supported"]:
            raise ValueError(
                "T4J_WIRE_BACKEND=uring but this kernel has no usable "
                "io_uring (the probe failed) — use auto (resolves to "
                "sendmsg here) or sendmsg (docs/performance.md "
                "\"io_uring wire backend\")"
            )
    lib.t4j_set_hier(_HIER_MODES[hier], hier_min)
    lib.t4j_set_resilience(retry, boff_base, boff_max, replay)
    lib.t4j_set_elastic(_ELASTIC_MODES[elastic], world_floor, resize_s)
    lib.t4j_set_telemetry(_TEL_MODES[tel_mode], tel_bytes)
    # crash-consistent flight recorder (docs/observability.md "flight
    # recorder"): must be decided before init — the mmap'd arena is
    # created inside t4j_init while the process is single-threaded
    lib.t4j_set_flight(
        1 if flight else 0, None if fdir is None else str(fdir).encode()
    )
    rc = lib.t4j_init()
    if rc != 0:
        detail = last_error()
        raise BridgeError(
            detail
            if detail
            else "native bridge init failed (check T4J_* env)"
        )
    _register_ffi_targets(lib)
    # membership baseline: a rejoined replacement starts at the
    # survivors' current epoch without a spurious WorldResized
    _state["world_view"] = world_info()
    # trace-guided tuning (docs/performance.md "trace-guided
    # autotuning"): load the fingerprint-keyed cache and thread it
    # through the same set_tuning/set_hier/set_coalesce plumbing;
    # explicit T4J_* env always wins, rank 0's resolution is broadcast
    # so divergent per-host cache files can never split the knob
    # vector.  T4J_AUTOTUNE calibrates first (collective) and writes
    # the cache.  A corrupt/stale cache degrades to env/defaults with
    # a warning rather than killing the job.
    try:
        from mpi4jax_tpu import tuning

        tuning.startup(progress=lambda m: print(m, flush=True))
    except BridgeError:
        raise  # a wedged collective during autotune is a real failure
    except Exception as e:  # noqa: BLE001 — cache trouble must not kill
        import sys as _sys

        print(
            f"t4j: tuning cache ignored: {type(e).__name__}: {e}",
            file=_sys.stderr,
            flush=True,
        )
    if tel_dir is not None:
        # registered BEFORE finalize: atexit runs LIFO, so the drain
        # happens after teardown and carries the exit-phase events too
        from mpi4jax_tpu.telemetry import dump

        dump.install_atexit(tel_dir)
    # live metrics exporter (docs/observability.md "live exporter"):
    # T4J_METRICS_PORT=P makes rank k serve its metrics snapshot +
    # link stats on 127.0.0.1:P+k as Prometheus text (/metrics) and
    # JSON (/metrics.json); the launcher's --metrics sets it and
    # aggregates the job view
    mport = config.metrics_port()
    if mport:
        try:
            from mpi4jax_tpu.telemetry import exporter

            srv = exporter.MetricsExporter(
                mport + int(lib.t4j_world_rank())
            )
            srv.start()
            _state["exporter"] = srv
        except Exception as e:  # noqa: BLE001 — metrics must not kill the job
            import sys as _sys

            print(
                f"t4j: metrics exporter failed to start: "
                f"{type(e).__name__}: {e}",
                file=_sys.stderr,
                flush=True,
            )
    atexit.register(finalize)
    return True


def finalize():
    srv = _state.pop("exporter", None)
    if srv is not None:
        try:
            srv.stop()
        except Exception:
            pass
    lib = _state["lib"]
    if lib and lib.t4j_initialized():
        # request-leak detection (docs/async.md): loud on stderr — the
        # native stop reports its own count too, but only this layer
        # knows the Python-level op kinds.  Not raised: finalize runs
        # from atexit, where an exception would mask the job's real
        # outcome; tests assert on the message instead.
        if _async_reqs:
            import sys as _sys

            kinds = ", ".join(
                rec["kind"] for rec in list(_async_reqs.values())[:8]
            )
            print(
                f"t4j: {len(_async_reqs)} async request(s) never waited "
                f"at finalize ({kinds}) — request leak; every "
                "iallreduce/isend/irecv must be completed by "
                "wait/waitall (docs/async.md)",
                file=_sys.stderr,
                flush=True,
            )
        # snapshot the teardown-sensitive telemetry state (per-link
        # counters, topology) while still initialized: the exit-time
        # rank-file drain deliberately runs AFTER this (atexit LIFO)
        # and would otherwise write link_stats {}
        try:
            from mpi4jax_tpu.utils import config

            if config.telemetry_dir() is not None:
                from mpi4jax_tpu.telemetry import dump

                dump.capture_runtime_state()
        except Exception:
            pass
        # flush pending XLA work before tearing down sockets — the
        # reference registers the same hygiene (decorators.py:11-24,
        # flush.py) to avoid the deadlock-on-exit class of bugs.
        # Skipped after a fault: pending work may itself be a wedged
        # collective, and native finalize already skips the exit
        # barrier then.
        if not lib.t4j_health():
            try:
                from mpi4jax_tpu.utils.runtime import drain
                import jax
                import jax.numpy as jnp

                drain(jnp.zeros(()) + 0)
            except Exception:
                pass
        lib.t4j_finalize()
        _async_reqs.clear()  # native reaped everything; release pins


def world_rank():
    ensure_initialized()
    return _state["lib"].t4j_world_rank()


def world_size():
    ensure_initialized()
    return _state["lib"].t4j_world_size()


def set_logging(enabled):
    lib = _load()
    lib.t4j_set_logging(1 if enabled else 0)


def _stable_ctx(ranks, context):
    """Deterministic 30-bit channel id for a communicator.

    Every member must derive the same wire context regardless of its
    local comm-creation order (MPMD processes create comms at different
    times), so the id is a pure function of the group + clone generation
    — FNV-1a over the rank list and context counter.  The world comm is
    pinned to ctx 0 natively.
    """
    h = 0x811C9DC5
    for v in (*ranks, 0x7FFFFFFF, context):
        h ^= (v + 1) & 0xFFFFFFFF
        h = (h * 0x01000193) & 0xFFFFFFFF
    ctx = h & 0x3FFFFFFF
    return ctx if ctx != 0 else 1


def comm_handle(comm):
    """Native handle for a ProcComm (cached per (ranks, context))."""
    ensure_initialized()
    key = (tuple(comm.ranks), comm.context)
    cached = _state["comm_cache"].get(key)
    if cached is not None:
        return cached
    lib = _state["lib"]
    if len(comm.ranks) == world_size() and comm.context == 0:
        handle = 0  # the pre-created world communicator
    else:
        arr = (ctypes.c_int32 * len(comm.ranks))(*comm.ranks)
        handle = lib.t4j_comm_create(
            arr, len(comm.ranks), _stable_ctx(comm.ranks, comm.context)
        )
    _state["comm_cache"][key] = handle
    return handle
