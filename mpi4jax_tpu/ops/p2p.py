"""Point-to-point ops: send, recv, sendrecv (+ Status).

Reference API: mpi4jax/_src/collective_ops/send.py:37-60,
recv.py:39-84, sendrecv.py:41-103.

The MPMD→SPMD translation (SURVEY §7 hard part 1): the reference's p2p
ops are *per-rank* calls — rank 0 runs ``send`` while rank 1 runs
``recv`` in a different program.  A single SPMD program is uniform across
devices, so here a p2p pattern is specified *globally*:

* ``dest`` / ``source`` may be a **callable** ``rank -> partner`` (return
  ``None`` to sit out, the MPI_PROC_NULL analog) or an explicit list of
  ``(source_rank, dest_rank)`` pairs;
* a plain ``int`` is only meaningful on size-1 / multi-process backends
  — on a MeshComm it raises with guidance, since "every rank sends to
  rank k" is not a permutation.

``sendrecv`` lowers to one ``lax.ppermute`` over ICI.  Its transpose is
the inverse permutation — exactly the reference's transpose rule that
swaps source and dest so gradients travel the reverse network direction
(sendrecv.py:366-385) — and unlike the reference, forward-mode also works
(the reference hard-errors at sendrecv.py:128-133).

Lone ``send``/``recv`` pairs are matched **at trace time** through the
token: ``send`` stages its payload and pattern on the token's
pending-send queue, and the matching ``recv`` pops it and emits the fused
``ppermute``.  This reproduces MPI's eager-send/matching-recv semantics
(including tag matching and FIFO message order per pattern) with zero
runtime rendezvous cost.  The deadlock-freedom the reference must test
for (tests/collective_ops/test_send_and_recv.py:104-117) holds by
construction: a ppermute cannot deadlock.

Patterns that trace-time matching cannot express fall back to the
**host rendezvous** tier (ops/_rendezvous.py): a ``send`` whose ``dest``
is a traced (data-dependent) per-rank value posts its payload to the
in-process matching engine via ``io_callback``, and a wildcard ``recv``
with no trace-time match takes the earliest-arriving envelope match at
execution time — the reference's runtime ``ANY_SOURCE``/``ANY_TAG``
semantics (recv.py:39-47), with the Status reporting the true runtime
source.  Single-host scope (the engine is per-process); true
cross-process MPMD stays on the proc backend.
"""

import numpy as np

import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops._core import (
    ANY_SOURCE,
    ANY_TAG,
    PendingSendMeta,
    as_token,
    comm_key,
    fence_in,
    fence_out,
    publishes_token,
)
from mpi4jax_tpu.utils.validation import (
    check_comm,
    check_rank_range,
    check_static_int,
)

__all__ = [
    "send",
    "recv",
    "sendrecv",
    "sendrecv_multi",
    "Status",
    "ANY_SOURCE",
    "ANY_TAG",
]


class Status:
    """Output status for recv/sendrecv (MPI.Status analog).

    ``source`` and ``tag`` are filled on return; ``source`` may be a
    traced per-device value on the mesh backend.  The mpi4py accessor
    methods (``Get_source``/``Get_tag``/``Get_error``) are provided for
    call-compatibility with reference user code.
    """

    def __init__(self):
        self.source = None
        self.tag = None

    def Get_source(self):
        return self.source

    def Get_tag(self):
        return self.tag

    def Get_error(self):
        return 0


def _deliver_status(status, st):
    """Fill a Status object, working under jit too.

    Eager values are assigned synchronously (the old behaviour).  Under
    a trace, the reference bakes the MPI_Status struct's address into
    the executable and writes through it at execution time
    (sendrecv.py status out-param; utils.py:35-39 pointer plumbing);
    the JAX-native equivalent is a debug callback that receives the
    concrete envelope each run and mutates the object — read the status
    after the op's results are materialised (or ``jax.effects_barrier``)
    just as the reference requires the execution to have happened.
    """
    import jax

    if not isinstance(st, jax.core.Tracer):
        vals = np.asarray(st)
        status.source = int(vals[0])
        status.tag = int(vals[1])
        return

    def setter(vals):
        status.source = int(vals[0])
        status.tag = int(vals[1])

    jax.debug.callback(setter, st)


def _resolve_pairs(spec, size, role):
    """Normalise a p2p partner spec into (source, dest) pairs.

    ``role`` is "dest" (spec maps rank -> where its data goes) or
    "source" (spec maps rank -> where its data comes from).
    """
    if callable(spec):
        pairs = []
        for r in range(size):
            p = spec(r)
            if p is None:
                continue
            p = int(p)
            if not 0 <= p < size:
                raise ValueError(
                    f"{role} callable returned rank {p} for rank {r}, out "
                    f"of range for communicator of size {size}. Wrap "
                    f"explicitly (e.g. (r + 1) % size) for periodic "
                    f"patterns, or return None to sit out."
                )
            pairs.append((r, p) if role == "dest" else (p, r))
        return pairs
    if isinstance(spec, (list, tuple)) and all(
        isinstance(e, (list, tuple)) and len(e) == 2 for e in spec
    ):
        return [(int(s), int(d)) for s, d in spec]
    value = check_static_int(spec, role)
    if size == 1:
        if value != 0:
            raise ValueError(
                f"{role}={value} out of range for communicator of size 1"
            )
        return [(0, 0)]
    raise ValueError(
        f"{role}={value!r}: a bare integer rank is ambiguous under SPMD "
        f"(all {size} devices would target the same rank, which is not a "
        f"permutation). Pass a callable rank->partner (e.g. "
        f"lambda r: (r + 1) % size), an explicit list of (source, dest) "
        f"pairs, or use comm.shift_perm(axis, disp). Per-rank integer "
        f"addressing, as in MPI, works on the multi-process backend "
        f"(python -m mpi4jax_tpu.launch)."
    )


def _validate_perm(pairs, size, what):
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
        raise ValueError(f"{what} pattern is not a permutation: {pairs}")
    for s, d in pairs:
        if not (0 <= s < size and 0 <= d < size):
            raise ValueError(f"{what} pattern rank out of range: {pairs}")
    return pairs


def _ppermute(x, axes, pairs):
    from mpi4jax_tpu.ops._core import promote_vma

    x = promote_vma(x, axes)
    if all(s == d for s, d in pairs):
        # pure self-sends (e.g. periodic wrap on a size-1 mesh axis): a
        # CollectivePermute would deliver x to every listed rank and 0
        # elsewhere, and callers mask non-destination ranks with the
        # recv template anyway (_recv_merge) — so the collective is an
        # identity with launch overhead.  Eliding it removes ~50 no-op
        # collectives per shallow-water step on a single chip.
        return x
    if x.dtype == jnp.bool_:
        return lax.ppermute(x.astype(jnp.int8), axes, pairs).astype(jnp.bool_)
    return lax.ppermute(x, axes, pairs)


def _recv_merge(permuted, template, pairs, comm):
    """Ranks with no inbound message keep their recv buffer (MPI leaves
    recvbuf untouched for MPI_PROC_NULL partners)."""
    size = comm.size
    if len(pairs) == size:
        return permuted
    has_msg = np.zeros(size, bool)
    for _, d in pairs:
        has_msg[d] = True
    mask = jnp.asarray(has_msg)[comm.rank()]
    return jnp.where(mask, permuted, template)


def _static_source_of(pairs, comm):
    src_of = np.full(comm.size, ANY_SOURCE, np.int32)
    for s, d in pairs:
        src_of[d] = s
    return jnp.asarray(src_of)[comm.rank()]


def _is_runtime_rank(spec):
    """A p2p partner given as a traced per-rank value (data-dependent
    routing) — only resolvable at execution time."""
    import jax

    return isinstance(spec, jax.core.Tracer)


def _is_static_rank_int(spec):
    """A partner given as a plain static int — bools are rejected on
    every rank-taking path (``check_static_int`` semantics), so they
    must not slip through to the rendezvous routes either."""
    return isinstance(spec, (int, np.integer)) and not isinstance(
        spec, (bool, np.bool_)
    )


def _check_tag(tag, rendezvous_ok):
    """Tags are static on the trace-time matching paths (matching keys on
    the value); the rendezvous tier accepts traced tags (they ride the
    io_callback operands).  ADVICE r3: a traced tag used to fall through
    to a generic concretization error."""
    if _is_runtime_rank(tag):
        if rendezvous_ok:
            return tag
        raise TypeError(
            "tag must be a static (trace-time) integer here: trace-time "
            "send/recv matching keys on the tag value. A traced "
            "(runtime-valued) tag is supported only on the mesh backend's "
            "rendezvous tier — send with an int or traced dest, or recv "
            "with an int or traced source or source=ANY_SOURCE (pattern-"
            "list partners stay trace-matched and need a static tag)."
        )
    return check_static_int(tag, "tag")


def _proc_partner(spec, comm, role):
    """Resolve a p2p partner spec to THIS process's partner rank on the
    multi-process backend.

    A plain int keeps the MPI per-rank addressing (each process passes
    its own value).  A callable or (source, dest) pair list — the
    mesh-backend pattern vocabulary, what ``shift_perm`` produces — is
    resolved against ``comm.rank()``; returns ``None`` when this rank
    has no partner in the pattern (the MPI_PROC_NULL analog: the op
    side simply drops out).  This is what lets grid-shaped code (halo
    exchanges over a :class:`~mpi4jax_tpu.parallel.proc.ProcGridComm`)
    run unchanged on OS-process worlds.
    """
    if _is_static_rank_int(spec):
        return check_rank_range(int(spec), role, comm.size)
    pairs = _resolve_pairs(spec, comm.size, role)
    me = int(comm.rank())
    if role == "dest":
        mine = [d for s, d in pairs if s == me]
    else:
        mine = [s for s, d in pairs if d == me]
    if not mine:
        return None
    if len(mine) > 1:
        raise ValueError(
            f"{role} pattern gives rank {me} {len(mine)} partners "
            f"({mine}); a p2p op takes exactly one — split the pattern "
            "into separate calls"
        )
    return mine[0]


def _rendezvous_send(x, dest, tag, comm, token):
    """Mesh send with a runtime destination: post the local shard to the
    host matching engine (ops/_rendezvous.py) via io_callback."""
    import jax
    from jax.experimental import io_callback

    from mpi4jax_tpu.ops._core import promote_vma
    from mpi4jax_tpu.ops._rendezvous import engine

    key = comm_key(comm)
    size = comm.size
    token, (x,) = fence_in(token, x)

    def post_cb(rank_v, dest_v, tag_v, payload, stamp):
        dest_i = int(dest_v)
        if not 0 <= dest_i < size:
            raise RuntimeError(
                f"rendezvous send: dest={dest_i} out of range for "
                f"communicator of size {size} (runtime-valued dest)"
            )
        tag_i = int(tag_v)
        if tag_i < 0:
            # a computed tag that lands on -1 would otherwise become the
            # ANY wildcard in the engine — silent mismatched delivery
            raise RuntimeError(
                f"rendezvous send: tag={tag_i} is negative (runtime-"
                "valued tags must be >= 0; wildcards are recv-only)"
            )
        engine().post(
            key, int(rank_v), dest_i, tag_i, np.asarray(payload).copy()
        )
        return np.asarray(stamp)

    # tag rides the operands, not the closure: a traced (runtime-valued)
    # tag is then just as legal as a runtime dest (ADVICE r3 — a closure
    # int(tag) on a tracer died with a generic concretization error)
    stamp = io_callback(
        post_cb,
        jax.ShapeDtypeStruct((), np.float32),
        comm.rank(), dest, jnp.int32(tag), x, token.stamp,
        ordered=False,
    )
    return token.with_stamp(promote_vma(stamp, comm.axes))


def _rendezvous_recv(x, source, tag, comm, token, status):
    """Mesh recv with runtime envelope matching: block in an
    io_callback until the engine has a message whose (source, tag)
    matches; Status reports the true runtime source."""
    import jax
    from jax.experimental import io_callback

    from mpi4jax_tpu.ops._core import promote_vma
    from mpi4jax_tpu.ops._rendezvous import engine

    key = comm_key(comm)
    if _is_runtime_rank(source):
        want = source
    else:
        # a static source reaches here either as the ANY_SOURCE wildcard
        # or as a specific rank paired with a traced tag (ADVICE r4) —
        # the engine matches both shapes at runtime
        want = jnp.int32(int(source))
    token, _ = fence_in(token)

    shape, dtype = tuple(x.shape), x.dtype

    tag_is_traced = _is_runtime_rank(tag)

    def take_cb(rank_v, want_v, tag_v, stamp):
        tag_i = int(tag_v)
        if tag_is_traced and tag_i < 0:
            # only the STATIC ANY_TAG constant may wildcard: a computed
            # traced tag that evaluates to -1 is a bug, not a wildcard
            raise RuntimeError(
                f"rendezvous recv on rank {int(rank_v)}: runtime-valued "
                f"tag={tag_i} is negative (pass the static ANY_TAG "
                "constant for a wildcard)"
            )
        payload, src, tg = engine().take(
            key, int(rank_v), int(want_v), tag_i
        )
        payload = np.asarray(payload)
        if payload.shape != shape or payload.dtype != np.dtype(dtype):
            raise RuntimeError(
                f"rendezvous recv on rank {int(rank_v)}: matched message "
                f"has shape/dtype {payload.shape}/{payload.dtype}, but "
                f"the recv template expects {shape}/{np.dtype(dtype)}"
            )
        return (
            payload,
            np.full(env, src, np.int32),
            np.full(env, tg, np.int32),
            np.reshape(stamp, env),
        )

    # The envelope scalars travel at the payload's rank.  On TPU a host
    # callback inside shard_map becomes one host receive per result, and
    # jax (0.9.0, callback.receive_from_host) annotates each of them with
    # the FIRST result's sharding: results of another rank are refused by
    # the verifier ("sharding doesn't match tensor rank").
    env = (1,) * len(shape)
    y, src, tg, stamp = io_callback(
        take_cb,
        (
            jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(env, np.int32),
            jax.ShapeDtypeStruct(env, np.int32),
            jax.ShapeDtypeStruct(env, np.float32),
        ),
        comm.rank(), want, jnp.int32(tag), token.stamp,
        ordered=False,
    )
    src, tg, stamp = (v.reshape(()) for v in (src, tg, stamp))
    y = promote_vma(y, comm.axes)
    token = token.with_stamp(promote_vma(stamp, comm.axes))
    if status is not None:
        # mesh-backend Status convention (class docstring): the fields
        # are per-device traced values — here the TRUE runtime envelope
        # as matched by the engine, not a trace-time reconstruction
        status.source = promote_vma(src, comm.axes)
        status.tag = promote_vma(tg, comm.axes)
    return y, token


@publishes_token
def send(x, dest, tag=0, *, comm=None, token=None):
    """Stage a send of ``x`` along the ``dest`` pattern; returns a token
    (reference: mpi4jax/_src/collective_ops/send.py:37-60 — returns token
    only, send.py:139-140).

    The payload rides the token until the matching :func:`recv` in the
    same trace consumes it.
    """
    comm = check_comm(comm)
    token = as_token(token)
    x = jnp.asarray(x)
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        tag = check_static_int(tag, "tag")
        dest = _proc_partner(dest, comm, "dest")
        if dest is None:
            return token  # no partner in the pattern (MPI_PROC_NULL)
        stamp = _proc.proc_send(x, token.stamp, comm, dest, tag)
        return token.with_stamp(stamp)
    if comm.backend == "mesh" and (
        _is_runtime_rank(dest)
        or (_is_runtime_rank(tag) and _is_static_rank_int(dest))
    ):
        # data-dependent destination (trace-time matching needs a static
        # pattern) or a traced tag on a single-rank dest (the matching
        # recv keys on the runtime tag value, so both sides must meet in
        # the engine; ADVICE r4) — route through the host rendezvous tier
        if not _is_runtime_rank(dest):
            dest = check_rank_range(dest, "dest", comm.size)
        return _rendezvous_send(x, dest, _check_tag(tag, True), comm, token)
    tag = _check_tag(tag, False)
    pairs = _resolve_pairs(dest, comm.size, "dest")
    _validate_perm(pairs, comm.size, "send dest")
    meta = PendingSendMeta(
        perm=tuple(sorted(pairs)),
        tag=tag,
        comm_key=comm_key(comm),
        shape=tuple(x.shape),
        dtype=str(x.dtype),
    )
    return token.push_send(x, meta)


@publishes_token
def recv(x, source=ANY_SOURCE, tag=ANY_TAG, *, comm=None, token=None, status=None):
    """Receive into the shape/dtype of template ``x`` (a template only —
    arrays are immutable; reference: mpi4jax/_src/collective_ops/
    recv.py:39-84, ANY defaults at recv.py:39-47).

    Matches the earliest staged :func:`send` on the token whose
    communicator, tag and pattern are compatible, and emits the fused
    ``ppermute``.
    """
    comm = check_comm(comm)
    token = as_token(token)
    x = jnp.asarray(x)
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        tag = check_static_int(tag, "tag")
        if _is_static_rank_int(source) and int(source) == ANY_SOURCE:
            source = ANY_SOURCE
        else:
            source = _proc_partner(source, comm, "source")
        if source is None:
            # no inbound message in the pattern: keep the recv buffer
            # (MPI_PROC_NULL semantics, matching the mesh merge path)
            if status is not None:
                status.source, status.tag = -1, -1
            return x, token
        y, stamp, st = _proc.proc_recv(x, token.stamp, comm, source, tag)
        if status is not None:
            _deliver_status(status, st)
        return y, token.with_stamp(stamp)
    source_is_any = (
        isinstance(source, (int, np.integer)) and int(source) == ANY_SOURCE
    )
    if comm.backend == "mesh" and (
        _is_runtime_rank(source)
        or (_is_runtime_rank(tag) and _is_static_rank_int(source))
    ):
        # runtime-valued source (no static pattern to match against) or
        # a traced tag (trace-time matching cannot key on it; the engine
        # matches any static-int or wildcard source at runtime, ADVICE
        # r4): match at execution time in the host engine
        if not _is_runtime_rank(source) and not source_is_any:
            source = check_rank_range(source, "source", comm.size)
        return _rendezvous_recv(
            x, source, _check_tag(tag, True), comm, token, status
        )
    tag = _check_tag(tag, False)
    want_pairs = None
    if not source_is_any:
        want_pairs = frozenset(
            _validate_perm(
                _resolve_pairs(source, comm.size, "source"), comm.size, "recv source"
            )
        )

    key = comm_key(comm)
    for i, meta in enumerate(token.pending_meta):
        if meta.comm_key != key:
            continue
        if tag != ANY_TAG and meta.tag != tag:
            continue
        if want_pairs is not None and frozenset(meta.perm) != want_pairs:
            continue
        if meta.shape != tuple(x.shape) or meta.dtype != str(x.dtype):
            raise ValueError(
                f"recv template shape/dtype {x.shape}/{x.dtype} does not "
                f"match staged send {meta.shape}/{meta.dtype}"
            )
        payload, meta, token = token.pop_send(i)
        pairs = list(meta.perm)
        if comm.backend == "self":
            token, (y,) = fence_out(token, payload)
        elif comm.backend == "mesh":
            token, (payload,) = fence_in(token, payload)
            y = _ppermute(payload, comm.axes, comm.expand_perm(pairs))
            y = _recv_merge(y, x, pairs, comm)
            token, (y,) = fence_out(token, y)
        else:
            raise NotImplementedError(
                f"recv not implemented for backend {comm.backend!r}"
            )
        if status is not None:
            if comm.backend == "self":
                status.source, status.tag = 0, meta.tag
            else:
                status.source = _static_source_of(pairs, comm)
                status.tag = meta.tag
        return y, token

    if comm.backend == "mesh" and source_is_any:
        # wildcard recv with no trace-time match: the message must be
        # coming from a runtime-routed send — match it at execution
        # time through the host engine (reference recv.py:39-47
        # semantics; Status reports the true runtime source)
        return _rendezvous_recv(x, source, tag, comm, token, status)
    staged = "; ".join(
        f"tag={meta.tag} perm={meta.perm} "
        f"{meta.dtype}[{'x'.join(map(str, meta.shape))}]"
        + ("" if meta.comm_key == key else " (different comm)")
        for meta in token.pending_meta
    )
    wanted = (
        f"tag={'ANY' if tag == ANY_TAG else tag}, source="
        f"{'ANY' if want_pairs is None else sorted(want_pairs)}"
    )
    raise RuntimeError(
        "recv found no matching in-trace send on this token. This recv "
        f"wants {wanted}; the token carries "
        + (f"staged send(s) [{staged}]" if staged else "no staged sends")
        + ". Under SPMD, "
        "send and recv must be paired within the same trace (the send "
        "stages its payload on the token; pass that token to recv). For "
        "true cross-process MPMD p2p use the multi-process backend."
    )


@publishes_token
def sendrecv(
    sendbuf,
    recvbuf,
    source,
    dest,
    sendtag=0,
    recvtag=ANY_TAG,
    *,
    comm=None,
    token=None,
    status=None,
):
    """Combined send+receive (reference: mpi4jax/_src/collective_ops/
    sendrecv.py:41-103).

    ``dest`` gives where each rank's ``sendbuf`` goes, ``source`` where
    its ``recvbuf`` comes from; the two views must describe the same
    global permutation.  Lowers to one ``lax.ppermute``; transposition
    reverses the permutation (reference transpose rule:
    sendrecv.py:366-385).
    """
    comm = check_comm(comm)
    token = as_token(token)
    check_static_int(sendtag, "sendtag")
    check_static_int(recvtag, "recvtag")
    sendbuf = jnp.asarray(sendbuf)
    recvbuf = jnp.asarray(recvbuf)
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        source = _proc_partner(source, comm, "source")
        dest = _proc_partner(dest, comm, "dest")
        if source is None and dest is None:
            if status is not None:
                status.source, status.tag = -1, -1
            return recvbuf, token
        if source is None:
            # send-only edge of a non-periodic pattern: the recv buffer
            # is returned unchanged (MPI_PROC_NULL recv side)
            stamp = _proc.proc_send(
                sendbuf, token.stamp, comm, dest, sendtag
            )
            if status is not None:
                status.source, status.tag = -1, -1
            return recvbuf, token.with_stamp(stamp)
        if dest is None:
            y, stamp, st = _proc.proc_recv(
                recvbuf, token.stamp, comm, source, recvtag
            )
            if status is not None:
                _deliver_status(status, st)
            return y, token.with_stamp(stamp)
        y, stamp, st = _proc.proc_sendrecv(
            sendbuf, recvbuf, token.stamp, comm, source, dest, sendtag,
            recvtag,
        )
        if status is not None:
            _deliver_status(status, st)
        return y, token.with_stamp(stamp)
    if comm.backend == "self":
        token, (y,) = fence_out(token, sendbuf)
        if status is not None:
            status.source, status.tag = 0, sendtag
        return y, token
    if comm.backend == "mesh":
        if tuple(sendbuf.shape) != tuple(recvbuf.shape) or sendbuf.dtype != recvbuf.dtype:
            raise ValueError(
                "mesh-backend sendrecv requires uniform send/recv "
                f"shapes and dtypes, got {sendbuf.shape}/{sendbuf.dtype} vs "
                f"{recvbuf.shape}/{recvbuf.dtype}"
            )
        dpairs = _validate_perm(
            _resolve_pairs(dest, comm.size, "dest"), comm.size, "sendrecv dest"
        )
        source_is_any = isinstance(source, (int, np.integer)) and int(source) == ANY_SOURCE
        if not source_is_any:
            spairs = _resolve_pairs(source, comm.size, "source")
            if frozenset(spairs) != frozenset(dpairs):
                raise ValueError(
                    "sendrecv source and dest views disagree: "
                    f"dest implies {sorted(dpairs)}, source implies "
                    f"{sorted(spairs)}. They must describe one global "
                    "permutation."
                )
        pairs_global = comm.expand_perm(dpairs)
        if all(s == d for s, d in pairs_global):
            # pure self-exchange (periodic wrap on a size-1 mesh axis):
            # no data crosses devices, so there is no cross-device
            # ordering to enforce — skip the token fences entirely.
            # This lets XLA fuse across the op: on a single chip the
            # whole solver step becomes a handful of fusions instead of
            # being cut at every (elided) exchange.
            y = _recv_merge(_ppermute(sendbuf, comm.axes, pairs_global),
                            recvbuf, dpairs, comm)
        else:
            token, (payload,) = fence_in(token, sendbuf)
            y = _ppermute(payload, comm.axes, pairs_global)
            y = _recv_merge(y, recvbuf, dpairs, comm)
            token, (y,) = fence_out(token, y)
        if status is not None:
            status.source = _static_source_of(dpairs, comm)
            status.tag = sendtag
        return y, token
    raise NotImplementedError(
        f"sendrecv not implemented for backend {comm.backend!r}"
    )


@publishes_token
def sendrecv_multi(
    sendbufs,
    recvbufs,
    source,
    dest,
    sendtag=0,
    recvtag=ANY_TAG,
    *,
    comm=None,
    token=None,
    status=None,
    coalesce=None,
):
    """Exchange several same-pattern messages at once — the coalescing
    entry point (docs/performance.md "small-message coalescing").

    Semantically identical to one :func:`sendrecv` per
    ``(sendbufs[i], recvbufs[i])`` pair along the same
    ``source``/``dest`` pattern (bit-identical results), but on the
    multi-process backend a small run travels as ONE fused wire frame
    — a single header + gathered payloads — instead of one frame per
    part.  Fusion applies when the combined payload is at or below
    ``T4J_COALESCE_BYTES`` (autotuner-calibrated; both sides derive
    the decision from the same knob).  ``coalesce=True``/``False``
    forces a side (benchmark plumbing); ``T4J_COALESCE_BYTES=0``
    restores the exact per-part wire behaviour.

    ``sendbufs`` and ``recvbufs`` are independent lists (they usually
    pair up, as in a halo exchange).  Returns ``(outs, token)`` with
    ``outs`` shaped like ``recvbufs``; ranks without an inbound
    partner in the pattern keep their recv buffers (MPI_PROC_NULL).
    """
    comm = check_comm(comm)
    token = as_token(token)
    check_static_int(sendtag, "sendtag")
    check_static_int(recvtag, "recvtag")
    sendbufs = [jnp.asarray(b) for b in sendbufs]
    recvbufs = [jnp.asarray(b) for b in recvbufs]
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        my_src = _proc_partner(source, comm, "source")
        my_dst = _proc_partner(dest, comm, "dest")
        sends = sendbufs if my_dst is not None else []
        recvs = recvbufs if my_src is not None else []
        if not sends and not recvs:
            if status is not None:
                status.source, status.tag = -1, -1
            return list(recvbufs), token

        # Fusion is decided PER WIRE DIRECTION: my send decision must
        # match my receiver's expectation, and it does because both
        # compute eligibility from the same part shapes (the program is
        # uniform across ranks) and the same T4J_COALESCE_BYTES — an
        # edge rank with only one side still agrees with its interior
        # peer about that one direction.
        def _eligible(bufs):
            if isinstance(coalesce, bool):
                return coalesce and len(bufs) >= 1
            from mpi4jax_tpu import tuning

            total = sum(int(b.size) * b.dtype.itemsize for b in bufs)
            return tuning.coalesce_eligible(total, len(bufs))

        fuse_send = bool(sends) and _eligible(sendbufs)
        fuse_recv = bool(recvs) and _eligible(recvbufs)
        outs = None
        st = None
        if fuse_send and fuse_recv:
            out = _proc.proc_sendrecv_fused(
                sends, recvs, token.stamp, comm, my_src, my_dst,
                sendtag, recvtag,
            )
            outs = list(out[:len(recvs)])
            token = token.with_stamp(out[len(recvs)])
            st = out[len(recvs) + 1]
        else:
            if fuse_send:
                out = _proc.proc_sendrecv_fused(
                    sends, [], token.stamp, comm, -1, my_dst, sendtag,
                    recvtag,
                )
                token = token.with_stamp(out[0])
            elif sends:
                # unfused: the exact pre-coalescing wire behaviour, one
                # frame per part (eager sends first — cannot deadlock)
                for sb in sends:
                    token = send(sb, my_dst, sendtag, comm=comm,
                                 token=token)
            if fuse_recv:
                out = _proc.proc_sendrecv_fused(
                    [], recvs, token.stamp, comm, my_src, -1, sendtag,
                    recvtag,
                )
                outs = list(out[:len(recvs)])
                token = token.with_stamp(out[len(recvs)])
                st = out[len(recvs) + 1]
            elif recvs:
                outs = []
                for rb in recvs:
                    y, token = recv(
                        rb, my_src, recvtag, comm=comm, token=token,
                        status=status,
                    )
                    outs.append(y)
        if status is not None:
            if st is not None:
                _deliver_status(status, st)
            elif not recvs:
                status.source, status.tag = -1, -1
        return (outs if recvs else list(recvbufs)), token
    if comm.backend == "self":
        outs = []
        for sb, rb in zip(sendbufs, recvbufs):
            y, token = sendrecv(
                sb, rb, source, dest, sendtag, recvtag, comm=comm,
                token=token, status=status,
            )
            outs.append(y)
        return outs, token
    if comm.backend == "mesh":
        # one ppermute per part; fusion is a wire-tier concept (the ICI
        # tier has no frame overhead to amortise — batching there is
        # the caller's jnp.stack, see halo_exchange_2d_batch)
        outs = []
        for sb, rb in zip(sendbufs, recvbufs):
            y, token = sendrecv(
                sb, rb, source, dest, sendtag, recvtag, comm=comm,
                token=token, status=status,
            )
            outs.append(y)
        return outs, token
    raise NotImplementedError(
        f"sendrecv_multi not implemented for backend {comm.backend!r}"
    )
