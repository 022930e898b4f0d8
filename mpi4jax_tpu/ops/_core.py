"""Token plumbing and shared machinery for the communication ops.

The reference threads an XLA token through every op and marks lowerings
``has_side_effect=True`` so XLA cannot reorder or DCE communication
(mpi4jax/_src/collective_ops/allreduce.py:58-66, _src/jax_compat.py:24-50;
token misuse declared UB in docs/sharp-bits.rst:6-34).  On TPU the same
ordering contract is expressed through *data dependence*: a
:class:`Token` carries a scalar "stamp" array, and every op is fenced with
``lax.optimization_barrier`` so its collective depends on the incoming
stamp and the outgoing stamp depends on the collective's result.  Under
SPMD, XLA schedules collectives in a program order consistent across all
devices, so a connected token chain is sufficient to rule out cross-device
mismatches and deadlocks.

The token additionally carries the *pending-send queue*: in SPMD there is
no per-rank control flow, so a ``send`` stages its payload on the token at
trace time and the matching ``recv`` consumes it, emitting a single fused
``ppermute`` (see :mod:`mpi4jax_tpu.ops.p2p`).  This materialises MPI's
eager-send/matching-recv semantics at trace time instead of at runtime.
"""

import threading
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_derivatives import SymbolicZero
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir
from jax.tree_util import register_pytree_node

__all__ = [
    "Token",
    "create_token",
    "as_token",
    "token_array",
    "ANY_SOURCE",
    "ANY_TAG",
]

ANY_SOURCE = -1
ANY_TAG = -1

# Every public op runs inside jax.named_scope(SCOPE_PREFIX + <op>): see
# publishes_token.
SCOPE_PREFIX = "mpi4jax_tpu."


@dataclass(frozen=True)
class PendingSendMeta:
    """Static descriptor of a staged send (aux data of the Token pytree)."""

    perm: tuple  # tuple of (source_rank, dest_rank) pairs
    tag: int
    comm_key: tuple  # (backend, axes/context) identifying the communicator
    shape: tuple
    dtype: str


class Token:
    """Opaque ordering token returned by every communication op.

    A pytree whose children are the ordering stamp plus any staged
    (pending) send payloads; the matching metadata is static aux data.
    """

    def __init__(self, stamp=None, pending=(), pending_meta=()):
        if stamp is None:
            stamp = jnp.zeros((), jnp.float32)
        self.stamp = stamp
        self.pending = tuple(pending)
        self.pending_meta = tuple(pending_meta)
        if len(self.pending) != len(self.pending_meta):
            raise ValueError("pending payloads and metadata out of sync")

    def push_send(self, payload, meta):
        return Token(
            self.stamp,
            self.pending + (payload,),
            self.pending_meta + (meta,),
        )

    def pop_send(self, index):
        """Remove pending send ``index``; returns (payload, meta, token)."""
        payload = self.pending[index]
        meta = self.pending_meta[index]
        tok = Token(
            self.stamp,
            self.pending[:index] + self.pending[index + 1 :],
            self.pending_meta[:index] + self.pending_meta[index + 1 :],
        )
        return payload, meta, tok

    def with_stamp(self, stamp):
        return Token(stamp, self.pending, self.pending_meta)

    def assert_drained(self):
        """Raise if sends were staged but never matched by a recv."""
        if self.pending:
            descs = [f"tag={m.tag} perm={m.perm}" for m in self.pending_meta]
            raise RuntimeError(
                "token still carries unmatched send(s): "
                + "; ".join(descs)
                + ". Every mpi4jax_tpu.send must be paired with a recv in "
                "the same trace (SPMD programs are uniform across devices)."
            )
        return self

    def __repr__(self):
        return f"Token(pending={len(self.pending)})"


def _token_flatten(tok):
    return (tok.stamp, *tok.pending), tok.pending_meta


def _token_unflatten(meta, children):
    return Token(children[0], children[1:], meta)


register_pytree_node(Token, _token_flatten, _token_unflatten)


def create_token(arg=None):
    """Create a fresh communication token.

    ``arg`` is accepted (and ignored) for call-compatibility with
    ``jax.lax.create_token`` / the reference examples.
    """
    del arg
    return Token()


def as_token(token):
    """Coerce user-supplied token values (None / array / Token) to a Token.

    Under :func:`mpi4jax_tpu.experimental.auto_tokenize`, ``token=None``
    resolves to the ambient token instead of a fresh one, so consecutive
    ops chain automatically (the reference's auto-token-threading
    transform, mpi4jax/experimental/tokenizer.py:108-164, reimagined as
    an ambient context rather than a jaxpr interpreter).
    """
    if token is None:
        stack = _ambient_stack()
        if stack:
            return stack[-1].resolve()
        return Token()
    if isinstance(token, Token):
        return token
    from jax._src import core as _jcore

    if isinstance(token, getattr(_jcore, "Token", ())) or isinstance(
        getattr(token, "aval", None), getattr(_jcore, "AbstractToken", ())
    ):
        # jax.lax.create_token() value (concrete or traced — the
        # reference's idiom, shallow_water.py:165 there): an opaque
        # ordering token with no data — ordering here rides this
        # library's own stamp chain
        return Token()
    if isinstance(token, jax.Array) or hasattr(token, "dtype"):
        return Token(jnp.asarray(token, jnp.float32).reshape(()) * 0)
    raise TypeError(f"cannot interpret {type(token)} as a communication token")


# -- ambient-token context (backing store for experimental.auto_tokenize) --

_ambient = threading.local()


def _ambient_stack():
    stack = getattr(_ambient, "stack", None)
    if stack is None:
        stack = _ambient.stack = []
    return stack


def _current_trace():
    from jax._src import core as _jcore

    return _jcore.trace_ctx.trace


def _is_ancestor(trace, current):
    """True iff ``trace`` is ``current`` or on its parent chain."""
    t = current
    while t is not None:
        if t is trace:
            return True
        t = getattr(t, "parent_trace", None)
    return False


def _pending_multiset(tok):
    """Multiset {(payload identity, meta): count} of a token's pendings."""
    counts = {}
    for p, meta in zip(tok.pending, tok.pending_meta):
        key = (id(p), meta)
        counts[key] = counts.get(key, 0) + 1
    return counts


class AmbientChain:
    """Per-auto_tokenize-scope token chain, stratified by JAX trace.

    Tokens committed inside an inner trace (a scan/while body, a cond
    branch, a nested jit) are only valid while that trace is live; using
    them afterwards leaks a tracer.  Each committed token is therefore
    recorded with the trace it was created under, and lookups discard
    levels whose trace is not an ancestor of the current one — exiting a
    control-flow body transparently resumes the chain from the enclosing
    trace's token.  (The reference instead rewrites control-flow
    sub-jaxprs to carry the token through — tokenizer.py:19-105; the
    stratification here gives the same user-visible chaining without a
    jaxpr interpreter.)
    """

    def __init__(self):
        self.levels = []  # [(trace, token)], outermost first

    def _prune(self):
        """Drop levels whose trace has exited, auditing their pending
        sends: entries also tracked at the surviving outer level are fine
        (consumption is propagated by ``commit``), live payloads staged
        in the dead trace are hoisted out, and dead-trace payloads that
        were never matched raise — they can never be delivered."""
        cur = _current_trace()
        while self.levels and not _is_ancestor(self.levels[-1][0], cur):
            tr, tok = self.levels.pop()
            if not tok.pending:
                continue
            parent_tok = self.levels[-1][1] if self.levels else Token()
            parent_keys = _pending_multiset(parent_tok)
            for p, meta in zip(tok.pending, tok.pending_meta):
                key = (id(p), meta)
                if parent_keys.get(key, 0) > 0:
                    parent_keys[key] -= 1
                    continue  # outer level still tracks this send
                if isinstance(p, jax.core.Tracer) and _is_ancestor(tr, p._trace):
                    raise RuntimeError(
                        "a send staged inside a control-flow body / nested "
                        f"jit (tag={meta.tag}, perm={meta.perm}) was never "
                        "matched by a recv before its trace exited; it can "
                        "no longer be delivered. Pair every send with a "
                        "recv inside the same control-flow scope."
                    )
                # payload from an enclosing trace, staged while tracing
                # the inner scope: still deliverable — hoist it out
                parent_tok = parent_tok.push_send(p, meta)
            if self.levels:
                self.levels[-1] = (self.levels[-1][0], parent_tok)
            elif parent_tok.pending:
                self.levels.append((cur, parent_tok))
        return cur

    def resolve(self):
        cur = self._prune()
        if not self.levels:
            self.levels.append((cur, Token()))
        return self.levels[-1][1]

    def commit(self, token):
        cur = self._prune()
        if self.levels and self.levels[-1][0] is cur:
            self.levels[-1] = (cur, token)
        else:
            self.levels.append((cur, token))
        # Propagate consumption: a pending entry an ancestor level tracks
        # that is gone from the committed token was matched by a recv in
        # this (deeper) trace — drop it from the ancestor too, or it would
        # be delivered twice when the inner trace exits.
        kept = _pending_multiset(token)
        for i in range(len(self.levels) - 1):
            tr, tok = self.levels[i]
            if not tok.pending:
                continue
            avail = dict(kept)
            new_p, new_m = [], []
            for p, meta in zip(tok.pending, tok.pending_meta):
                key = (id(p), meta)
                if avail.get(key, 0) > 0:
                    avail[key] -= 1
                    new_p.append(p)
                    new_m.append(meta)
            if len(new_p) != len(tok.pending):
                self.levels[i] = (tr, Token(tok.stamp, new_p, new_m))


def commit_token(token):
    """Publish an op's output token to the ambient chain (no-op when no
    auto_tokenize scope is active)."""
    stack = _ambient_stack()
    if stack:
        stack[-1].commit(token)
    return token


# ops whose debug log uses the reference's MPI_<Op> wire name
_LOGGED_OPS = {
    "allgather", "allreduce", "alltoall", "barrier", "bcast", "gather",
    "recv", "reduce", "scan", "scatter", "send", "sendrecv",
}

_ALNUM = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
)


def _first_array(tree):
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and hasattr(leaf, "size"):
            return leaf
    return None


def _rid_str(code):
    """8-char call id from a 32-bit code (the reference uses 8 random
    alphanumerics, mpi_xla_bridge.pyx:47-52)."""
    chars = []
    code = int(code) & 0xFFFFFFFF
    for _ in range(8):
        code, r = divmod(code, len(_ALNUM))
        chars.append(_ALNUM[r])
    return "".join(chars)


# per-execution timers, keyed by the execution-unique (rank, call id):
# concurrent executions of one call site cannot collide on the key
_debug_timers = {}
_debug_timers_mu = threading.Lock()


def _debug_emit(line):
    """One atomic line to stdout: concurrent executions emit from
    multiple callback threads, and ``print`` writes text and newline
    separately — torn lines would corrupt the debug-log wire format the
    observability tests (and any log parser) key on."""
    import sys

    with _debug_timers_mu:
        sys.stdout.write(line + "\n")
        sys.stdout.flush()


def _scalar(v):
    """First element of a possibly-batched callback operand (vmap may
    hand the callback a stacked value; the id is replicated)."""
    return int(np.ravel(np.asarray(v))[0])


def _debug_begin(name, args, kwargs, comm):
    """Stage the reference-format begin line and start the call timer.

    Wire format follows the reference's bridge logging exactly
    (mpi_xla_bridge.pyx:47-60): ``r{rank} | {8-char random id} |
    MPI_<Op> with {n} items`` at execution time, then a matching
    ``MPI_<Op> done with code 0 (1.23e-04s)`` line from
    :func:`_debug_end`.  Toggled by MPI4JAX_TPU_DEBUG /
    utils.config.set_debug; zero cost when disabled (nothing is staged
    at trace time).

    Structure (three callbacks per op, for transform-safety AND
    execution-unique pairing):

    * a ``pure_callback`` whose only operands are the rank and a
      trace-time nonce generates the per-execution id and a fallback
      start time.  Keeping user data out of its operands keeps it out
      of reach of JVP/vmap traces — ``pure_callback`` supports neither
      (the reference suite runs grad/vmap tests with logging enabled).
    * the begin/done lines print from ``jax.debug.callback`` (which is
      transform-proof by design), data-dependent on the op's
      operands/results for best-effort placement, carrying the id.
    * timers pair begin→done through :data:`_debug_timers` keyed by the
      unique id, so concurrent executions of one call site cannot
      mispair (the done callback falls back to the generated start time
      if it somehow runs before its begin — callbacks are unordered).
    """
    import random
    import time

    arr = _first_array((args, kwargs))
    nitems = int(arr.size) if arr is not None else 0
    opname = "MPI_" + name.capitalize()
    try:
        rank = comm.rank()
    except Exception:
        rank = -1

    def gen_cb(rank_val, _nonce):
        hi, lo = divmod(time.perf_counter_ns(), 1 << 31)
        return (
            np.uint32(random.getrandbits(32)),
            np.int32(hi),
            np.int32(lo),
        )

    # trace-time nonce: makes each call site's generator unique so XLA
    # can't CSE two otherwise-identical callbacks into one id
    nonce = jnp.uint32(random.getrandbits(32))
    rid, t_hi, t_lo = jax.pure_callback(
        gen_cb,
        (
            jax.ShapeDtypeStruct((), np.uint32),
            jax.ShapeDtypeStruct((), np.int32),
            jax.ShapeDtypeStruct((), np.int32),
        ),
        jnp.asarray(rank),
        nonce,
    )

    def begin_cb(rank_val, rid_val, *_deps):
        r, i = _scalar(rank_val), _scalar(rid_val)
        with _debug_timers_mu:
            # bound the dict: entries orphan when a done callback ran
            # before its begin (unordered callbacks) or an execution
            # aborted between the two; evict oldest-inserted first
            while len(_debug_timers) >= 4096:
                _debug_timers.pop(next(iter(_debug_timers)))
            _debug_timers[(r, i)] = time.perf_counter_ns()
        _debug_emit(f"r{r} | {_rid_str(i)} | {opname} with {nitems} items")

    deps = (arr,) if arr is not None else ()
    jax.debug.callback(begin_cb, jnp.asarray(rank), rid, *deps)
    return {"opname": opname, "rank": rank, "carry": (rid, t_hi, t_lo)}


def _debug_end(state, out):
    import time

    opname = state["opname"]

    def end_cb(rank_val, rid, t_hi, t_lo, *_deps):
        r, i = _scalar(rank_val), _scalar(rid)
        with _debug_timers_mu:
            t0_ns = _debug_timers.pop(
                (r, i), (_scalar(t_hi) << 31) + _scalar(t_lo)
            )
        dt = (time.perf_counter_ns() - t0_ns) / 1e9
        _debug_emit(
            f"r{r} | {_rid_str(i)} | {opname} done with code 0 ({dt:.2e}s)"
        )

    arr = _first_array(out)
    deps = (arr,) if arr is not None else ()
    jax.debug.callback(
        end_cb, jnp.asarray(state["rank"]), *state["carry"], *deps
    )


def _tel_nbytes(args, kwargs):
    arr = _first_array((args, kwargs))
    if arr is None:
        return 0
    try:
        return int(arr.size) * np.dtype(arr.dtype).itemsize
    except Exception:
        return 0


def publishes_token(fn):
    """Instrumentation wrapper for every public op: profiler scope,
    opt-in per-call debug logging, opt-in telemetry bracketing
    (T4J_TELEMETRY=trace — the Python-level begin/end events that
    enclose the native segment events on the merged timeline,
    docs/observability.md), publication of the returned Token (if any)
    to the ambient auto_tokenize chain, and — while a ``verify_comm``
    extraction is active — reporting the call to the contract analyzer
    (analysis/record.py).

    The ``jax.named_scope`` below is load-bearing for the analyzer too:
    it stamps every lowered eqn's name stack with ``mpi4jax_tpu.<op>``
    (``SCOPE_PREFIX`` + the op's name), which is how the jaxpr walker
    (analysis/jaxpr_walk.py) identifies communication eqns inside
    control-flow sub-jaxprs regardless of backend.  The same scope
    reaches the compiled program (an instruction's ``op_name``) and the
    device profile (an event's ``tf_op``), and is what the benchmark's
    per-layer metrics read to give device time to the op that emitted
    it (perfbench/harness/scopes.py).  A composite op names its phases
    with nested scopes that do not start with the prefix
    (parallel/halo.py: ``pack``, ``wire``, ``unpack``).
    """
    import contextlib
    import functools

    name = fn.__name__
    scope = SCOPE_PREFIX + name

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from mpi4jax_tpu.utils import config

        log_state = None
        if config.debug_enabled() and name in _LOGGED_OPS:
            from mpi4jax_tpu.utils.validation import check_comm

            log_state = _debug_begin(
                name, args, kwargs, check_comm(kwargs.get("comm"))
            )
        # Python-level op bracket: at execution time for eager/proc
        # calls (the MPMD idiom), at trace time under jit — the staged
        # tier additionally brackets its runtime callbacks
        # (ops/_proc.py), which is where in-jit wall time is spent.
        # EVERY wrapped op is bracketed (reduce_scatter, the halo and
        # attention composites included) — _LOGGED_OPS is the debug
        # log's MPI_<Op> wire-name set, a different concern.
        tel_scope = contextlib.nullcontext()
        from mpi4jax_tpu.telemetry import recorder as _telrec

        if _telrec.tracing():
            tel_scope = _telrec.py_op(name, _tel_nbytes(args, kwargs))
        from mpi4jax_tpu.analysis import record as _arecord

        with tel_scope:
            if _arecord.active():
                with _arecord.op_frame():
                    with jax.named_scope(scope):
                        out = fn(*args, **kwargs)
                    _arecord.record_op(name, fn, args, kwargs, out)
            else:
                with jax.named_scope(scope):
                    out = fn(*args, **kwargs)
        token = None
        if isinstance(out, Token):
            token = out
        elif isinstance(out, tuple):
            for item in out:
                if isinstance(item, Token):
                    token = item
                    break
        if token is not None:
            commit_token(token)
        if log_state is not None:
            _debug_end(log_state, out)
        return out

    return wrapper


def token_array(token):
    """The raw stamp array (for interop with array-token code)."""
    return as_token(token).stamp


def fence_in(token, *arrays):
    """Make ``arrays`` depend on the token's stamp (pre-collective fence)."""
    from mpi4jax_tpu.utils import config

    if not config.fences_enabled():
        return token, arrays
    out = lax.optimization_barrier((token.stamp, *arrays))
    return token.with_stamp(out[0]), out[1:]


def fence_out(token, *arrays):
    """Make the token's stamp depend on ``arrays`` (post-collective fence)."""
    from mpi4jax_tpu.utils import config

    if not config.fences_enabled():
        return token, arrays
    out = lax.optimization_barrier((token.stamp, *arrays))
    return token.with_stamp(out[0]), out[1:]


def vma_of(x):
    """``x``'s varying-manual-axes tuple, or ``None`` when the aval has
    no vma typing at all (older JAX) — callers treating None as "no
    axes" should use ``vma_of(x) or ()``."""
    import jax

    try:
        return tuple(jax.typeof(x).vma)
    except AttributeError:
        return None


def union_vma_struct(shape, dtype, *arrays):
    """``ShapeDtypeStruct`` carrying the union of ``arrays``' varying
    manual axes (required by shard_map's vma checking for pallas_call
    outputs); plain struct on JAX builds without vma typing."""
    import jax

    vmas = [vma_of(a) for a in arrays]
    if all(v is None for v in vmas):
        return jax.ShapeDtypeStruct(shape, dtype)
    axes = frozenset().union(*(v or () for v in vmas))
    return jax.ShapeDtypeStruct(shape, dtype, vma=axes)


def promote_vma(x, axes):
    """Promote ``x`` to be device-varying over all of ``axes``.

    JAX's collectives require a uniform varying-state across the named
    axes; values derived from only one mesh axis (e.g. a y-coordinate
    field on a ("y","x") comm) must be explicitly ``pvary``-ed before a
    multi-axis collective.  No-op outside shard_map and for already-
    varying values.
    """
    vma = vma_of(x)
    if vma is None:
        return x
    missing = tuple(a for a in axes if a not in vma)
    if missing:
        if hasattr(lax, "pcast"):
            x = lax.pcast(x, missing, to="varying")
        else:
            x = lax.pvary(x, missing)
    return x


def both_modes(forward, keep, tangent, transpose):
    """``forward`` (operands -> results) as a function with a derivative
    in both modes that is written out: ``jax.jvp``, ``jax.linearize``
    and ``jax.jacfwd`` through it run ``tangent(kept, *tangents)``, and
    ``jax.vjp`` and ``jax.grad`` run ``transpose(kept, cotangents)``,
    which returns the cotangents of the operands, in their structure;
    ``kept = keep(*operands)`` is all that is held for either.  A call
    that is not differentiated runs ``forward`` and nothing else.

    ``forward`` runs on the operands with their derivatives stopped, as
    the caller's own lines (nothing wraps it: an instruction of it keeps
    its scopes and its source line in a compiled text), and its results
    go through a ``jax.custom_jvp`` that hands them on untouched and
    takes the operands beside them for its rule.  (``forward`` inside
    the ``custom_jvp`` is the same program with every instruction named
    for the line that calls it: jax 0.9 lowers a ``custom_jvp_call``
    once, out of line, and inlines it at the call's location.)  The rule
    binds the tangents to :data:`linear_p`, a linear map that carries
    its transpose: reverse mode, which linearises the rule and
    transposes what it finds, finds that primitive and runs
    ``transpose``, not the transposes of ``tangent``'s parts (which is
    all a ``jax.custom_vjp`` is for, and a ``custom_vjp`` has no forward
    mode).  Neither function is traced before a program that runs it is
    lowered: a reverse-mode program never traces ``tangent``
    (``jax.custom_derivatives.linear_call``, the same idea, traces its
    linear function where it is called: 1.5 s more of every set-up of
    the gradient's cell, ``PERF.md`` PR 59).  So neither may close over
    a traced value: what they read of the operands comes through
    ``kept``.  Operands whose tangents are symbolic zeros (a token made
    inside the differentiated function) are no operands of the linear
    map, which in a transposition takes unknowns alone: ``tangent`` is
    handed zeros there and their cotangents are dropped.  What stays
    open is forward over reverse: the map's own JVP asks for tangents of
    ``kept``, which ``transpose`` (an adjoint kernel, say) does not
    give."""
    @jax.custom_jvp
    def derived(out, *operands):
        del operands
        return out

    def rule(primals, tangents):
        out, *primals = primals
        leaves, tree = jax.tree.flatten(
            tuple(tangents[1:]), is_leaf=lambda t: isinstance(t, SymbolicZero))
        live = [not isinstance(t, SymbolicZero) for t in leaves]
        like = [(x.shape, x.dtype, vma_of(x) or ())
                for x in jax.tree.leaves(primals)]
        kept, kept_tree = jax.tree.flatten(keep(*primals))
        held = len(kept)
        out_tree = jax.tree.structure(out)

        def pushed(*flat):
            some = iter(flat[held:])
            whole = tree.unflatten([
                next(some) if is_live
                else promote_vma(jnp.zeros(shape, dtype), vma)
                for is_live, (shape, dtype, vma) in zip(live, like)])
            return jax.tree.leaves(
                tangent(kept_tree.unflatten(flat[:held]), *whole))

        def pulled(*flat):
            back = jax.tree.leaves(transpose(
                kept_tree.unflatten(flat[:held]), out_tree.unflatten(flat[held:])))
            if len(back) != len(live):
                raise TypeError(
                    f"a transpose that returns {len(back)} cotangents for "
                    f"{len(live)} operands")
            return [ct for ct, is_live in zip(back, live) if is_live]

        pushed_out = linear_p.bind(
            *kept, *(t for t, is_live in zip(leaves, live) if is_live),
            fun=pushed, transpose=pulled, held=held,
            out_avals=tuple(jax.typeof(x) for x in jax.tree.leaves(out)))
        return out, out_tree.unflatten(pushed_out)

    derived.defjvp(rule, symbolic_zeros=True)

    def both(*operands):
        return derived(
            forward(*jax.tree.map(lax.stop_gradient, operands)), *operands)

    return both


# A linear map with its transpose beside it: ``bind(*kept, *linear,
# fun=, transpose=, held=, out_avals=)`` is ``fun(*kept, *linear)``,
# linear in ``linear`` (the operands after the first ``held``), and its
# transposition is the same primitive with the two functions' places
# changed, on the cotangents.  Both are flat functions of arrays, traced
# where a program that holds the primitive is lowered and not before.
linear_p = Primitive("mpi4jax_tpu_linear")
linear_p.multiple_results = True


def _linear_impl(*args, fun, transpose, held, out_avals):
    del transpose, held, out_avals
    return fun(*args)


def _linear_jvp(primals, tangents, **how):
    held = how["held"]
    if any(type(t) is not ad.Zero for t in tangents[:held]):
        raise NotImplementedError(
            "forward over reverse through a derivative that is written out "
            "(ops/_core.py both_modes): what it keeps has a tangent, and its "
            "transpose gives none")
    pushed = [ad.instantiate_zeros(t) for t in tangents[held:]]
    return (linear_p.bind(*primals, **how),
            linear_p.bind(*primals[:held], *pushed, **how))


def _linear_transpose(cts, *args, fun, transpose, held, out_avals):
    kept, linear = args[:held], args[held:]
    if any(ad.is_undefined_primal(x) for x in kept) or not all(
            ad.is_undefined_primal(x) for x in linear):
        raise NotImplementedError(
            "a linear map (ops/_core.py linear_p) is transposed in all of "
            "its linear operands, and in none it keeps")
    back = linear_p.bind(
        *kept, *(ad.instantiate_zeros(ct) for ct in cts), fun=transpose,
        transpose=fun, held=held, out_avals=tuple(x.aval for x in linear))
    return [None] * held + list(back)


def _linear_batch(args, dims, *, fun, transpose, held, out_avals):
    """``jax.vmap`` (``jax.jacfwd``, ``jax.jacrev``): the map and its
    transpose each under ``jax.vmap``, every linear operand batched
    along its first axis."""
    size = next(x.shape[d] for x, d in zip(args, dims) if d is not None)
    args = [x if d is None else jnp.moveaxis(x, d, 0)
            for x, d in zip(args, dims)]
    linear = [jnp.broadcast_to(x, (size, *x.shape)) if d is None else x
              for x, d in zip(args[held:], dims[held:])]
    axes = [None if d is None else 0 for d in dims[:held]]

    def over(one):
        return lambda *flat: jax.vmap(
            lambda kept, rest: one(*kept, *rest), in_axes=(axes, 0))(
                list(flat[:held]), list(flat[held:]))

    out = linear_p.bind(
        *args[:held], *linear, fun=over(fun), transpose=over(transpose),
        held=held,
        out_avals=tuple(a.update(shape=(size, *a.shape)) for a in out_avals))
    return out, [0] * len(out)


linear_p.def_impl(_linear_impl)
linear_p.def_abstract_eval(lambda *args, out_avals, **_: list(out_avals))
ad.primitive_jvps[linear_p] = _linear_jvp
ad.primitive_transposes[linear_p] = _linear_transpose
batching.primitive_batchers[linear_p] = _linear_batch
# (not cached: the functions are a trace's own, so no second equation
# would hit, and a cached lowering is emitted at the equation's location,
# every line of the function's body named for the call's)
mlir.register_lowering(
    linear_p, mlir.lower_fun(_linear_impl, multiple_results=True),
    cacheable=False)


def comm_key(comm):
    """Hashable identity of a communicator for send/recv matching."""
    if comm.backend == "mesh":
        return ("mesh", comm.axes, comm.context)
    return (comm.backend, comm.context)
