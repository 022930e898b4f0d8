"""Closed-jaxpr walker for the communication-contract analyzer.

Walks a traced program's jaxpr, recursing into every sub-jaxpr
(``pjit``/``scan``/``while``/``cond``/``custom_*`` — discovered
generically from eqn params, the same recursion the reference's
auto-tokenize interpreter performs over control flow), and provides the
two things the Python-level event recorder cannot see:

* **communication eqns in lowered form** — every public op wraps itself
  in ``jax.named_scope("mpi4jax_tpu.<op>")`` (ops/_core.py), so its
  lowered eqns carry that scope on their ``source_info.name_stack``
  regardless of backend (mesh psum/ppermute, proc ffi_call/io_callback).
  Consecutive eqns under one scope collapse to one *op occurrence*.
* **rank-provenance of branch predicates** — outputs of ``axis_index``
  (the mesh backend's ``comm.rank()``) are tainted and the taint is
  propagated through eqns and into sub-jaxprs, so a ``cond`` whose
  predicate derives from the rank is recognisable (rule T4J005).

Rank-dependent ``cond`` is only a contract violation when the branches
*communicate differently*: uniform branches (same op occurrences, same
shapes/dtypes/axes) are legal — e.g. masking a halo edge.  Divergent
branch schedules under a rank-derived predicate are exactly the
"collective matching depends on control flow" bug class MPI-Checker
flags statically; on the proc backend the same bug class is per-process
Python control flow, invisible to a single trace, which is what the
cross-rank fingerprint pass (analysis/fingerprint.py) exists for.
"""

from mpi4jax_tpu.analysis.contracts import Finding

__all__ = ["walk_comm_jaxpr", "OpOccurrence"]


class OpOccurrence:
    """One communication op as seen in the lowered jaxpr.

    ``n_eqns`` counts the lowered eqns merged into this occurrence.  It
    is part of the comparison signature: two *adjacent* calls of one op
    from the same source line are indistinguishable by scope and
    callsite, but they double the eqn run — identical programs lower to
    identical eqn counts, so a count mismatch means a schedule mismatch.
    """

    def __init__(self, op, detail, src_info, path):
        self.op = op            # "allreduce", "send", ...
        self.detail = detail    # hashable descriptor for comparisons
        self.src_info = src_info
        self.path = path        # control-flow nesting, e.g. ("cond[0]",)
        self.n_eqns = 1

    def signature(self):
        return (self.op, self.detail, self.n_eqns)

    def __repr__(self):
        return f"OpOccurrence({self.op}, {self.detail}, n={self.n_eqns})"


def walk_comm_jaxpr(closed_jaxpr):
    """Returns ``(occurrences, findings)`` for a closed jaxpr.

    ``occurrences`` is the flat, program-ordered list of communication
    op occurrences (loop bodies contribute once — the schedule is
    symbolic); ``findings`` currently carries rule T4J005.
    """
    occurrences = []
    findings = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk(jaxpr, set(), (), occurrences, findings)
    return occurrences, findings


def _walk(jaxpr, tainted_invars, path, occurrences, findings):
    """``tainted_invars``: set of this jaxpr's invars carrying
    rank-derived values (object identity of Var)."""
    tainted = set(tainted_invars)
    current_scope = None
    current_occ = None  # the run's own occurrence — recursion into a
    #                     sub-jaxpr may append nested occurrences, so
    #                     occurrences[-1] is not necessarily it
    for eqn in jaxpr.eqns:
        prim = getattr(eqn.primitive, "name", str(eqn.primitive))
        # -- taint seeding and propagation ------------------------------
        if prim == "axis_index":
            tainted.update(eqn.outvars)
        elif any(_is_tainted(v, tainted) for v in eqn.invars):
            tainted.update(eqn.outvars)

        # -- communication-op occurrence collapse -----------------------
        # one public op lowers to several adjacent eqns sharing the
        # same scope; collapse them to one occurrence.  The user call
        # site is part of the key so two back-to-back calls of the
        # same op (identical scope strings) stay two occurrences.
        scope = _comm_scope(eqn)
        if scope is not None:
            occ_key = (scope, _src(eqn))
            if occ_key != current_scope:
                current_occ = OpOccurrence(
                    op=scope.split(".", 1)[1],
                    detail=_eqn_detail(eqn),
                    src_info=_src(eqn),
                    path=path,
                )
                occurrences.append(current_occ)
            else:
                current_occ.n_eqns += 1
            current_scope = occ_key
        else:
            current_scope = None
            current_occ = None

        # -- rank-dependent cond (T4J005) -------------------------------
        if prim == "cond":
            branches = _branches(eqn)
            pred_tainted = bool(eqn.invars) and _is_tainted(
                eqn.invars[0], tainted
            )
            branch_occs = []
            for bi, br in enumerate(branches):
                sub_occ = []
                sub_taint = _map_subinvars(br, eqn.invars[1:], tainted)
                _walk(br, sub_taint, path + (f"cond[{bi}]",),
                      sub_occ, findings)
                branch_occs.append(sub_occ)
                occurrences.extend(sub_occ)
            if pred_tainted and _branches_disagree(branch_occs):
                where = _first_comm_src(branch_occs)
                findings.append(Finding(
                    rule="T4J005",
                    message=(
                        "cond predicate derives from the communicator "
                        "rank (axis_index) and its branches issue "
                        "different communication schedules: "
                        f"{_describe_branches(branch_occs)}. Under SPMD "
                        "every device must issue the same collective "
                        "sequence; hoist the collective out of the "
                        "branch or make the branches communicate "
                        "identically."
                    ),
                    src_info=where,
                ))
            continue  # sub-jaxprs already walked

        # -- generic recursion into sub-jaxprs --------------------------
        for sub in _sub_jaxprs(eqn):
            any_taint = any(_is_tainted(v, tainted) for v in eqn.invars)
            if prim in _POSITIONAL_PRIMS:
                # call-like primitives pass their operands through to
                # the sub-jaxpr positionally (pjit exactly; shard_map /
                # custom_partitioning may curry constants in front, so
                # align the zip at the TAIL) — precise mapping keeps an
                # untainted shard_map operand untainted inside, so a
                # cond on plain data inside shard_map does not
                # false-positive T4J005 just because axis_index was
                # used elsewhere in the call
                sub_taint = _tail_align_taint(sub, eqn.invars, tainted)
            else:
                sub_taint = (
                    set(sub.invars) if any_taint else set()
                )  # conservative: taint everywhere if any operand is
            #      tainted (scan/while reorder operands into carries)
            _walk(sub, sub_taint, path + (prim,), occurrences, findings)
    return tainted


# Primitives whose sub-jaxpr invars line up positionally with the eqn
# invars.  shard_map is the ROADMAP item-1 target: a collective under a
# rank-dependent branch INSIDE shard_map must still raise T4J005, which
# needs taint to flow through the shard_map call boundary (axis_index
# inside the body is also seeded directly — both routes must work).
_POSITIONAL_PRIMS = frozenset({
    "pjit", "shard_map", "custom_partitioning", "closed_call",
    "core_call", "xla_call",
})


def _tail_align_taint(sub_jaxpr, outer_invars, tainted):
    """Map outer operand taint onto sub-jaxpr invars, aligning at the
    tail (leading sub invars with no outer counterpart — lifted
    constants — stay untainted)."""
    sub_in = list(sub_jaxpr.invars)
    outer = list(outer_invars)
    sub_taint = set()
    for inner, out_v in zip(reversed(sub_in), reversed(outer)):
        if _is_tainted(out_v, tainted):
            sub_taint.add(inner)
    return sub_taint


def _is_tainted(var, tainted):
    # Literals are never tainted; Var identity is unique per jaxpr
    return not hasattr(var, "val") and var in tainted


def _comm_scope(eqn):
    """The innermost ``mpi4jax_tpu.<op>`` segment of the eqn's name
    stack, or None."""
    # here, not at module scope: this package imports without jax, and
    # an eqn to look at means jax is there
    from mpi4jax_tpu.ops._core import SCOPE_PREFIX

    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:
        return None
    hit = None
    for seg in stack.split("/"):
        if seg.startswith(SCOPE_PREFIX):
            hit = seg
    return hit


def _eqn_detail(eqn):
    """Hashable descriptor of a comm eqn for branch comparison: lowered
    primitive, operand/result types, and the collective-identity params
    (axes, permutation, groups) when present."""
    prim = getattr(eqn.primitive, "name", str(eqn.primitive))
    avals = tuple(
        str(getattr(v, "aval", "?")) for v in (*eqn.invars, *eqn.outvars)
    )
    params = []
    for key in ("axes", "axis_name", "perm", "axis_index_groups", "op",
                "root", "tag", "source", "dest", "comm"):
        if key in eqn.params:
            params.append((key, _hashable(eqn.params[key])))
    return (prim, avals, tuple(params))


def _hashable(v):
    try:
        hash(v)
        return v
    except TypeError:
        return str(v)


def _src(eqn):
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return ""


def _branches(eqn):
    out = []
    for br in eqn.params.get("branches", ()):
        out.append(getattr(br, "jaxpr", br))
    return out


def _map_subinvars(sub_jaxpr, outer_operands, tainted):
    """Positional taint mapping from a cond's operands onto a branch
    jaxpr's invars."""
    sub_taint = set()
    for outer, inner in zip(outer_operands, sub_jaxpr.invars):
        if _is_tainted(outer, tainted):
            sub_taint.add(inner)
    return sub_taint


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an eqn's params (pjit's ``jaxpr``, scan's
    ``jaxpr``, while's ``cond_jaxpr``/``body_jaxpr``, custom_jvp's
    ``call_jaxpr``, ...), discovered generically so new primitives keep
    working."""
    subs = []
    for value in eqn.params.values():
        subs.extend(_as_jaxprs(value))
    return subs


def _as_jaxprs(value):
    inner = getattr(value, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return [inner]
    if hasattr(value, "eqns"):
        return [value]
    if isinstance(value, (tuple, list)):
        out = []
        for v in value:
            out.extend(_as_jaxprs(v))
        return out
    return []


def _branches_disagree(branch_occs):
    sigs = [tuple(o.signature() for o in occs) for occs in branch_occs]
    return len(set(sigs)) > 1


def _first_comm_src(branch_occs):
    for occs in branch_occs:
        for o in occs:
            if o.src_info:
                return o.src_info
    return ""


def _describe_branches(branch_occs):
    return "; ".join(
        f"branch {i}: [{', '.join(o.op for o in occs) or 'no comm'}]"
        for i, occs in enumerate(branch_occs)
    )
