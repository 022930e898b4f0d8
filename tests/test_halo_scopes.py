"""The halo exchange names its phases: ``pack``, ``wire`` and ``unpack``
as nested ``jax.named_scope``s inside the op's own ``mpi4jax_tpu.<op>``
scope.  Metadata only: the traced program stays eqn for eqn what it was,
and the contract analyzer still sees one op a call."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from mpi4jax_tpu.analysis import verify_comm
from mpi4jax_tpu.analysis.jaxpr_walk import walk_comm_jaxpr
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.ops._core import SCOPE_PREFIX
from mpi4jax_tpu.parallel import halo
from mpi4jax_tpu.parallel.halo import halo_exchange_2d, halo_exchange_2d_batch


def _eqns(jaxpr, outer=""):
    """``(eqn, its name stack)`` over a jaxpr and its sub-jaxprs, in
    program order; a sub-jaxpr's stacks are relative to the eqn that
    holds it (the exchange's body is a ``custom_jvp_call``'s since
    PR 59, a ``custom_jvp_call``'s from PR 54), and the stack given is the whole one, as lowering joins it."""
    for eqn in jaxpr.eqns:
        stack = "/".join(filter(None, [outer, str(eqn.source_info.name_stack)]))
        yield eqn, stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, stack)


def _stacks(fn, *args):
    """``{primitive name: {name stacks}}`` over the traced program."""
    out = {}
    for eqn, stack in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        out.setdefault(eqn.primitive.name, set()).add(stack)
    return out


def _program(comm, exchange):
    def local(_):
        arr = jnp.arange(64.0).reshape(8, 8)
        return exchange(arr, comm)[None]

    return jax.shard_map(
        local, mesh=comm.mesh, in_specs=jax.P(("y", "x")),
        out_specs=jax.P(("y", "x"), None, None))


def _single(arr, comm):
    return halo_exchange_2d(arr, comm, periodic=(False, True), width=2)[0]


def _batch(arr, comm):
    return halo_exchange_2d_batch(
        [arr, 2 * arr], comm, periodic=(False, True), width=2)[0][1]


def test_the_phase_names_do_not_look_like_ops():
    # the analyzer takes the innermost segment with the prefix as the op
    assert (halo.PACK, halo.WIRE, halo.UNPACK) == ("pack", "wire", "unpack")
    assert SCOPE_PREFIX == "mpi4jax_tpu."
    assert not any(p.startswith(SCOPE_PREFIX)
                   for p in (halo.PACK, halo.WIRE, halo.UNPACK))


@pytest.mark.parametrize("exchange,op,packed_by", [
    (_single, "halo_exchange_2d", "slice"),
    (_batch, "halo_exchange_2d_batch", "concatenate"),
])
def test_an_exchange_lowers_with_its_three_phases(comm2d, exchange, op, packed_by):
    stacks = _stacks(_program(comm2d, exchange), jnp.zeros(8))
    outer = f"{SCOPE_PREFIX}{op}"
    # slab slices (and the batched form's stack) under pack, the permute
    # under wire inside the sendrecv's own scope, the ghost writes, once
    # and after both wires, under unpack
    assert any(s.endswith(f"{outer}/pack") for s in stacks["slice"])
    assert any(s.endswith(f"{outer}/pack") for s in stacks[packed_by])
    assert all(s.endswith(f"{outer}/wire/{SCOPE_PREFIX}sendrecv")
               for s in stacks["ppermute"])
    assert stacks["ppermute"]
    assert all(s.endswith(f"{outer}/unpack")
               for s in stacks["dynamic_update_slice"])
    assert "scatter" not in stacks  # a write is not a scatter with its masks
    # the block is held row-major where the slabs are sliced from it
    assert all(s.endswith(f"{outer}/pack") for s in stacks["layout_constraint"])
    # nothing of the op lies outside its three phases
    # (the call that carries the exchange's derivative takes the three's
    # results and hands them on, its operands beside them with their
    # derivatives stopped for the three: both lower to nothing)
    mine = {s for name, group in stacks.items()
            if name not in ("custom_jvp_call", "stop_gradient")
            for s in group if outer in s}
    assert stacks["custom_jvp_call"] == stacks["stop_gradient"] == {outer}
    assert all(s.split(outer + "/")[-1].split("/")[0] in ("pack", "wire", "unpack")
               for s in mine)


def test_verify_comm_still_counts_one_op_a_call(comm2d):
    def local(_):
        arr = jnp.arange(64.0).reshape(8, 8)
        out, token = halo_exchange_2d(arr, comm2d, periodic=(False, True), width=2)
        outs, _ = halo_exchange_2d_batch(
            [out, 2 * out], comm2d, periodic=(False, True), width=2, token=token)
        return outs[0][None]

    prog = jax.shard_map(
        local, mesh=comm2d.mesh, in_specs=jax.P(("y", "x")),
        out_specs=jax.P(("y", "x"), None, None))
    report = verify_comm(lambda: prog(jnp.zeros(8)))()
    assert report.ok, report
    assert [e.kind for e in report.events] == [
        "halo_exchange_2d", "halo_exchange_2d_batch"]
    # the walker's occurrences: the op's own eqns and its four sendrecvs,
    # by the innermost scope with the prefix, as before the phases
    occurrences, findings = walk_comm_jaxpr(jax.make_jaxpr(prog)(jnp.zeros(8)))
    assert not findings
    ops = [o.op for o in occurrences]
    assert set(ops) == {"halo_exchange_2d", "halo_exchange_2d_batch", "sendrecv"}
    assert ops.count("sendrecv") == 8
    assert not any(p in ops for p in ("pack", "wire", "unpack"))


# (mesh, ghost) -> eqns of the traced 10-step program and the sha1 of their
# primitive names in order.  Here, on the CPU, every step is array code
# (five halo_exchange_2d, or one batched at ghost 4), so all three moved
# when the exchange took to writing its ghosts once (PR 35: a
# layout_constraint a block, the row slabs' ends patched by a
# concatenate, four dynamic_update_slice for four scatters, and no
# slices for a shift that moves nothing: 497, 748 and 633 eqns before);
# the kernel path's program, which calls halo_slabs_2d alone, is pinned
# by tests/test_tpu_compile.py
PINNED = {
    ((1, 1), 2): (482, "dc50b4237504"),
    ((2, 4), 2): (833, "68df98b0a745"),
    ((2, 4), 4): (684, "d129378393af"),
}


@pytest.mark.parametrize("mesh_shape,ghost", sorted(PINNED))
def test_the_solvers_program_is_eqn_for_eqn_what_it_was(mesh_shape, ghost):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    import mpi4jax_tpu as m

    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=24 * py, nx=48 * px, ghost=ghost)
    state = jax.eval_shape(sw.make_init(cfg, comm))
    jaxpr = jax.make_jaxpr(sw.make_multistep(cfg, comm, 10))(state).jaxpr
    # but for the call round each mesh-tier exchange that carries its
    # derivative (PR 54, PR 59: five a step, one at ghost 4), whose body is the
    # exchange's eqns as they were
    names = [eqn.primitive.name for eqn, _ in _eqns(jaxpr)]
    calls = names.count("custom_jvp_call")
    assert calls == (1 if ghost == 4 else 5)
    names = [name for name in names
             if name not in ("custom_jvp_call", "stop_gradient")]
    digest = hashlib.sha1(" ".join(names).encode()).hexdigest()[:12]
    assert (len(names), digest) == PINNED[mesh_shape, ghost]
