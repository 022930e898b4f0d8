"""The main path's kernels and the solver step compile for a described
TPU v5e — no chip attached, nothing executed.

The TPU compiler is installed wherever libtpu is, and refuses here what
it would refuse on the chip: a slice off the tiling, a kernel over its
fast-memory budget, a program that does not fit device memory.  These
guard the shapes ``chip_smoke.py`` drives at no chip time.  A compile
that passes is not a chip run and says nothing about results or speed.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

import mpi4jax_tpu as m  # noqa: E402
from mpi4jax_tpu.models import shallow_water as sw  # noqa: E402
from mpi4jax_tpu.ops.flash import flash_attention  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # an entry compiled for a described chip is written to the cache but
    # cannot be read back without the chip: the next run would warn and
    # compile again
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# [B, T, H, D] of benchmarks/transformer.py SIZES["large"] and ["long"]
HEAD_SHAPES = {"large": (16, 2048, 16, 128), "long": (2, 8192, 16, 128)}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("size", sorted(HEAD_SHAPES))
def test_flash_compiles_for_v5e(v5e, size, direction):
    x = jax.ShapeDtypeStruct(
        HEAD_SHAPES[size], jnp.bfloat16,
        sharding=SingleDeviceSharding(v5e.devices[0]),
    )

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return (fwd(q, k, v).astype(jnp.float32) ** 2).sum()

    fn = fwd if direction == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


@functools.cache  # a dozen tests read five programs
def _compiled_multistep(v5e, mesh_shape, ghost, ny, nx, steps):
    """The donated ``steps``-step call at ``ny`` x ``nx`` cells a chip,
    compiled for the described chips; ``steps`` 0: the first step."""
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px],
    )
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=ny * py, nx=nx * px, ghost=ghost)
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(sw.make_init(cfg, comm)),
    )
    if steps == 0:
        return sw.make_first_step(cfg, comm).lower(state).compile()
    return sw.make_multistep(cfg, comm, steps, donate=True).lower(
        state).compile()


def _kernel_calls(text):
    """Every call of the step's kernel (the forward one) in a compiled
    program's text, in the text's order, as :func:`_kernels` describes
    one."""
    return [_kernels(line)["wide_step"] for line in text.splitlines()
            if "tpu_custom_call" in line
            and re.match(r"\s*(?:ROOT )?%wide_step(?:\.\d+)? =", line)]


def _trips(text):
    """How often a program's loop runs: the constant its condition
    compares the counter with."""
    condition = re.search(r"\bwhile\(.*?condition=%([\w.\-]+)", text)[1]
    lines = re.search(
        rf"^%{re.escape(condition)} \(.*?^\}}", text, re.S | re.M)[0]
    bound, = re.findall(r"s32\[\]\S* constant\((\d+)\)", lines)
    return int(bound)


def _kernels(text):
    """The Pallas calls of a compiled program's text: name -> (line,
    the operands that are fields, the kernel's own text, the fields'
    places among the operands).  A field is an operand of the shape of
    the call's first result; the slabs and the scalars are not."""
    found = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            name = line.split("=")[0].split("%")[1].split(".")[0].strip()
            shape = re.search(r"= \(?(\w+\[[\d,]*\])", line)[1]
            operands = line.split("custom-call(")[1].split(")")[0]
            operands = re.sub(r"/\*index=\d+\*/", "", operands).split(", ")
            layouts = line.split("operand_layout_constraints={")[1].split("}}")[0]
            places = [k for k, layout in enumerate(layouts.split("}, "))
                      if layout.startswith(shape)]
            body = line.split('"custom_call_config":{"body":"')[1].split('"')[0]
            found[name] = line, [operands[k] for k in places], body, places
    return found


def _aliased_in_place(line, places):
    """Whether a Pallas call's line aliases its results, in order, to
    the operands at ``places`` (:func:`_kernels`'s fields)."""
    aliasing = ", ".join(
        f"{{{k}}}: ({place}, {{}})" for k, place in enumerate(places))
    return f"output_to_operand_aliasing={{{aliasing}}}" in line


def _copied(text, fields):
    """Those of a call's field operands that are copies, but for a
    field XLA kept in its faster memory (``S(1)``: a field of a few
    tens of MB) and moves to where the call takes it."""
    moved = {f"%{m[1]}" for m in re.finditer(
        r"%(copy-done[\w.]*) = .*copy-done\(%(copy-start[\w.]*)\)", text)
        if re.search(rf"%{re.escape(m[2])} = .*S\(1\).*copy-start\(", text)}
    return [op for op in fields if "copy" in op and op not in moved]


@pytest.mark.parametrize("ghost", [1, 2, 4])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_solver_multistep_compiles_for_v5e(v5e, mesh_shape, ghost):
    """1800x3600 per chip, the donated 25-step call the bench times."""
    py, px = mesh_shape
    compiled = _compiled_multistep(v5e, mesh_shape, ghost, 1800, 3600, 25)
    text = compiled.as_text()
    # one chip: XLA elides every halo exchange; four: they are real
    assert ("collective-permute" in text) == (py * px > 1)
    # the wide-halo step after its first exchange is one Pallas kernel
    # on TPU devices, updating the six arrays of the state in place (XLA
    # copies a field that a custom call both overwrites and reads
    # through a second operand); the other two schedules are array code
    kernels = _kernels(text)
    assert sorted(kernels) == (["wide_step"] if ghost == 2 else [])
    # a walk of the kernel is two steps, beside neighbours too (PR 53),
    # and the 25 are twelve walks of two in the loop and one of one
    # after it
    calls = _kernel_calls(text)
    assert len(calls) == text.count("tpu_custom_call") == (ghost == 2) * 2
    if kernels:
        assert _trips(text) == 12
        # the walk of one step is another kernel than the walk of two
        assert len({body for _, _, body, _ in calls}) == len(calls)
    for line, fields, _, places in calls:
        assert _aliased_in_place(line, places)
        assert len(fields) == 6 and not _copied(text, fields), fields
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 2**30  # six fields of ~26 MB
    # not one field's bytes beside the state: every intermediate of the
    # step stays in the kernel
    assert (mem.temp_size_in_bytes < 1800 * 3600 * 4) == (ghost == 2)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_first_step_and_the_rest_hold_one_kernel_text(v5e, mesh_shape):
    """Forward Euler is the AB2 kernel with other scalars, so a process
    traces and lowers the step's kernel once for its two programs.
    That kernel walks two steps, on one chip and on four, and the first
    step's call passes the first of them over by a scalar."""
    texts = [_compiled_multistep(v5e, mesh_shape, 2, 1800, 3600, steps).as_text()
             for steps in (0, 10)]
    first, rest = (_kernels(text) for text in texts)
    assert sorted(first) == sorted(rest) == ["wide_step"]
    assert first["wide_step"][2] == rest["wide_step"][2]
    assert not _copied(texts[0], first["wide_step"][1]), first["wide_step"][1]


def _scoped_vmem(line):
    """``(used, limit)`` of a compiled Pallas call's line: the bytes of
    VMEM the TPU compiler laid out for it (blocks, scratch and its own
    spills) and the most it was allowed (``None``: the compiler's own).
    The room starts past whatever XLA keeps in that memory across the
    call (8 KB of slabs in the job's program on 2x2 since PR 52)."""
    size = r'scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"'
    used, = re.findall('"used_' + size, line)
    limit = re.findall('"' + size, line)
    return int(used), int(limit[0]) if limit else None


# the VMEM the compiler laid out for the step's kernel at 1800x3600 a
# chip.  PR 47 (a row's fluxes, energy and vorticity product made once
# and kept for the strip after: four strips of 29 vector registers more
# a step in scratch, fewer 113-register values alive at once and so
# fewer spilled): (1, 1), the walk of two steps, 58 318 848 before, up
# by 188 416 (eight strips of 118 784 bytes in, 761 856 of spills out);
# (2, 2), the walk of one, 89 575 424 before, down by 622 592 (four
# strips in, 1 097 728 of spills out).  PR 52, (2, 2): 88 952 832 was
# 53 731 328 of two fields that XLA kept in that memory across the call,
# beside the transposes it made of them, and 35 221 504 of the call's
# own after them; with the block held row-major no field lies there, the
# call's room starts at 0, and its own is 5 226 496 more: two windows of
# 88 rows of 3712 for each of those two fields, which it reads from HBM
# again like the other four.  PR 53, (2, 2): the walk of two steps there
# too, (1, 1)'s blocks, windows and rings and beside them the slabs of
# six arrays for three, four deep for two (a slab of columns in blocks
# of a tile's rows and a register's lanes, a slab of rows whole, both
# double-buffered) and three strips for the tendencies of the strip
# before the block's first; at this size XLA keeps `h` in that memory
# across the call again (34 496 512 before the call's own room: the
# field's 26 MB and the slabs'), as it did before PR 52, now that six
# arrays' packing and permutes stand between the loop's top and the call
KERNEL_VMEM = {(1, 1): 58_507_264, (2, 2): 90_509_312}


@pytest.mark.parametrize("mesh_shape", sorted(KERNEL_VMEM))
def test_the_steps_kernel_takes_the_vmem_it_took(v5e, mesh_shape):
    text = _compiled_multistep(v5e, mesh_shape, 2, 1800, 3600, 10).as_text()
    (line, *_), = _kernel_calls(text)
    assert _scoped_vmem(line)[0] == KERNEL_VMEM[mesh_shape]


def test_the_cells_kernel_keeps_its_tiles_under_its_vmem_limit(v5e):
    """At the benchmark cells' 7204 x 14404 the walk of two steps keeps
    tiles of 24 rows (16 cost the step 1 %, PERF.md, PR 41) with the
    strips of row values PR 47 keeps beside its rings, and what the
    compiler lays out for the call stays under the limit it is given."""
    from mpi4jax_tpu.models import sw_kernels

    assert sw_kernels.tile_rows(7204, 14404, jnp.float32, 6, steps=2) == 24
    text = _compiled_multistep(v5e, (1, 1), 2, 7200, 14400, 10).as_text()
    (line, *_), = _kernel_calls(text)
    used, limit = _scoped_vmem(line)
    assert limit == sw_kernels._VMEM_LIMIT * sw_kernels._buffers(2) // 5
    assert 60e6 < used < limit


def _computation(text, name):
    """A computation of a compiled program's text: ``(instructions,
    types)``, the instructions as ``(name, opcode, operand names, line)``
    without those that move nothing (parameters, tuples and their
    elements, constants, bitcasts), the types by name."""
    lines = re.search(
        rf"^%{re.escape(name)} \(.*?^\}}", text, re.S | re.M)[0].splitlines()
    found, types = [], {}
    for line in lines[1:-1]:
        name, rest = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)", line).groups()
        types[name], opcode, operands = re.match(
            r"(.*?) ([a-z][a-z\-]*)\((.*?)\)(?:, |$)", rest).groups()
        if opcode not in ("parameter", "tuple", "get-tuple-element",
                          "constant", "bitcast"):
            found.append(
                (name, opcode, re.findall(r"%([\w.\-]+)", operands), line))
    return found, types


def _step_body(text):
    """The body of a program's loop (the 10-step program's, the table's
    chain's), as :func:`_computation` gives it."""
    return _computation(
        text, re.search(r"\bwhile\(.*?body=%([\w.\-]+)", text)[1])


def _moves_a_field(text, field):
    """The instructions of a compiled program's text that hand back a
    field's bytes in another place or layout: a ``copy``, a
    ``copy-start``, a ``copy-done`` or a ``transpose`` whose result, or
    the first element of it, is of the shape ``field``."""
    return re.findall(
        rf"%([\w.\-]+) = \(?{re.escape(field)}[^=]*? "
        r"(?:copy|copy-start|copy-done|transpose)\(", text)


# what the step's loop body holds that moves or computes something.  One
# chip: the loop's counter, three fusions of two slices (a field's sent
# columns) and the kernel, which walks two steps (the broadcast that
# built the kernel's two wall flags in the loop went when `lone` joined
# them: on one chip the three are a constant).  Four: 99 while XLA
# transposed each field once a step (three `copy` of a field, five
# `copy-start` and `copy-done` of fields and flags into and out of its
# faster memory, four `slice-start` and `slice-done` and the
# `ConcatBitcast` that put a field together again); since PR 52 those
# 22 are gone, and each of the six sent column slabs is transposed by
# itself for its permute, as the six received ones were and are.  PR 53:
# a walk of two steps there too, from slabs of six arrays four deep, 83
# a step became 219 for two (twice the slabs of twice the arrays, each
# row slab laid out along a block's lanes for the kernel, and the seven
# instructions with which XLA keeps `h` in its faster memory across the
# call at this size: test_the_cells_step_copies_no_block has the size
# at which no field fits there)
STEP_INSTRUCTIONS = {(1, 1): 5, (2, 2): 219}


@pytest.mark.parametrize("mesh_shape", sorted(STEP_INSTRUCTIONS))
def test_the_step_writes_no_ghost_outside_its_kernel(v5e, mesh_shape):
    """The exchange hands the kernel its slabs and writes none: beside
    the one call a step, nothing updates or scatters into a field on
    any mesh, and the six arrays of the state are the call's own
    operands, aliased to its results and no copies."""
    chips = mesh_shape[0] * mesh_shape[1]
    text = _compiled_multistep(v5e, mesh_shape, 2, 1800, 3600, 10).as_text()
    body, types = _step_body(text)

    def on_a_field(opcode):
        return [name for name, op, operands, _ in body if op == opcode and any(
            types[x].startswith("f32[1804,3604]") for x in operands)]

    assert not on_a_field("dynamic-update-slice") and not on_a_field("scatter")
    # no copy of a field on any mesh: XLA wants the permutes' column
    # slabs lane-dense, and the block they are sliced from is held
    # row-major (parallel/halo.py _row_major), so it transposes the
    # slabs and not the block (PERF.md section 6, PR 52); on four chips
    # a field of this size goes to XLA's faster memory and back round
    # the call, which is no transpose and no `copy` (`_copied` below)
    moved = _moves_a_field(text, "f32[1804,3604]")
    assert not [name for name in moved if not name.startswith(
        ("copy-start", "copy-done") if chips > 1 else ())], moved
    calls = [line for *_, line in body if "tpu_custom_call" in line]
    # one for two steps, five times for ten
    assert len(calls) == 1
    assert _trips(text) == 5
    (line, fields, _, places), = _kernels(text).values()
    assert line == calls[0]
    # the state's arrays stand round the slabs: two a field on one chip
    # (the y shifts move nothing), four an array on four, the
    # tendencies' too
    slabs = 3 * 2 if chips == 1 else 6 * 4
    assert places == [2, 3, 4, *(5 + slabs + k for k in range(3))]
    assert _aliased_in_place(line, places)
    assert len(fields) == 6 and not _copied(text, fields), fields
    opcodes = [opcode for _, opcode, *_ in body]
    assert ("collective-permute-start" in opcodes) == (chips > 1)
    assert len(body) == STEP_INSTRUCTIONS[mesh_shape], opcodes


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_cells_step_copies_no_block(v5e, mesh_shape):
    """The 10-step call at the benchmark cells' own block, 7200 x 14400
    cells a chip (`sw-monitored-2x2-weak` on 2x2, the six one-chip cells
    on 1x1): nowhere in the program is a field's bytes handed back by a
    ``copy``, a ``copy-start`` or a ``transpose`` (three ``copy`` of a
    415 MB field to ``{0,1}`` a step on 2x2 until PR 52, a third of the
    step's device time), the program's temporaries are the slabs and
    not a field, the six arrays of the state are the kernel call's own
    operands, aliased to its results, and the wire is counted a call of
    the kernel, which is two steps (PR 53): on four chips 24 permutes,
    on one none."""
    chips = mesh_shape[0] * mesh_shape[1]
    compiled = _compiled_multistep(v5e, mesh_shape, 2, 7200, 14400, 10)
    text = compiled.as_text()
    assert not _moves_a_field(text, "f32[7204,14404]")
    body, types = _step_body(text)
    # (a slab of four columns takes a lane tile a row: 3.7 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2**20 if chips == 1 else 10 * 2**20)
    (line, fields, _, places), = _kernel_calls(text)
    assert _aliased_in_place(line, places)
    assert len(fields) == 6 and not _copied(text, fields), fields
    assert _trips(text) == 5
    # a walk of two steps: on four chips 24 permutes a walk, two column
    # slabs and two row slabs of each of the state's six arrays, four
    # deep, the row slabs as wide as the block and its deeper x slabs
    sent = sorted(types[name].split("{")[0] for name, opcode, *_ in body
                  if opcode == "collective-permute-start")
    assert sent == (["(f32[4,14408]"] * 12 + ["(f32[7204,4]"] * 12) * (chips > 1)
    assert text.count(" collective-permute-start(") == len(sent)


@functools.cache  # four tests read two programs
def _halo_row(v5e, program):
    """The benchmark's halo row (``perfbench/workloads/coll-2x2.json``:
    width 2 on 1804 x 3604 a chip, 2x2) under ``program(op, reps, mesh,
    spec)``, compiled for the described chips: ``(blocks, updates)``, the
    loop body's instructions whose result is a block as ``(opcode, type,
    line)``, and the type of what every ``dynamic-update-slice`` of the
    exchange writes there, those inside the body's fusions too."""
    import json

    from perfbench.drivers import collectives

    mesh = jax.make_mesh(
        (2, 2), collectives.AXES,
        axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=v5e.devices[:4])
    with open("perfbench/workloads/coll-2x2.json") as f:
        row, = (r for r in json.load(f)["rows"] if r["op"] == "halo")
    ny, nx = row["shape"]
    spec = jax.P(*collectives.AXES)
    x = jax.ShapeDtypeStruct(
        (2 * ny, 2 * nx), jnp.float32, sharding=NamedSharding(mesh, spec))
    op = collectives.library_op(row, m.MeshComm.from_mesh(mesh))
    text = program(op, row["reps"], mesh, spec).lower(x).compile().as_text()
    body, types = _step_body(text)
    blocks = [(opcode, types[name], line) for name, opcode, _, line in body
              if types[name].startswith(f"f32[{ny},{nx}]")]
    updates = []
    for _, opcode, operands, line in body:
        inside, kinds = [(None, opcode, operands, line)], types
        if opcode == "fusion":
            inside, kinds = _computation(
                text, re.search(r"calls=%([\w.\-]+)", line)[1])
        updates += [kinds[operands[1]].split("{")[0]
                    for _, opcode, operands, line in inside
                    if opcode == "dynamic-update-slice"
                    and "halo_exchange_2d" in line]
    return blocks, updates


def _in_place(op, reps, mesh, spec):
    """``x = op(x)`` alone in a loop whose carry is donated."""

    def local(x):
        return jax.lax.fori_loop(0, reps, lambda _, x: op(x), x)

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec),
        donate_argnums=0)


def _opcodes(blocks):
    return sorted(opcode for opcode, *_ in blocks)


def test_the_tables_halo_row_moves_its_block_once(v5e):
    """The table calls the exchange with its input kept alive.  The
    ghosts are four writes in place on one value, after both wires, in
    a block that stays row-major (left to itself XLA lays the whole
    block out to suit the two-column slabs sliced from it, and each row
    write is then 3604 pieces: PERF.md, PR 35): the two row slabs by a
    ``dynamic-update-slice`` each, the two column slabs by a fusion each
    that rewrites the lane tiles they lie in (PR 37).  Three copies: the
    result out to the carried output is the exchange's; the input saved
    before those writes and handed back after the chain's own write are
    what XLA makes of the benchmark's chain, inside its faster memory.
    None transposes."""
    from perfbench.drivers import collectives

    blocks, _ = _halo_row(v5e, collectives.chained)
    assert all(kind.startswith("f32[1804,3604]{1,0") for _, kind, _ in blocks)
    assert _opcodes(blocks) == (
        ["copy"] * 3 + ["dynamic-update-slice"] * 3 + ["fusion"] * 2)
    placed = [(opcode, line) for opcode, _, line in blocks
              if "halo_exchange_2d/unpack" in line]
    assert sorted(opcode for opcode, _ in placed) == (
        ["dynamic-update-slice"] * 2 + ["fusion"] * 2)
    # a fusion that writes where it reads: no block beside the block
    assert all('"aliasing_operands":{"lists":[{"indices":["0",' in line
               for opcode, line in placed if opcode == "fusion")
    # the one write that is not the exchange's is the chain's
    assert len([1 for opcode, _, line in blocks
                if opcode == "dynamic-update-slice"
                and "halo_exchange_2d" not in line]) == 1
    # one copy leaves the faster memory: the result; the chain's two stay
    copies = [kind for opcode, kind, _ in blocks if opcode == "copy"]
    assert sorted("S(1)" in kind for kind in copies) == [False, True, True]


def test_an_exchange_in_place_copies_no_block(v5e):
    """``x = halo_exchange_2d(x)`` alone in a loop whose carry is
    donated: four writes on the carried block where it lies, and nothing
    else of a block's size."""
    blocks, _ = _halo_row(v5e, _in_place)
    assert all(kind.startswith("f32[1804,3604]{1,0") for _, kind, _ in blocks)
    assert _opcodes(blocks) == ["dynamic-update-slice"] * 2 + ["fusion"] * 2
    assert all("halo_exchange_2d/unpack" in line for *_, line in blocks)


@pytest.mark.parametrize("form", ["chained", "in_place"])
def test_no_write_of_the_exchange_is_two_columns_wide(v5e, form):
    """A ``[1804, 2]`` slab in a row-major ``(8, 128)``-tiled block is
    1804 pieces of 8 bytes, 13 us a write on a v5e where the row slabs'
    take 1 (PERF.md, PRs 35 and 37): the column slabs land as the strips
    of whole lane tiles that hold them, columns 0 to 128 and 3584 to
    3604, and the row slabs as they are."""
    from perfbench.drivers import collectives

    program = collectives.chained if form == "chained" else _in_place
    _, updates = _halo_row(v5e, program)
    assert sorted(updates) == [
        "f32[1804,128]", "f32[1804,20]", "f32[2,3604]", "f32[2,3604]"]


# what one trip of the as-written step's loop holds at a field's size
# (7202 x 14402 padded), by opcode, since PR 43 made every field of the
# step at the padded shape: five fusions (`fe`, `fn`, `q`, `ke`; the
# tendencies and AB2 in two; friction in two more, the first of them
# with the last of AB2), each writing its results whole, ring and all;
# two copies (`hc` is `h` but for its ring, so a copy of it; one of the
# new `h`, which XLA could not write over the old one the `du` tendency
# still reads); no `pad`, no `concatenate`, no `slice`, no interior
# placed into a live field.  A fusion "in place" writes the exchange's
# lane-tile strips into fields where they lie (up to four fields a
# fusion) and moves no field; a "strip in place" is a row or a column
# written into a field where it lies (`hc`'s ring, four; the northern
# wall's row of `fn` and `v`, three).  Before: ten fusions, ten whole
# interiors written in place, eight pads, six copies, two concatenates
# and a slice, 87.31 passes.
AS_WRITTEN_TRIP = {
    "fusion": 5, "fusion in place": 10, "copy": 2, "strip in place": 7}
AS_WRITTEN_PASSES = 45  # the most a trip may make by the benchmark's rules


def test_the_as_written_cell_compiles_for_v5e_and_its_passes_are_pinned(v5e):
    """``sw-as-written-1chip``'s donated 10-step call (``ghost`` 1,
    14400 x 7200 cells on one chip): it fits the chip, runs no kernel
    call, carries the step's scopes, and a trip of its loop holds the
    field-sized instructions counted above: 35.33 passes over a field
    a step by the benchmark's rules (``perfbench/layer_metrics/
    sw_field_passes_per_step.py``), where the kernel cells make 6."""
    from types import SimpleNamespace

    from perfbench.harness import files, scopes

    compiled = _compiled_multistep(v5e, (1, 1), 1, 7200, 14400, 10)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    field_bytes = 7202 * 14402 * 4
    state_bytes = 6 * field_bytes  # filled up to whole (8, 128) tiles
    assert state_bytes <= mem.argument_size_in_bytes <= 1.01 * state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert "tpu_custom_call" not in text and "collective-permute" not in text
    assert _trips(text) == 10
    body, types = _step_body(text)
    passes = files.load_module("layer_metrics", "sw_field_passes_per_step")
    in_place = passes.in_place_writes(text)
    counts = {}
    for name, opcode, _, _ in body:
        if re.search(r"f32\[720[02],1440[02]\]", types[name]):
            if opcode == "fusion" and name in in_place:
                opcode = "fusion in place"
            elif opcode == "dynamic-update-slice":
                # what it writes, not what it writes into: a row or a
                # column, never an interior
                assert in_place[name] <= 14402 * 4, (name, in_place[name])
                opcode = "strip in place"
            counts[opcode] = counts.get(opcode, 0) + 1
    assert counts == AS_WRITTEN_TRIP
    # by the benchmark's own rules, every instruction of the trip an
    # event that ran once: the count the cell's traced run reports
    events = [SimpleNamespace(name=line.strip().removeprefix("ROOT "))
              for _, _, _, line in body]
    moved, kernel_calls = passes.moved_bytes(events, text)
    assert kernel_calls == 0
    assert 30 < moved / field_bytes <= AS_WRITTEN_PASSES, moved / field_bytes
    # every ghost column is written in place as the strip of lane tiles
    # that holds it, never as a piece of 4 bytes a row
    strips = {in_place[name] for name, opcode, _, _ in body
              if opcode == "fusion" and name in in_place}
    assert strips <= {k * 7202 * cols * 4 for k in (1, 2, 4) for cols in (128, 66)}
    # the phases and the exchanged fields reach the TPU's text
    table = scopes.origins(text)
    names = {o.op_name for o in table.values() if o.op_name}
    for phase in sw.STEP_PHASES:
        assert any(f"/{sw.STEP_SCOPE}/{phase}/" in n for n in names), phase
    exchanged = {n.split(f"/{sw.STEP_SCOPE}/exchange.")[1].split("/")[0]
                 for n in names if f"/{sw.STEP_SCOPE}/exchange." in n}
    # one chip: no wire, and XLA writes the columns of several fields in
    # one fusion under the first one's name; every exchange that is left
    # a name is one of the twelve
    assert exchanged and exchanged <= set(sw.STEP_EXCHANGES)
    assert all(scopes.layer_of(o) == scopes.OP_SURFACE for o in table.values()
               if o.op_name and f"/{sw.STEP_SCOPE}/exchange." in o.op_name)
    assert all(scopes.layer_of(o) == scopes.PROGRAMS for o in table.values()
               if o.op_name and re.search(
                   rf"/{sw.STEP_SCOPE}/(?!exchange\.)", o.op_name))


@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_make_state_builds_the_form_the_schedule_carries_on_a_tpu(v5e, ghost):
    """Where the ``ghost`` 2 step runs as the kernel the tendencies are
    padded, which no CPU run shows: ``make_state``'s shapes are
    ``make_init``'s on the described chips for every schedule."""
    mesh = jax.make_mesh(
        (2, 2), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:4])
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=3600, nx=7200, ghost=ghost)
    field = jax.ShapeDtypeStruct(
        (3600, 7200), jnp.float32, sharding=NamedSharding(mesh, jax.P("y", "x")))
    made = jax.eval_shape(sw.make_state(cfg, comm), field, field, field)
    assert made == jax.eval_shape(sw.make_init(cfg, comm))
    assert made.dh.shape == made.h.shape == (3600 + 4 * ghost, 7200 + 4 * ghost)
    sw.make_state(cfg, comm).lower(field, field, field).compile()


@pytest.mark.parametrize("n", [1, 4])
def test_op_surface_compiles_for_v5e(v5e, n):
    """chip_smoke.py's 13-op program and its rendezvous ring (host
    callbacks from device code) lower for the chip."""
    import chip_smoke

    devices = v5e.devices[:n]
    sharding = NamedSharding(
        jax.make_mesh(
            (n,), ("p",), axis_types=(jax.sharding.AxisType.Auto,),
            devices=devices,
        ),
        jax.P("p"),
    )
    fn, x = chip_smoke.ops_program(devices)
    text = fn.lower(
        jax.ShapeDtypeStruct((x.size,), x.dtype, sharding=sharding)
    ).compile().as_text()
    # across chips the ops are ICI collectives; on one chip XLA elides them
    assert ("all-reduce" in text) == (n > 1)
    ring = chip_smoke.rendezvous_program(devices).lower(
        jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=sharding)
    ).compile()
    # on TPU a host callback is a pair of host transfers, not a custom call
    assert "is_host_transfer=true" in ring.as_text()


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_v5e_programs_text_says_where_an_instruction_came_from(
        v5e, mesh_shape):
    """The benchmark gives device time to the layer that emitted it by
    reading the compiled program's text (perfbench/harness/scopes.py):
    the TPU executable's has to carry the ops' scopes, the halo's three
    phases and the source lines, in the form that reader parses."""
    from perfbench.harness import scopes

    py, px = mesh_shape
    text = _compiled_multistep(v5e, mesh_shape, 2, 180, 360, 10).as_text()
    table = scopes.origins(text)
    lines = dict(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", text, re.M))
    # the step's exchange has no third phase: its kernel writes the ghosts
    halo = "mpi4jax_tpu.halo_slabs_2d"
    phases = {o.scopes[1] for o in table.values() if len(o.scopes) > 1}
    assert phases == ({"pack"} if py * px == 1 else {"pack", "wire"})
    packs = [o for o in table.values() if o.scopes == (halo, "pack")]
    assert {o.source.split(":")[0] for o in packs} == {
        "mpi4jax_tpu/parallel/halo.py"}
    # the exchange that writes them (the benchmark's halo row, the
    # solver's initial state) has all three
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px])
    init = sw.make_init(
        sw.SWConfig(ny=180 * py, nx=360 * px, ghost=2), m.MeshComm.from_mesh(mesh))
    written = scopes.origins(init.lower().compile().as_text())
    assert {("mpi4jax_tpu.halo_exchange_2d", phase)
            for phase in ("pack", "unpack")} <= {
        o.scopes for o in written.values()}
    # what the model's step leaves beside its kernel (the scalars it is
    # handed, its results taken apart; the call's own line the reader
    # does not find yet, PERF.md section 7) is the programs', not the
    # halo's
    model = [o for o in table.values()
             if o.source and o.source.startswith("mpi4jax_tpu/models/")]
    assert model and all(
        scopes.layer_of(o) == scopes.PROGRAMS and not o.scopes for o in model)
    permutes = [table[name] for name, rest in lines.items()
                if scopes.is_collective(f"%{name} = {rest}")]
    # one chip: every permute is elided; four: each lies under the wire
    assert bool(permutes) == (py * px > 1)
    assert all(o.scopes == (halo, "wire", "mpi4jax_tpu.sendrecv")
               for o in permutes)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_with_output_donates_and_its_snapshot_carries_its_scope(
        v5e, mesh_shape):
    """``make_job`` with a snapshot, at the benchmark cell's size a
    chip: the call's multistep is the donated program (no ``copy`` of a
    field: output does not turn donation off) and holds **one** kernel
    text in two places: in its loop with the sums switched off by a
    scalar, and after the loop the one walk that writes the sums over
    four rows of ``h``, ``u``, ``v``, a quarter of a field each, into
    the room the program is handed beside the state and hands back:
    both donated, every result in an operand's place, so that a call
    allocates nothing and has no temporary of the sums' size; the
    job's first step is that kernel too, so a process builds one.  A
    job without output runs the kernel without sums, as it did.  The snapshot program, lowered with the sums, is
    one ``reduce-window`` a field along the rows of sums as they lie,
    under ``mpi4jax_tpu.snapshot/coarsen``, with no temporary (a slice
    of the interior first would be one; on whole fields the plain
    ``reshape`` to ``(ny/4, 4, nx/4, 4)`` takes 13.7 GB)."""
    from mpi4jax_tpu.models import sw_kernels
    from perfbench.harness import scopes

    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px])
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=7200 * py, nx=14400 * px, dx=1250.0, dy=1250.0, ghost=2)
    job = sw.make_job(cfg, comm, 10, sw.Snapshot(coarsen=4), lambda *a: None)
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(sw.make_init(cfg, comm)))

    room = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(job._room))
    lowered = job.multi.lower(state, room)
    multi = lowered.compile()
    text = multi.as_text()
    in_loop, last = _kernel_calls(text)
    quiet = sw.make_job(cfg, comm, 10).multi.lower(state).compile().as_text()
    (_, _, without, _), = _kernel_calls(quiet)
    first, = _kernel_calls(job.first.lower(state).compile().as_text())
    # the first step makes the room it returns beside the state
    assert [(a.shape, a.dtype) for a in job.first.lower(state).out_info[1]] == [
        (a.shape, a.dtype) for a in room]
    assert in_loop[2] == last[2] == first[2] != without
    # the loop runs every walk but the call's last; a walk is two
    # steps, on four chips too
    walks = 5
    assert _trips(text) == walks - 1 and _trips(quiet) == walks
    for line, fields, _, _ in (in_loop, last):
        assert len(fields) == 6 and not _copied(text, fields), fields
        # the room: the call's last three operands, no copies either
        operands = line.split("custom-call(")[1].split(")")[0].split(", ")
        assert not _copied(text, operands[-3:]), operands
    # nothing of a field's size is copied on any mesh, in the loop's
    # step or in the last (until PR 52 XLA transposed each field once a
    # step on four chips to slice its column slabs: 2 * 3 here)
    assert not _moves_a_field(text, "f32[7204,14404]")
    # three arrays of row sums beside the state: 76 blocks of 24 rows,
    # four tiles' sums each, whole width, in and out
    new_state, sums = lowered.out_info
    assert [a.shape for a in new_state] == [a.shape for a in state]
    assert [(a.shape, a.dtype) for a in sums] == [(a.shape, a.dtype) for a in room] == [
        ((1824 * py, 14404 * px), jnp.float32)] * 3
    assert all(a.donated for a in jax.tree.leaves(lowered.args_info))
    memory = multi.memory_analysis()
    # every argument is a result's room (the results' tuple is the rest)
    assert memory.argument_size_in_bytes == memory.alias_size_in_bytes >= (
        (6 * 7204 + 3 * 1824) * 14404 * 4)
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 4096
    # the walks in the loop name the sums as results like the last, and
    # write them where the room lies: no temporary of the sums' size
    if py * px == 1:
        assert memory.temp_size_in_bytes < 2**20
    # what the compiler laid out in VMEM for the walk that can sum: over
    # the walk without, under the limit the walk is given
    used, limit = _scoped_vmem(last[0])
    assert limit == sw_kernels._VMEM_LIMIT * sw_kernels._buffers(2) // 5
    assert _scoped_vmem(_kernel_calls(quiet)[0][0])[0] < used < limit
    assert sw_kernels.tile_rows(7204, 14404, jnp.float32, 6, steps=2) == 24

    snap = job.snap.lower(*room).compile()
    table = scopes.origins(snap.as_text())
    under = [o for o in table.values()
             if o.scopes[:2] == ("mpi4jax_tpu.snapshot", "coarsen")]
    assert {o.op_name.rsplit("/", 1)[1] for o in under} >= {"reduce_window", "mul"}
    assert all(scopes.layer_of(o) == scopes.OP_SURFACE for o in under)
    assert snap.as_text().count(" reduce-window(") == 3
    assert "collective-permute" not in snap.as_text()  # each chip its own block
    mem = snap.memory_analysis()
    # a quarter of a field in (and the rows that fill a block), a
    # sixteenth out, its rows filled up to whole vector registers
    assert 3 * 1824 * 14404 * 4 <= mem.argument_size_in_bytes <= (
        3 * 1824 * 14464 * 4 + 4096)
    assert 3 * 1800 * 3600 * 4 <= mem.output_size_in_bytes <= 3 * 1800 * 3712 * 4 + 4096
    assert mem.temp_size_in_bytes == 0
    assert scopes.signature(snap.as_text()).taken == 3 * 1824 * 14404 * 4


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_jobs_save_leaves_the_step_alone_and_its_staging_carries_its_scope(
        v5e, mesh_shape):
    """``make_job`` with a checkpoint, at the benchmark cell's size a
    chip: the call's multistep is the program of a job without one,
    text for text (a save adds a program beside the step, nothing to
    it); the staging program cuts each chip's six blocks into bands of
    rows under ``mpi4jax_tpu.checkpoint/stage``, every band a copy of at
    most ``checkpoint.PIECE_BYTES`` a chip, with no temporary and no
    wire; the restore's program puts them together again under
    ``.../unstage``."""
    from mpi4jax_tpu.utils import checkpoint as ckpt
    from perfbench.harness import scopes

    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px])
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=7200 * py, nx=14400 * px, dx=1250.0, dy=1250.0, ghost=2)
    job = sw.make_job(cfg, comm, 10, checkpoint=sw.Checkpoint(
        "/nowhere", every_calls=32, ahead_bytes=160_000_000))
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(sw.make_init(cfg, comm)))
    assert job.form()["tendencies"] == "padded"

    def instructions(text):
        """A program's instructions without where they were traced from."""
        return [re.sub(r",? metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    text = job.multi.lower(state).compile().as_text()
    bare = sw.make_job(cfg, comm, 10).multi.lower(state).compile().as_text()
    assert instructions(text) == instructions(bare) and len(instructions(text)) > 20
    # every call donates, saved or not: the kernel's operands are its results
    assert "input_output_alias" in text.split("ENTRY")[0]
    assert not _copied(text, _kernels(text)["wide_step"][1])

    stage = job.stage.lower(state).compile()
    pieces = sum(len(rows) for rows in job._plan)
    a_piece = max(hi - lo for rows in job._plan for lo, hi in rows) * 14404 * 4
    assert a_piece <= ckpt.PIECE_BYTES < 2 * a_piece
    assert pieces == 6 * len(job._plan[0]) and job._plan[0][-1][1] == 7204
    table = scopes.origins(stage.as_text())
    under = [o for o in table.values()
             if o.scopes[:2] == ("mpi4jax_tpu.checkpoint", "stage")]
    assert under and all(scopes.layer_of(o) == scopes.OP_SURFACE for o in under)
    assert "collective-permute" not in stage.as_text()
    mem = stage.memory_analysis()
    state_bytes = 6 * 7204 * 14404 * 4
    # the bands' rows and columns filled up to whole tiles of (8, 128)
    assert state_bytes <= mem.output_size_in_bytes <= 1.02 * state_bytes
    assert mem.temp_size_in_bytes < 1 << 20

    a_field = tuple(
        jax.ShapeDtypeStruct(((hi - lo) * py, 14404 * px), jnp.float32,
                             sharding=sharding) for lo, hi in job._plan[0])
    unstage = sw.make_unstage(comm).lower(*a_field).compile()
    table = scopes.origins(unstage.as_text())
    assert any(o.scopes[:2] == ("mpi4jax_tpu.checkpoint", "unstage")
               for o in table.values())
    assert unstage.memory_analysis().output_size_in_bytes >= 7204 * 14404 * 4
    assert unstage.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_with_both_halves_compiles_the_programs_of_its_halves(v5e, mesh_shape):
    """``make_job`` with a snapshot and a checkpoint under one bound, at
    the size of the benchmark cell ``sw-output-restart-1chip`` a chip:
    its three programs are, instruction for instruction, those of the
    jobs that have one half each (the one bound is the host's code, and
    adds nothing to a program); the call's multistep still donates
    through the kernel; and the bound the cell states holds two
    snapshots and a piece, not three snapshots."""
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px])
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=7200 * py, nx=14400 * px, dx=1250.0, dy=1250.0, ghost=2)
    bound = 160_000_000
    snapshot = sw.Snapshot(coarsen=4, lag=4, ahead_bytes=bound)
    checkpoint = sw.Checkpoint("/nowhere", every_calls=32, ahead_bytes=bound)
    both = sw.make_job(cfg, comm, 10, snapshot, lambda *a: None, checkpoint)
    written = sw.make_job(cfg, comm, 10, snapshot, lambda *a: None)
    saved = sw.make_job(cfg, comm, 10, checkpoint=checkpoint)
    assert both.ahead_bytes == both.checkpoint.ahead_bytes == bound
    assert both._plan == saved._plan
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(sw.make_init(cfg, comm)))
    # where the step is the kernel a call takes the row sums' room beside
    # the state and its snapshot program reads that
    room = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(both._room))

    def instructions(program, *args):
        """A program's instructions without where they were traced from."""
        text = program.lower(*args).compile().as_text()
        return text, [re.sub(r",? metadata=\{[^}]*\}", "", line)
                      for line in text.splitlines() if " = " in line]

    text, multi = instructions(both.multi, state, room)
    assert multi == instructions(written.multi, state, room)[1] and len(multi) > 20
    assert "input_output_alias" in text.split("ENTRY")[0]
    assert not _copied(text, _kernels(text)["wide_step"][1])
    assert instructions(both.snap, *room)[1] == instructions(written.snap, *room)[1]
    assert instructions(both.stage, state)[1] == instructions(saved.stage, state)[1]
    # what the bound holds, a chip's share each: copies of 77.76 MB and 4 MiB
    one = 3 * 1800 * 3600 * 4
    a_piece = max(hi - lo for rows in both._plan for lo, hi in rows) * 14404 * 4
    assert one == 77_760_000 and a_piece <= sw.ckpt.PIECE_BYTES
    if py * px == 1:
        assert sum(len(rows) for rows in both._plan) == 606
        assert 2 * one + a_piece <= bound < 3 * one


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_that_watches_itself_adds_a_program_and_leaves_the_step_alone(
        v5e, mesh_shape):
    """``make_job(monitor=)`` at the blocks of the cells
    ``sw-monitored-1chip`` and ``sw-monitored-2x2-weak``: the call's
    multistep is the program of a job without a monitor, instruction for
    instruction (so the accepted cells' programs are what they were);
    the monitor program reads each chip's ``h``, ``u``, ``v`` once, a
    fusion a field, with no copy of a field and no temporary of a
    field's size; on four chips it holds one all-reduce a kind of
    reduction (sum, max, min), each over both mesh axes at once
    (``replica_groups={{0,1,2,3}}``, not one an axis), and on one chip
    none; its reductions lie under ``sw/monitor`` (the programs' layer)
    and its all-reduces under ``mpi4jax_tpu.allreduce`` inside it (the
    op surface's)."""
    from perfbench.harness import scopes

    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px])
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=7200 * py, nx=14400 * px, dx=1250.0 / py, dy=1250.0 / py,
                      ghost=2)
    job = sw.make_job(cfg, comm, 10, monitor=sw.Monitor())
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(sw.make_init(cfg, comm)))

    def instructions(text):
        return [re.sub(r",? metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    text = job.multi.lower(state).compile().as_text()
    bare = sw.make_job(cfg, comm, 10).multi.lower(state).compile().as_text()
    assert instructions(text) == instructions(bare) and len(instructions(text)) > 20
    assert ("collective-permute" in text) == (py * px > 1)
    assert " all-reduce(" not in text  # the step's program holds none

    mon = job.mon.lower(*state[:3]).compile()
    text = mon.as_text()
    field = 7204 * 14404 * 4
    mem = mon.memory_analysis()
    assert mem.argument_size_in_bytes >= 3 * field and mem.temp_size_in_bytes < 1 << 20
    assert scopes.signature(text) == (3 * field, 16)
    entry = text[text.index("\nENTRY"):]
    assert not re.findall(r"= f32\[7204,14404\]\S* copy\(", text)
    # each field is the operand of one fusion, and of nothing else
    params = re.findall(r"%([\w.\-]+) = f32\[7204,14404\]\S* parameter\(", entry)
    assert len(params) == 3
    for name in params:
        readers = [line for line in entry.splitlines()
                   if f"%{name}" in line.split(" = ", 1)[-1] and " parameter(" not in line]
        assert len(readers) == 1 and " fusion(" in readers[0], readers
    reduces = [line for line in entry.splitlines() if " all-reduce(" in line]
    if py * px == 1:
        assert not reduces and "collective" not in text
        return
    assert len(reduces) == 3
    assert all("replica_groups={{0,1,2,3}}" in line for line in reduces)
    kinds = set()
    for line in reduces:
        region = re.search(r"to_apply=%([\w.\-]+)", line)[1]
        body = re.search(rf"^%{re.escape(region)} \(.*?^\}}", text, re.S | re.M)[0]
        kinds |= set(re.findall(r" (add|maximum|minimum)\(", body))
    assert kinds == {"add", "maximum", "minimum"}
    table = scopes.origins(text)
    lines = {name: rest for name, rest in scopes._INSTRUCTION.findall(text)}
    for name, origin in table.items():
        if " all-reduce(" in lines[name]:
            assert "sw/monitor/mpi4jax_tpu.allreduce" in origin.op_name
            assert scopes.layer_of(origin) == scopes.OP_SURFACE
        elif " fusion(" in lines[name] and "reduce" in name:
            assert "sw/monitor" in origin.op_name
            assert scopes.layer_of(origin) == scopes.PROGRAMS


# -- the differentiated run (PR 54) ---------------------------------------


@functools.cache
def _compiled_gradient(v5e, mesh_shape, ny, nx, calls, steps):
    """``make_gradient``'s two programs at ``ny`` x ``nx`` cells a chip,
    observed over 2 x 2 cells, compiled for the described chips:
    ``(forward, backward)``."""
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px],
    )
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=ny * py, nx=nx * px, dx=2500.0, dy=2500.0, ghost=2)
    field = jax.ShapeDtypeStruct(
        (cfg.ny, cfg.nx), jnp.float32,
        sharding=NamedSharding(mesh, jax.P("y", "x")))
    obs = jax.ShapeDtypeStruct(
        (calls + 1, cfg.ny // 2, cfg.nx // 2), jnp.float32,
        sharding=NamedSharding(mesh, jax.P(None, "y", "x")))
    gradient = sw.make_gradient(cfg, comm, calls=calls, num_steps=steps, observe=2)
    args = (field, field, field, obs)
    _cost, *kept = jax.eval_shape(gradient.forward, *args)
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    kept = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), kept)
    return (gradient.forward.lower(*args).compile(),
            gradient.backward.lower(*args, *kept).compile())


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_call_that_is_not_differentiated_compiles_to_what_it_did(
        v5e, mesh_shape, monkeypatch):
    """The step's and the exchange's written-out derivatives leave nothing in
    a program nobody differentiates: ``make_multistep``'s compiled text
    is, instruction for instruction, the text of the same program with
    both wrappers taken off (``_with_derivative`` handing back the
    kernel's walk, ``_transposable`` calling the exchange)."""
    from mpi4jax_tpu.parallel import halo

    def instructions(text):
        return [re.sub(r",? metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    text = _compiled_multistep(v5e, mesh_shape, 2, 1800, 3600, 10).as_text()
    monkeypatch.setattr(
        sw, "_with_derivative",
        lambda forward, keep, tangent, backward, scope: forward)
    monkeypatch.setattr(
        halo, "_transposable",
        lambda forward, backward, arrs, token: forward(list(arrs), token))
    bare = _compiled_multistep.__wrapped__(
        v5e, mesh_shape, 2, 1800, 3600, 10).as_text()
    assert instructions(text) == instructions(bare)
    assert len(instructions(text)) > 20 and _kernel_calls(text)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_backward_sweep_moves_no_block_for_an_exchange(v5e, mesh_shape):
    """``jax.vjp`` through the exchange runs the adjoint exchange
    (``parallel/halo.py _adjoint``), slab-sized work like the exchange:
    under a ``mpi4jax_tpu.halo_*`` scope the backward sweep's text holds
    no ``copy``, ``pad``, ``add`` or other pass over a block; what
    carries such a scope and a block's shape is a write in place (a
    ``dynamic-update-slice``, or a fusion whose only block-shaped
    instruction is one: the lane-tile strip of ``_place``).  By the
    rules of what the exchange is made of, each slab sliced from a
    block came back as a block of zeros with the slab padded into it
    and an add of two blocks (PERF.md, PR 54).  The forward sweep's
    kernel walks stand as every cell's: two steps a walk."""
    ny, nx = 1800, 3600
    forward, backward = _compiled_gradient(v5e, mesh_shape, ny, nx, 1, 4)
    block = f"f32[{ny + 4},{nx + 4}]"
    text = backward.as_text()
    computations = dict(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M))
    under, transposed = 0, 0
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        shaped = re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = \(?{re.escape(block)}", line)
        if not name or "mpi4jax_tpu.halo_" not in name[1] or not shaped:
            continue
        opcode = re.match(
            r".*? ([a-z][a-z\-]*)\(", line.split(" = ", 1)[1])[1]
        if opcode in ("parameter", "get-tuple-element", "bitcast"):
            continue
        under += 1
        transposed += "/transpose/" in name[1]
        if opcode == "fusion":
            body = computations[re.search(r"calls=%([\w.\-]+)", line)[1]]
            whole = [
                found[1] for found in re.finditer(
                    rf"= {re.escape(block)}\S* ([a-z\-]+)\(", body)
                if found[1] not in ("parameter", "dynamic-update-slice", "bitcast")]
            assert not whole, (line, whole)
        else:
            assert opcode == "dynamic-update-slice", line
    assert under and transposed  # the adjoint exchange is there, and named
    # both sweeps run the kernel: the window as every cell's program
    # runs it, and a call's steps again
    assert _kernel_calls(forward.as_text()) and _kernel_calls(text)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_steps_derivative_is_a_kernel_call(v5e, mesh_shape):
    """Where the step is the kernel its derivative is one
    (``sw_kernels.wide_step_vjp``): under ``sw/adjoint/step_vjp`` the
    backward sweep's text holds the kernel's calls, the sweep's scan's
    and the first step's, and no pass over a block: what carries that
    scope and a block's shape is a kernel call or a write in place (the
    exchange's, forwards over the kept fields' ghosts and transposed).
    The array code's derivative was a hundred and forty fusions there
    (PERF.md, PR 54)."""
    ny, nx = 1800, 3600
    _, backward = _compiled_gradient(v5e, mesh_shape, ny, nx, 1, 4)
    block = f"f32[{ny + 4},{nx + 4}]"
    text = backward.as_text()
    computations = dict(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M))
    calls = 0
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        shaped = re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = \(?{re.escape(block)}", line)
        if not name or "sw/adjoint/step_vjp" not in name[1] or not shaped:
            continue
        opcode = re.match(r".*? ([a-z][a-z\-]*)\(", line.split(" = ", 1)[1])[1]
        if opcode in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if opcode == "custom-call":
            assert "tpu_custom_call" in line and "wide_step_vjp" in name[1], line
            # the kept fields and the six cotangents in, six out in place
            assert len(re.findall(re.escape(block), line.split(" = ", 1)[1])) == 15
            calls += 1
        elif opcode == "fusion":
            body = computations[re.search(r"calls=%([\w.\-]+)", line)[1]]
            whole = [
                found[1] for found in re.finditer(
                    rf"= {re.escape(block)}\S* ([a-z\-]+)\(", body)
                if found[1] not in ("parameter", "dynamic-update-slice", "bitcast")]
            assert not whole, (line, whole)
        else:
            assert opcode == "dynamic-update-slice", line
    assert calls == 2  # the sweep's scan's and the first step's


def _the_cells_gradient(v5e):
    """``sw-adjoint-1chip``'s two programs at the cell's own size and
    window: ``(forward, backward, calls)``."""
    import json

    with open("perfbench/workloads/sw-adjoint-1chip.json") as f:
        grid = json.load(f)["grid"]
    with open("perfbench/configs/shallow-water-adjoint.json") as f:
        calls = json.load(f)["window"]["calls"]
    return (*_compiled_gradient(v5e, (1, 1), grid["ny"], grid["nx"], calls, 10),
            calls)


def test_the_cells_gradient_fits_a_chip_with_room(v5e):
    """``sw-adjoint-1chip``'s two programs at the cell's own size and
    window: the backward sweep's peak by the compiler's buffer
    assignment under 14e9 bytes (PR 54's line for taking a call
    off the window), and over a quarter of a chip.  Pinned: 9.45e9
    (12.64e9 until PR 58, 6.64e9 of them the ``cost`` scope's broadcast
    of a coarse cotangent over its 2 x 2 cells, which a matrix product
    spreads since).  What it is made of stands inside a call's scan: arguments
    3.08e9 (the four call starts' 24 arrays, the observations, the last
    ``h``, the three fields), results 0.32e9, temporaries 6.27e9: the
    second level, three stacks of ten kept fields (3.12e9; fields, no
    tendencies, since the adjoint kernel reads no more), and some
    thirty blocks of cotangents, carries and copies round the loop
    (PERF.md, PR 58; ``ROADMAP.md`` S29)."""
    forward, backward, _calls = _the_cells_gradient(v5e)
    peak = backward.memory_analysis().peak_memory_in_bytes
    assert 0.25 * 16e9 < peak < 14e9, peak
    assert peak == pytest.approx(9.45e9, rel=0.01)
    kept = re.findall(r"f32\[10,3604,7204\]", backward.as_text().split("ENTRY")[1])
    assert kept  # a call's ten states, stacked: fields, no tendencies
    whiles = [line for line in backward.as_text().splitlines()
              if re.search(r" while\(", line)]
    assert whiles and all(
        len(re.findall(r"f32\[10,3604,7204\]", line.split(" while(")[0])) == 3
        for line in whiles)
    assert forward.memory_analysis().peak_memory_in_bytes < peak


def test_the_cells_sweep_spreads_a_coarse_cotangent_by_a_matrix_product(v5e):
    """The transpose of the observation operator in the backward sweep
    at the cell's size (``shallow_water._spread``): for each observed
    state one matrix product at the highest precision, a register of
    coarse columns times the 0/1 matrix, every one under
    ``sw/adjoint/cost`` (``adjoint_device_share.sw`` books it there, and
    ``adjoint_hbm_roofline_share``'s sweep is handed nothing new), as
    is every instruction that holds one of the spread's arrays (the
    coarse rows in registers, the rows spread, the rows copied).  Under
    that scope the text holds no array whose last dimension is 2 (the
    broadcast's ``f32[1800,2,3600,2]`` lay two columns to a tile of 128
    lanes: 6.64e9 bytes, 18 ms a transpose; PERF.md, PR 58) and no
    ``reduce-window`` with a base dilation (jax's own rule for a
    window's sum, wrong at this size on a v5e: PERF.md, PR 54)."""
    _forward, backward, calls = _the_cells_gradient(v5e)
    text = backward.as_text()
    products, under_cost = 0, 0
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if not name or " = " not in line:
            continue
        body = line.split(" = ", 1)[1]
        shapes = re.findall(r"[a-z]+\d*\[([\d,]+)\]", body.split(", metadata=")[0])
        if {"1800,29,128", "1800,29,256", "1800,2,7200"} & set(shapes):
            assert "sw/adjoint/cost" in name[1], line
        if "sw/adjoint/cost" not in name[1]:
            assert " convolution(" not in line, line
            continue
        under_cost += 1
        assert not [s for s in shapes if s.split(",")[-1] == "2"], line
        if " reduce-window(" in line:
            assert "lhs_dilate" not in line, line
        if " convolution(" in line:
            assert "operand_precision={highest,highest}" in line, line
            assert body.startswith("f32[1800,29,256]"), line
            products += 1
    assert under_cost > products == calls + 1


def _instructions_digest(text):
    """A digest of a compiled program's instructions as a multiset:
    every instruction's line less its metadata, the numbers that tell
    two instructions of a kind apart, and a kernel's own text (which
    names the lines of its source), sorted."""
    import hashlib

    lines = []
    for line in text.splitlines():
        if " = " not in line:
            continue
        line = re.sub(r",? metadata=\{[^}]*\}", "", line)
        if "tpu_custom_call" in line:
            line = line.split("custom_call_target")[0] + re.sub(
                r".*(operand_layout_constraints=\{[^}]*\}\}?).*", r"\1", line)
        line = re.sub(r", frontend_attributes=\{kernel_metadata=\{\}\}", "", line)
        line = re.sub(r"(%[A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)[\.\d]*", r"\1", line)
        line = re.sub(r"constantsunk[\.\w]*", "constantsunk", line)
        lines.append(line)
    return len(lines), hashlib.sha256("\n".join(sorted(lines)).encode()).hexdigest()


def test_the_cells_gradient_is_the_programs_it_was_before_the_tangent_kernel(v5e):
    """``sw-adjoint-1chip``'s two programs run no line of forward mode:
    at the cell's size they compile to the instructions they compiled to
    before the tangent kernel was written (PR 60: the same multisets at
    commit 12c86a8 and after, by ``_work/digest60.py`` in either tree),
    the adjoint kernel's call among them.  Pinned, so that a change to
    reverse mode or to the forward sweep says so here: whoever means one
    computes the new digests and writes them down."""
    forward, backward, _calls = _the_cells_gradient(v5e)
    assert _instructions_digest(forward.as_text()) == (
        656, "b4d527d9d7f9d6133615d85453c453489b6c246f445888c0f9962c1bc3e9d35d")
    assert _instructions_digest(backward.as_text()) == (
        3077, "a87b3008fa57bb0d4ea7f67744f1aead3fbb1bb014e4fdf4c942494b130224e7")


# -- the linearised run (PR 59) ---------------------------------------------


@functools.cache
def _compiled_inner_loop(v5e, mesh_shape, ny, nx, calls, steps):
    """An inner-loop iteration's three programs at ``ny`` x ``nx`` cells
    a chip, observed over 2 x 2 cells, compiled for the described
    chips: ``(tangent, adjoint, update)``."""
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=v5e.devices[:py * px],
    )
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=ny * py, nx=nx * px, dx=2500.0, dy=2500.0, ghost=2)
    sharding = NamedSharding(mesh, jax.P("y", "x"))
    field = jax.ShapeDtypeStruct((cfg.ny, cfg.nx), jnp.float32, sharding=sharding)
    obs = jax.ShapeDtypeStruct(
        (calls + 1, cfg.ny // 2, cfg.nx // 2), jnp.float32,
        sharding=NamedSharding(mesh, jax.P(None, "y", "x")))
    how = dict(calls=calls, num_steps=steps, observe=2)
    gradient = sw.make_gradient(cfg, comm, **how)
    _cost, starts, _last = jax.eval_shape(gradient.forward, field, field, field, obs)
    starts = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), starts)
    product = sw.make_product(cfg, comm, weight=0.11, **how)
    _begin, step = sw.make_inner_step(cfg, comm, 0.11)
    triple = (field,) * 3
    one = jax.ShapeDtypeStruct(mesh_shape, jnp.float32, sharding=sharding)
    return (product.tangent.lower(*triple, starts, *triple).compile(),
            product.adjoint.lower(*triple, starts, obs).compile(),
            step.lower(triple, triple, triple, triple, one, one).compile())


def _tangent_kernel_calls(text):
    """The lines of a compiled program's text that call the step's
    tangent kernel."""
    return [line for line in text.splitlines() if "tpu_custom_call" in line
            and re.match(r"\s*(?:ROOT )?%wide_step_jvp(?:\.\d+)? =", line)]


def _loop_bodies(text, holding):
    """The computations of a compiled program's text that a ``while``
    runs as its body and that hold ``holding``."""
    bodies = set(re.findall(r"\bwhile\(.*?body=%([\w.\-]+)", text))
    return [body for name, body in re.findall(
        r"^%([\w.\-]+) \(.*?\) -> .*? \{$(.*?)^\}", text, re.M | re.S)
        if name in bodies and holding in body]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_tangent_sweep_runs_the_forward_and_the_tangent_kernels(v5e, mesh_shape):
    """``make_tangent`` where the step is the kernel: the window's walks
    run as the forward kernel and each step's tangent as the tangent
    kernel (``sw_kernels.wide_step_jvp``: a walk of two is the forward
    kernel's walk of two, the tangent kernel at the state it started
    from, the forward kernel's walk of one for the state between, and
    the tangent kernel there), every instruction of the sweep under
    ``sw/adjoint/tangent``, the exchanges' tangents under the exchange's
    own scopes inside ``jvp(...)`` and none under the ``transpose``
    marker; beside neighbours both the state's and the tangents' slabs
    go over the wire.  A walk copies three fields and no more: the kept
    ``h``, ``u``, ``v`` get fresh ghosts for the tangent kernel while
    the walk of two still reads them as they are.  The adjoint sweep
    beside it is the gradient's: the adjoint kernel and the adjoint
    exchange."""
    ny, nx = 1800, 3600
    tangent, adjoint, update = _compiled_inner_loop(v5e, mesh_shape, ny, nx, 1, 4)
    text = tangent.as_text()
    assert _kernel_calls(text) and _tangent_kernel_calls(text)
    assert "wide_step_vjp" not in text
    body, = _loop_bodies(text, "wide_step_jvp")
    assert len(_tangent_kernel_calls(body)) == 2
    # the walk of two in place; the walk of one, whose state the walk
    # of two reads as well, not
    assert sorted(_aliased_in_place(line, places)
                  for line, _fields, _text, places in _kernel_calls(body)) == [False, True]
    field = rf"f32\[{ny + 4},{nx + 4}\]"
    assert len(re.findall(rf"= {field}\S* copy\(", body)) <= 3
    assert not re.findall(rf"= \({field}\S*, {field}\S*, u32\[\]\S*\) copy-start\(", body)
    names = re.findall(r'op_name="([^"]*)"', text)
    scoped = [name for name in names if "sw/adjoint/" in name]
    assert scoped and all("sw/adjoint/tangent" in name for name in scoped)
    # every instruction that is named at all: a trace places each event
    # of the sweep by its ``op_name``
    elsewhere = {name for line in text.splitlines() if " parameter(" not in line
                 for name in re.findall(r'op_name="([^"]*)"', line)
                 if "sw/adjoint/" not in name}
    assert all(re.fullmatch(r"jit\(tangent\)/shard_map(/\w+\.\d+)?", name)
               for name in elsewhere), elsewhere
    exchanged = [name for name in names if "mpi4jax_tpu.halo_" in name]
    assert any("jvp(mpi4jax_tpu.halo_exchange_2d)/unpack" in name for name in exchanged)
    assert not any("/transpose/" in name for name in exchanged)
    assert ("collective-permute" in text) == (mesh_shape != (1, 1))
    if mesh_shape != (1, 1):
        wired = [line for line in text.splitlines()
                 if " collective-permute-start(" in line]
        # the tangents' (and the kept fields') exchanges, and the slabs
        # of the state that the forward kernel walks from
        assert any("jvp(mpi4jax_tpu.halo_exchange_2d)/wire" in line for line in wired)
        assert any("/mpi4jax_tpu.halo_slabs_2d/wire" in line for line in wired)
    backward = adjoint.as_text()
    assert "wide_step_vjp" in backward and "/transpose/unpack" in backward
    assert "wide_step_jvp" not in backward
    assert " all-reduce(" not in text and " all-reduce(" not in backward
    # the loop's two dot products are the mesh's
    reduces = [line for line in update.as_text().splitlines()
               if " all-reduce(" in line]
    assert (0 < len(reduces) <= 2) if mesh_shape != (1, 1) else not reduces


def test_the_cells_inner_loop_fits_a_chip_with_room(v5e):
    """``sw-incremental-1chip``'s three programs at the cell's own size
    and window: each sweep's peak by the compiler's buffer assignment,
    with the loop's vectors held beside it (``vector_bytes``, 1.24e9; a
    sweep's arguments are the first guess and the trajectory's first
    level), under 14e9 bytes (PR 54's line for taking a call off the
    window) and over a quarter of a chip.  The adjoint sweep is the
    gradient's backward sweep with a vector for the residuals, and
    peaks where that does: 9.35e9, the cell's fullest program.  The
    tangent sweep's peak is pinned: 7.40e9 since its steps are the
    tangent kernel's (3.16e9 of it arguments, the first guess and the
    four call starts; a walk holds two states and a half besides its
    own: the tangents, the state between its two steps and the three
    kept fields with fresh ghosts), 8.04e9 while they were the array
    code's fusions (PERF.md, PRs 59, 60): with the loop's vectors
    8.6e9, over a quarter of a chip and under the adjoint sweep's, which
    holds the cell's memory as before (``incremental_memory_share``)."""
    import json

    with open("perfbench/workloads/sw-incremental-1chip.json") as f:
        grid = json.load(f)["grid"]
    with open("perfbench/configs/shallow-water-incremental.json") as f:
        calls = json.load(f)["window"]["calls"]
    tangent, adjoint, update = _compiled_inner_loop(
        v5e, (1, 1), grid["ny"], grid["nx"], calls, 10)
    vectors = 4 * 3 * grid["ny"] * grid["nx"] * 4
    peaks = {key: program.memory_analysis().peak_memory_in_bytes
             for key, program in (("tangent", tangent), ("adjoint", adjoint))}
    for key, peak in peaks.items():
        assert 0.25 * 16e9 < peak + vectors < 14e9, (key, peak)
    assert peaks["tangent"] == pytest.approx(7.40e9, rel=0.02), peaks
    assert peaks["tangent"] < peaks["adjoint"]
    _forward, backward, _calls = _the_cells_gradient(v5e)
    assert peaks["adjoint"] == pytest.approx(
        backward.memory_analysis().peak_memory_in_bytes, rel=0.02)
    assert update.memory_analysis().temp_size_in_bytes < 1 << 22
