"""Device time by the layer that emitted it: the compiled program's
text read for scopes and source lines, programs kept apart, and the two
readers on made-up traces whose shares are computed by hand."""

import re
import types

import jax
import pytest

from perfbench import run
from perfbench.harness import files, scopes
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import make_copy

CHIP = "/device:TPU:0"
HALO = "mpi4jax_tpu.halo_exchange_2d"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(tmp_path_factory.mktemp("perfbench_scopes"))


def _session(copy, cell):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    return driver.setup(run.Context(config, workload, 11, jax.devices(), bench))


@pytest.fixture(scope="module")
def solver(copy):
    """The toy solver cell's session (24x48 cells, ghost 2, one CPU
    device) and the text of its compiled multistep."""
    session = _session(copy, "sw-toy-1x1")
    return session, session.multi.lower(session.state).compile().as_text()


@pytest.fixture(scope="module")
def table(copy):
    """The toy table's session on 2x2 CPU devices and the texts of its
    halo and 4 MiB allreduce programs."""
    session = _session(copy, "coll-toy")
    texts = {
        name: session.programs[name].lower(session.inputs[name]).compile().as_text()
        for name in ("halo-1804x3604", "allreduce-4MiB")}
    return session, texts


def _lines(text):
    """``{instruction: its line}``: what the chip's trace names an event by."""
    return {name: line.strip() for line, name in
            re.findall(r"^\s*(?:ROOT\s+)?(%([\w.\-]+) = .*)$", text, re.M)}


def _pick(text, want):
    """The line of the first instruction whose origin and line satisfy ``want``."""
    lines = _lines(text)
    for name, origin in scopes.origins(text).items():
        if want(origin, lines[name]):
            return lines[name]
    raise AssertionError("the program has no such instruction")


def _trace(executions, chips=(CHIP,)):
    """A trace of ``executions``, each a list of ``(event name, ns)``,
    run one after the other on every chip."""
    made = Trace()
    for chip in chips:
        t = 0.0
        made.device_ops[chip], made.modules[chip] = [], []
        for events in executions:
            start = t
            for name, ns in events:
                made.device_ops[chip].append(Event(name, t, float(ns)))
                t += ns
            made.modules[chip].append(Event("jit_local(1)", start, t - start))
            t += 7.0  # idle between programs
    return made


def _view(session, made, rows):
    return types.SimpleNamespace(
        session=session, trace=made,
        traced=[run.Sample(row, 0.0, 1.0) for row in rows])


# -- the compiled program's text ---------------------------------------


def test_origins_of_the_solvers_program(solver):
    _, text = solver
    table = scopes.origins(text)
    chains = {o.scopes for o in table.values() if o.scopes}
    assert (HALO, "pack") in chains and (HALO, "unpack") in chains
    assert all(chain[0] == HALO for chain in chains)
    # on one device the permutes are self-permutes: what survives of the
    # wire, if anything, lies under the sendrecv's own scope
    assert {c for c in chains if c[1] == "wire"} <= {
        (HALO, "wire", "mpi4jax_tpu.sendrecv")}
    unpack = [o for o in table.values() if o.scopes == (HALO, "unpack")]
    assert {o.source.split(":")[0] for o in unpack} == {"mpi4jax_tpu/parallel/halo.py"}
    # the callers lead from the ghost write up to the model's step
    assert any(c.startswith("mpi4jax_tpu/models/shallow_water.py:")
               for o in unpack for c in o.callers)
    model = [o for o in table.values()
             if o.source and o.source.startswith("mpi4jax_tpu/models/shallow_water.py:")]
    assert model and all(scopes.layer_of(o) == scopes.PROGRAMS
                         for o in model if not o.scopes)
    assert any(o.op_name and o.op_name.endswith("/scatter-add") for o in model)
    assert {scopes.layer_of(o) for o in table.values()} >= {
        scopes.OP_SURFACE, scopes.PROGRAMS, scopes.UNATTRIBUTED}


def test_origins_of_a_halo_across_chips_have_all_three_phases(table):
    _, texts = table
    origins = scopes.origins(texts["halo-1804x3604"])
    phases = {o.scopes[1] for o in origins.values() if len(o.scopes) > 1}
    assert phases == {"pack", "wire", "unpack"}
    permutes = [o for name, o in origins.items()
                if scopes.is_collective(_lines(texts["halo-1804x3604"])[name])]
    assert permutes and all(
        o.scopes == (HALO, "wire", "mpi4jax_tpu.sendrecv") for o in permutes)
    # the benchmark's chain write is the caller's, by its source line
    chain = [o for o in origins.values()
             if o.source and "perfbench/drivers/collectives.py" in o.source
             and not o.scopes]
    assert chain and all(scopes.layer_of(o) == scopes.CALLER for o in chain)


HEADER = """HloModule jit_f, is_scheduled=true

FileNames
1 "/work/perfbench/drivers/collectives.py"
2 "/work/mpi4jax_tpu/parallel/halo.py"
3 "/work/mpi4jax_tpu/models/shallow_water.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=95 end_line=95 column=1 end_column=2}
2 {file_name_id=3 function_name_id=1 line=456 end_line=456 column=1 end_column=2}
3 {file_name_id=2 function_name_id=1 line=159 end_line=159 column=1 end_column=2}
4 {file_name_id=3 function_name_id=1 line=509 end_line=509 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=3 parent_frame_id=3}
4 {file_location_id=4 parent_frame_id=2}

"""
COPY = "%copy.52 = f32[4]{0:T(128)} copy(f32[4]{0:T(128)} %p)"
WRITES_GHOSTS = HEADER + """ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0:T(128)} parameter(0)
  ROOT %copy.52 = f32[4]{0:T(128)} copy(%p), metadata={op_name="jit(f)/mpi4jax_tpu.halo_exchange_2d/unpack/scatter" stack_frame_id=3}
}
"""
UPDATES_FIELD = HEADER + """ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0:T(128)} parameter(0)
  %mark = f32[4]{0:T(128)} copy(%p), metadata={op_name="jit(f)/mpi4jax_tpu.allreduce/psum"}
  ROOT %copy.52 = f32[4]{0:T(128)} copy(%mark), metadata={op_name="jit(f)/while/body/scatter-add" source_file="/work/mpi4jax_tpu/models/shallow_water.py" source_line=509}
}
"""


def test_origins_read_the_header_tables_and_the_older_form():
    ghosts = scopes.origins(WRITES_GHOSTS, root="/work")["copy.52"]
    assert ghosts.scopes == (HALO, "unpack")
    assert ghosts.source == "mpi4jax_tpu/parallel/halo.py:159"
    # jax 0.9.0 prints the parent one too high: frame 3's is frame 2, whose is 1
    assert ghosts.callers == ("mpi4jax_tpu/models/shallow_water.py:456",
                              "perfbench/drivers/collectives.py:95")
    field = scopes.origins(UPDATES_FIELD, root="/work")
    assert field["copy.52"].source == "mpi4jax_tpu/models/shallow_water.py:509"
    assert scopes.layer_of(field["copy.52"]) == scopes.PROGRAMS
    assert scopes.layer_of(field["p"]) == scopes.UNATTRIBUTED
    assert scopes.layer_of(ghosts) == scopes.OP_SURFACE
    # a path outside the checkout stays as it is, and is the caller's
    outside = scopes.origins(WRITES_GHOSTS.replace("mpi4jax_tpu.halo", "x.halo"))
    assert outside["copy.52"].source == "/work/mpi4jax_tpu/parallel/halo.py:159"
    assert scopes.layer_of(outside["copy.52"]) == scopes.CALLER


def test_two_programs_that_share_an_instruction_name_are_kept_apart():
    made = _trace([[(COPY, 30)], [(COPY, 70)]])
    rows = scopes.attribute(
        made, ["ghosts", "field"],
        {"ghosts": WRITES_GHOSTS, "field": UPDATES_FIELD}.__getitem__)
    assert [(r.program, r.layer, r.opcode, r.seconds, r.events) for r in rows] == [
        ("field", scopes.PROGRAMS, "copy", 70e-9, 1),
        ("ghosts", scopes.OP_SURFACE, "copy", 30e-9, 1)]
    assert scopes.phase_of(rows[1]) == "unpack" and scopes.phase_of(rows[0]) is None


@pytest.mark.parametrize("executions,keys,why", [
    ([[(COPY, 30)], [(COPY, 70)]], ["ghosts"], "executed 2 programs, the traced batches ran 1"),
    ([[(COPY, 30), ("%fusion.3 = f32[4]{0} fusion(%p)", 5)]], ["ghosts"],
     "the text of 'ghosts' has no fusion.3"),
])
def test_a_trace_of_other_programs_is_refused_with_the_reason(
        capsys, executions, keys, why):
    texts = {"ghosts": WRITES_GHOSTS}
    assert scopes.attribute(_trace(executions), keys, texts.__getitem__) is None
    out = capsys.readouterr().out
    assert "do not belong together" in out and why in out


def test_an_event_in_no_programs_execution_is_refused(capsys):
    made = _trace([[(COPY, 30)]])
    made.device_ops[CHIP].append(Event(COPY, 500.0, 10.0))
    assert scopes.by_execution(made, ["ghosts"]) is None
    assert "lies in no program's execution" in capsys.readouterr().out


def test_a_program_without_a_scope_is_refused_and_not_reported_as_zero(capsys):
    bare = UPDATES_FIELD.replace("mpi4jax_tpu.allreduce", "allreduce")
    assert scopes.attribute(_trace([[(COPY, 70)]]), ["field"], lambda key: bare) is None
    assert "carries no mpi4jax_tpu.<op> scope" in capsys.readouterr().out


@pytest.mark.parametrize("event,opcode,collective", [
    ("%psum_invariant.17 = f32[536870912]{0:T(1024)} all-reduce(f32[536870912]{0:T(1024)} "
     "%get-tuple-element.61), channel_id=1, replica_groups={{0,1,2,3}}", "all-reduce", True),
    ("%copy.52 = f32[1804,3604]{0,1:T(8,128)} copy(f32[1804,3604]{0,1:T(8,128)S(1)} "
     "%scatter.72)", "copy", False),
    ("%collective-permute-done.2 = f32[2,3604]{1,0:T(2,128)S(1)} collective-permute-done("
     "(f32[2,3604]{1,0:T(2,128)S(1)}, f32[2,3604]{1,0:T(2,128)S(1)}, u32[]{:S(2)}) "
     "%collective-permute-start.2)", "collective-permute-done", True),
    ("%collective-permute-start.2 = (f32[2,3604]{1,0:T(2,128)S(1)}, f32[2,3604]{1,0:T(2,128)"
     "S(1)}, u32[]{:S(2)}, /*index=3*/u32[]{:S(2)}) collective-permute-start(f32[2,3604] %c)",
     "collective-permute-start", True),
    ("%all-gather.13 = f32[4194304]{0:T(1024)S(1)} all-gather(f32[1048576] %g), "
     "channel_id=2", "all-gather", True),
    ("%all_to_all.23 = f32[4,1,262144]{2,1,0:T(1,128)S(1)} all-to-all(%x)", "all-to-all", True),
    ("%all-reduce-scatter.1 = f32[8]{0} fusion(f32[32]{0} %x), kind=kLoop", "fusion", False),
    ("%slice-start = ((f32[1804,3604]{1,0:T(8,128)}), f32[456,3604]{1,0:T(8,128)S(1)}, "
     "s32[]{:S(2)}) async-start(f32[1804,3604]{1,0:T(8,128)} %g), calls=%async_computation",
     "async-start", False),
    ("traced_window", None, False),
])
def test_a_collective_is_told_by_its_opcode(event, opcode, collective):
    assert scopes.opcode(event) == opcode
    assert scopes.is_collective(event) is collective


# -- the two readers on made-up traces ----------------------------------


def test_the_solvers_share_is_what_the_hand_count_gives(solver, capsys):
    session, text = solver
    ghosts = _pick(text, lambda o, line: o.scopes == (HALO, "unpack"))
    slab = _pick(text, lambda o, line: o.scopes == (HALO, "pack"))
    update = _pick(text, lambda o, line: not o.scopes and o.source
                   and o.source.startswith("mpi4jax_tpu/models/"))
    bare = _pick(text, lambda o, line: o.op_name is None and " parameter(" not in line)
    once = [(ghosts, 30), (slab, 10), (update, 50), (bare, 10)]
    reader = files.load_module("layer_metrics", "op_surface_device_share.sw")
    # the toy cell's batch is two calls of the program: two executions
    value = reader.read(_view(session, _trace([once, once]), ["multistep"]))
    assert value == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert "programs 50.000 %, op surface 40.000 %, unattributed 10.000 %" in out
    assert "mpi4jax_tpu.halo_exchange_2d/unpack | mpi4jax_tpu/parallel/halo.py:" in out
    # 2 x 30 ns over the 2 x 10 steps of the batch
    assert "| 0.003 | 30.000 | 0.1" in out


def test_the_solvers_reader_reports_nothing_for_another_trace(solver, capsys):
    session, text = solver
    ghosts = _pick(text, lambda o, line: o.scopes == (HALO, "unpack"))
    reader = files.load_module("layer_metrics", "op_surface_device_share.sw")
    assert reader.read(_view(session, _trace([[(ghosts, 30)]]), ["multistep"])) is None
    assert "executed 1 programs, the traced batches ran 2" in capsys.readouterr().out


def test_the_tables_tax_is_what_the_hand_count_gives(table, capsys):
    session, texts = table
    halo, allreduce = texts["halo-1804x3604"], texts["allreduce-4MiB"]

    def caller(o, line):
        return (not o.scopes and o.source is not None
                and "perfbench/drivers/collectives.py" in o.source)

    halo_events = [
        (_pick(halo, lambda o, line: o.scopes[1:2] == ("pack",)), 10),
        (_pick(halo, lambda o, line: scopes.is_collective(line)), 40),
        (_pick(halo, lambda o, line: o.scopes[1:2] == ("wire",)
               and not scopes.is_collective(line)), 5),
        (_pick(halo, lambda o, line: o.scopes[1:2] == ("unpack",)), 20),
        (_pick(halo, lambda o, line: o.op_name is None and " parameter(" not in line), 15),
        (_pick(halo, caller), 10),
    ]
    allreduce_events = [
        (_pick(allreduce, lambda o, line: scopes.is_collective(line)), 95),
        (_pick(allreduce, caller), 5),
    ]
    made = _trace([halo_events, allreduce_events], chips=(CHIP, "/device:TPU:1"))
    reader = files.load_module("layer_metrics", "op_tax_device_share.coll")
    value = reader.read(_view(session, made, ["halo-1804x3604", "allreduce-4MiB"]))
    # pack 10 + the wire's own 5 + unpack 20 + unattributed 15, of 200 ns busy
    assert value == pytest.approx(25.0)
    out = capsys.readouterr().out
    assert "row halo-1804x3604:" in out and "tax 50.000 % of it" in out
    assert ("wire (collective) 40.000 %, unpack 20.000 %, unattributed 15.000 %, "
            "pack 10.000 %, caller 10.000 %, wire 5.000 %") in out
    assert "row allreduce-4MiB:" in out and "tax 0.000 % of it" in out
    assert "not split (collective) 95.000 %, caller 5.000 %" in out


def test_the_tables_reader_reports_nothing_without_scopes(table, capsys):
    session, texts = table
    bare = types.SimpleNamespace(
        rows=session.rows, units=session.units, inputs=session.inputs,
        programs={"allreduce-4MiB": session._program(
            session.rows["allreduce-4MiB"],
            files.load_module("drivers", "collectives").plain_op(
                session.rows["allreduce-4MiB"], session.grid))})
    text = bare.programs["allreduce-4MiB"].lower(
        session.inputs["allreduce-4MiB"]).compile().as_text()
    event = _pick(text, lambda o, line: scopes.is_collective(line))
    reader = files.load_module("layer_metrics", "op_tax_device_share.coll")
    made = _trace([[(event, 95)]])
    assert reader.read(_view(bare, made, ["allreduce-4MiB"])) is None
    assert "carries no mpi4jax_tpu.<op> scope" in capsys.readouterr().out
