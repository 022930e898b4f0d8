"""A floor is read from the program's own signature: the bytes a compiled
text says a program, or one kernel call of it, is handed and hands back,
and the readers that stand on them, on texts and traces made by hand of
programs that do not exist yet: a kernel call that advances two steps, a
call that is one program, a snapshot program of coarse sums, and a
kernel call's line as the TPU backend prints it."""

import types

import pytest

from perfbench import run
from perfbench.harness import files, scopes

from perfbench_fixtures import (
    a_step, event_lines, made_job_session, made_trace, multistep_text,
    program_text)

F, S = 36 * 68 * 4, 36 * 2 * 4  # a field, a slab
KERNEL_BYTES = 8 + 12 + 6 * F + 6 * S + 6 * F  # what wide_step.3 takes and hands back
# and twice every other result: the three fusions' slabs, the flags
STEP_BYTES = KERNEL_BYTES + 3 * 2 * (2 * S) + 2 * 8
JOB_READERS = ["snapshot_device_share.sw", "snapshot_hbm_roofline_share",
               "sw_hbm_roofline_share.job", "op_surface_device_share.job",
               "state_copy_bytes_per_call.sw"]


def _reader(name):
    return files.load_module("layer_metrics", name)


def _view(session, made, batches=1):
    return types.SimpleNamespace(
        session=session, trace=made, facts=session.facts(),
        peaks={"hbm_gbps": 819.0}, samples=[],
        traced=[run.Sample("multistep", 0.0, 1.0)] * batches)


# -- the one function ----------------------------------------------------


@pytest.mark.parametrize("shape,want", [
    ("f32[7204,14404]{1,0:T(8,128)}", 7204 * 14404 * 4),
    ("(f32[7204,2]{1,0:T(8,128)S(1)}, /*index=1*/bf16[8,128]{1,0}, s32[])",
     7204 * 2 * 4 + 8 * 128 * 2 + 4),
    ("pred[182]{0:T(512)(128)(4,1)S(1)}", 182),
    ("(f8e4m3fn[16]{0}, c64[2]{0}, u32[]{:S(2)}, token[])", 16 + 16 + 4),
])
def test_shape_bytes_are_logical_bytes(shape, want):
    assert scopes.shape_bytes(shape) == want


def test_the_signature_of_a_program_and_of_an_instruction():
    text = multistep_text()
    # the program: six fields in, six out
    assert scopes.signature(text) == (6 * F, 6 * F)
    kernel = scopes.signature(text, "wide_step.3")
    assert kernel == (8 + 12 + 6 * F + 6 * S, 6 * F)
    assert kernel.bytes == KERNEL_BYTES
    # an operand's shape overstates what a slice reads: a caller takes
    # twice the result of anything that is no kernel call
    assert scopes.signature(text, "fusion.6") == (F, 2 * S)
    assert scopes.signature(text, "no.such") is None
    assert scopes.signature("not a program") is None
    # the trace's event names print each operand's shape before its name
    event = ("%copy-done.2 = bf16[4,8]{1,0} copy-done((bf16[4,8]{1,0}, "
             "bf16[4,8]{1,0}, u32[]{:S(2)}) %copy-start.2)\n")
    assert scopes.signature(event, "copy-done.2") == (2 * 64 + 4, 64)


def test_a_kernel_calls_line_as_the_tpu_backend_prints_it_has_its_origin():
    """``frontend_attributes={kernel_metadata={}}`` stands before the
    line's own ``metadata={...}``: the kernel, 99.5 % of the solver, is
    the programs', and one a later PR puts under a scope is found."""
    text = multistep_text()
    line = event_lines(text)["wide_step.3"]
    assert line.index("kernel_metadata={}") < line.index(" metadata={op_name=")
    origin = scopes.origins(text)["wide_step.3"]
    assert origin.op_name.endswith("jit(wide_step)/pallas_call")
    assert origin.source == "mpi4jax_tpu/models/sw_kernels.py:350"
    assert origin.callers == ("mpi4jax_tpu/models/shallow_water.py:837",)
    assert scopes.layer_of(origin) == scopes.PROGRAMS
    scoped = text.replace("closed_call/jit(wide_step)/pallas_call",
                          "mpi4jax_tpu.snapshot/coarsen/pallas_call")
    origin = scopes.origins(scoped)["wide_step.3"]
    assert origin.scopes == ("mpi4jax_tpu.snapshot", "coarsen")
    assert scopes.layer_of(origin) == scopes.OP_SURFACE
    assert origin.source == "mpi4jax_tpu/models/sw_kernels.py:350"


# -- the readers on programs that do not exist yet -----------------------


@pytest.mark.parametrize("reader", ["sw_hbm_roofline_share",
                                    "sw_hbm_roofline_share.job"])
def test_a_kernel_call_that_advances_two_steps_halves_the_bytes_a_step(
        reader, capsys):
    texts = {"multistep": multistep_text(), "snapshot": program_text((36, 68), (16, 32))}
    lines = event_lines(texts["multistep"])
    out = [(event_lines(texts["snapshot"])["out.0"], 50)]
    session = made_job_session(texts, reps=2)
    share = {}
    for steps_a_kernel_call in (1, 2):
        # the same device time a call, whatever a kernel call advances
        a_call = a_step(lines, 731 * steps_a_kernel_call - 31) * (
            10 // steps_a_kernel_call)
        made = made_trace([a_call, out, a_call, out] if "job" in reader
                      else [a_call, a_call])
        share[steps_a_kernel_call] = _reader(reader).read(_view(session, made))
        said = capsys.readouterr().out
        assert f"a step moves {STEP_BYTES / steps_a_kernel_call:.0f} bytes" in said
        assert f"{1 / steps_a_kernel_call:g} kernel calls a step" in said
    # 73100 bytes a step in 731 ns (and the gaps where the reader takes
    # all the busy time): the hand-made chip is not faster than its table
    assert 0 < share[2] < share[1] < 100
    assert share[1] == pytest.approx(100 * (STEP_BYTES / 819e9) / 731e-9)
    assert share[2] == pytest.approx(share[1] / 2, rel=1e-3)


def test_a_last_step_that_writes_coarse_fields_adds_their_bytes(capsys):
    coarse = 3 * 16 * 32 * 4
    texts = {"multistep": multistep_text(coarse=(16, 32))}
    lines = event_lines(texts["multistep"])
    last = [(lines[f"slab.{i}"], 5) for i in range(6)] + [
        (lines["wide_step_out.4"], 730)] + [(lines[f"finish.{i}"], 4) for i in range(3)]
    made = made_trace([a_step(lines) * 9 + last] * 3)
    got = _reader("sw_hbm_roofline_share.job").read(
        _view(made_job_session(texts), made))
    a_call = (9 * STEP_BYTES + KERNEL_BYTES + coarse  # the kernel calls, whole
              + 6 * 2 * S + 2 * coarse)  # twice every other result
    assert f"a step moves {a_call / 10:.0f} bytes" in capsys.readouterr().out
    per_step = (9 * 731 + 6 * 5 + 730 + 3 * 4) / 10  # no gap inside a call
    assert got == pytest.approx(100 * (a_call / 10 / 819e9) / (per_step * 1e-9))
    assert got < 100


def test_a_call_of_one_program_gives_every_reader_a_number_or_a_reason(capsys):
    """A job with no ``snap``: the last kernel call of a call writes the
    coarse sums and three fusions finish them.  No reader refuses the
    whole trace: the snapshot's cost is the excess of the last period
    over the median period, and the reader of the snapshot program says
    that a call has none."""
    texts = {"multistep": multistep_text(coarse=(16, 32))}
    session = made_job_session(texts)
    assert session.programs() == ("multistep",)
    lines = event_lines(texts["multistep"])
    last = [(lines[f"slab.{i}"], 5) for i in range(6)] + [
        (lines["wide_step_out.4"], 730)] + [(lines[f"finish.{i}"], 4) for i in range(3)]
    made = made_trace([a_step(lines) * 9 + last] * 3)  # a batch of three calls
    view = _view(session, made)
    whole, executions = session.traced_programs(made, view.traced)
    assert whole is made and executions == ["multistep"] * 3
    got = {name: _reader(name).read(view) for name in JOB_READERS}
    said = capsys.readouterr().out
    assert "do not belong together" not in said
    # a period is a kernel call and the next step's slices: eight of
    # 731 ns, one of 730 (the six slabs sliced apart) and the last, with
    # the finish, 742, after the first step's slices
    call = 31 + 8 * 731 + 730 + 742
    assert got["snapshot_device_share.sw"] == pytest.approx(
        100 * (call - 10 * 731) / call)
    assert f"{(call - 7310) / 1e3:.3f} us a call are output's" in said
    # output's bytes are the coarse fields the last step hands back,
    # over the 41 ns a call spends because it has output
    assert got["snapshot_hbm_roofline_share"] == pytest.approx(
        100 * (3 * 16 * 32 * 4 / 819e9) / ((call - 7310) * 1e-9))
    assert "the multistep's results beyond its operands 6144 bytes" in said
    assert 0 < got["sw_hbm_roofline_share.job"] < 100
    assert got["op_surface_device_share.job"] == pytest.approx(
        100 * (9 * 30 + 6 * 5 + 3 * 4) / call)
    assert got["state_copy_bytes_per_call.sw"] == 0.0
    # the tables by layer and by origin are still printed
    assert "device time by layer" in said and "device time by origin" in said


def test_a_snapshot_program_of_coarse_sums_has_a_floor_of_their_bytes(capsys):
    """Handed three coarse sums and handing three coarse fields back,
    the program's floor is their bytes, not three whole fields'."""
    texts = {"multistep": multistep_text(coarse=(16, 32)),
             "snapshot": program_text((16, 32), (16, 32))}
    assert scopes.signature(texts["snapshot"]).bytes == 6 * 16 * 32 * 4
    lines = event_lines(texts["multistep"])
    means = [(event_lines(texts["snapshot"])[f"out.{i}"], 10) for i in range(3)]
    made = made_trace([a_step(lines) * 10, means] * 3)
    view = _view(made_job_session(texts), made)
    # every kernel call of this made trace takes as long as the others:
    # the sums the multistep hands back have no time of their own, and
    # their bytes are not held against the scaling program's
    assert _reader("snapshot_hbm_roofline_share").read(view) is None
    assert "no device time of their own" in capsys.readouterr().out
    # the same trace by the hand count of three whole fields: 1 250 %
    assert 100 * (3 * (F + 16 * 32 * 4) / 819e9) / 30e-9 > 100
    # what output costs a call: the program, and nothing of the steps
    call = 10 * 731 + 30
    assert _reader("snapshot_device_share.sw").read(view) == pytest.approx(
        100 * 30 / call)
    capsys.readouterr()


C = 16 * 32 * 4  # a coarse field
OUTPUT = {
    # today's: a multistep and a snapshot program of three whole fields
    "a call of two programs": (
        {"multistep": multistep_text(), "snapshot": program_text((36, 68), (16, 32))},
        "plain", 60, 3 * (F + C), 60),
    # PR 40's: the last kernel call writes the coarse fields, three fusions finish them
    "a last step that hands back coarse fields": (
        {"multistep": multistep_text(coarse=(16, 32))}, "last", 0, 3 * C, 41),
    # the last step writes coarse sums, a program of its own scales them
    "a snapshot program handed coarse sums": (
        {"multistep": multistep_text(coarse=(16, 32)),
         "snapshot": program_text((16, 32), (16, 32))}, "last", 30, 9 * C, 71),
    # every kernel call sums: the median period holds output's work
    "sums in every kernel call": (
        {"multistep": multistep_text(coarse=(16, 32))}, "plain", 0, 3 * C, 0),
    "no output at all": ({"multistep": multistep_text()}, "plain", 0, 0, 0),
}


@pytest.mark.parametrize("case", list(OUTPUT))
def test_outputs_share_of_the_roofline_wherever_its_work_runs(case, capsys):
    """``snapshot_hbm_roofline_share``: the bytes a call moves because it
    has output (every program but the multistep by its signature, and
    what the multistep hands back beyond what it is handed) over the
    time ``snapshot_device_share.sw`` says output costs a call.  No
    reading passes 100, and where there are no bytes or no time the
    reader says which and reports nothing."""
    texts, steps, snapshot_ns, moved, output_ns = OUTPUT[case]
    lines = event_lines(texts["multistep"])
    call = a_step(lines) * 10
    if steps == "last":  # the tenth step's kernel call and the finish after it
        call = a_step(lines) * 9 + [(lines[f"slab.{i}"], 5) for i in range(6)] + [
            (lines["wide_step_out.4"], 730)] + [(lines[f"finish.{i}"], 4) for i in range(3)]
    executions = [call]
    if "snapshot" in texts:
        of_snapshot = event_lines(texts["snapshot"])
        executions.append([(of_snapshot[f"out.{i}"], snapshot_ns // 3) for i in range(3)])
    session = made_job_session(texts)
    view = _view(session, made_trace(executions * 3))
    reader = _reader("snapshot_hbm_roofline_share")
    assert sum(n for _, _, n in reader.output_bytes(session)) == moved
    got = reader.read(view)
    said = capsys.readouterr().out
    assert "do not belong together" not in said
    if moved and output_ns:
        assert got == pytest.approx(100 * (moved / 819e9) / (output_ns * 1e-9))
        assert 0 < got < 100
        assert f"took {output_ns / 1e3:.3f} us of device time" in said
        # the time is the other reader's, to the digit
        share = _reader("snapshot_device_share.sw").read(view)
        total = 100 * output_ns / share
        assert total == pytest.approx(sum(ns for e in executions for _, ns in e))
    else:
        assert got is None and said.count("nothing is reported") == 1
        assert ("adds no bytes" if not moved else "no device time of their own") in said
    capsys.readouterr()


def test_a_step_without_a_kernel_call_reports_nothing(capsys):
    """The array code (no cell runs it): nothing says what its least
    bytes are, and there is no period to hold a call against."""
    texts = {"multistep": multistep_text()}
    lines = event_lines(texts["multistep"])
    made = made_trace([[(lines["fusion.6"], 10), (lines["flags"], 700)] * 10] * 3)
    view = _view(made_job_session(texts), made)
    for name in ("sw_hbm_roofline_share", "sw_hbm_roofline_share.job",
                 "snapshot_device_share.sw"):
        assert _reader(name).read(view) is None
    said = capsys.readouterr().out
    assert said.count("ran no kernel call") == 3
