"""The cells of the solver job that watches itself on the CPU's virtual
devices: the configuration's file against ``shallow-water``'s and the
two workload files against ``BENCHMARK.json``; small cells of the
configuration added as new files to a copy of the benchmark and run end
to end on 1x1 and 2x2; both controls; a band of a sharded field; the
comparison's reckoning of bytes at the real cells' sizes; and the five
per-layer readers on made-up traces whose values are computed by hand,
and on a session and a trace that lack the monitor program."""

import json
import re
import types

import jax
import numpy as np
import pytest

from perfbench import run
from perfbench.harness import files, scopes
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import KERNEL, PACK, ROOT, TABLES, cell_args, make_copy

CELLS = ["sw-monitored-1chip", "sw-monitored-2x2-weak"]
TOY = ["sw-mon-toy-1x1", "sw-mon-toy-2x2"]
ACCEPTED_CELLS = ["sw-bench-1chip", "coll-2x2", "sw-job-1chip", "sw-restart-1chip",
                  "sw-as-written-1chip", "sw-output-restart-1chip"]
NEW_READERS = ["monitor_device_share.sw", "monitor_hbm_roofline_share",
               "monitor_wait_share.sw", "halo_wire_device_share.sw",
               "monitor_allreduce_us_per_call"]
APPENDED_TO = ["solver_rate", "solver_step_p95_us", "device_idle_share.sw"]
# the accepted readers of the job cells that read true on both new cells on
# the chip (PERF.md section 6, PR 51: `_work/call51f.sh`, `call51g.sh`)
JOB_CELLS = ["sw-job-1chip", "sw-restart-1chip", "sw-output-restart-1chip"]
READ_TRUE = ["sw_hbm_roofline_share.job", "state_copy_bytes_per_call.sw",
             "host_device_clock_bracket_us", "idle_in_sync_share.sw",
             "idle_in_job_share.sw", "idle_unnamed_share.sw",
             "job_issue_us_per_call.sw"]
# and those that read nothing or misread there, left as they were: the
# monitor program's text has no op scope on one chip, and XLA's block
# copies carry none on the mesh; the rest take every execution for a step
LEFT_OUT = ["op_surface_device_share.job", "op_surface_device_share.sw",
            "sw_device_ops_per_step", "sw_hbm_roofline_share"]
CHECKS = {
    "lines_unread", "lines_out_of_order_or_torn", "max_lag_calls",
    "monitor_stops", "mass_drift_window", "nonfinite_after_window",
    "max_abs_diff_h", "max_abs_diff_u", "max_abs_diff_v",
    "line_nonfinite", "line_cfl", "line_h_min", "line_mass_relative",
    "last_line_nonfinite", "last_line_cfl", "last_line_h_min",
    "last_line_mass_relative"}


# -- the files ---------------------------------------------------------


def test_the_configuration_is_shallow_waters_with_a_monitor():
    config = files.load_json("configs", "shallow-water-monitored")
    accepted = files.load_json("configs", "shallow-water")
    assert config["model"] == accepted["model"]
    assert config["architecture"] is None and config["reduced"] == []
    assert config["monitor"]["every_calls"] == 1 and config["monitor"]["lag"] == 4
    assert 0.04 < config["monitor"]["cfl_limit"] <= 1.0
    check = config["check"]
    assert check["calls"] == accepted["check"]["calls"] == 4
    assert check["limits"] == accepted["check"]["limits"]
    assert check["row_blocks"] >= 16
    assert set(check["line_limits"]) == set(check["last_line_limits"]) == {
        "nonfinite", "cfl", "h_min", "mass_relative"} == set(check["line_limits_why"])
    # the order-free numbers' limits are the fields' carried through the formula
    dt_over_dx = 0.125 / (accepted["model"]["gravity"] * accepted["model"]["depth"]) ** 0.5
    assert check["limits"]["u"] * dt_over_dx <= check["line_limits"]["cfl"] <= (
        1.5 * check["limits"]["u"] * dt_over_dx)
    assert check["line_limits"]["h_min"] == check["limits"]["h"]
    assert check["line_limits"]["mass_relative"] <= 1e-5
    assert {k: config["guarantees"][k] for k in accepted["guarantees"]} == (
        accepted["guarantees"])
    assert set(config["guarantees"]) - set(accepted["guarantees"]) == {
        "every_line", "no_tearing", "stop", "mass"}
    assert {k: config["assumed"][k] for k in ("perturbation", "run_length")} == {
        k: accepted["assumed"][k] for k in ("perturbation", "run_length")}
    assert set(config["assumed"]["formulas"]) == {"nonfinite", "cfl", "h_min", "mass"}
    entry = next(c for c in files.load_benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["file"] == "perfbench/configs/shallow-water-monitored.json"
    assert entry["reduced"] == []
    from mpi4jax_tpu.models import shallow_water as sw

    ours = config["monitor"]
    assert sw.Monitor() == sw.Monitor(
        every_calls=ours["every_calls"], lag=ours["lag"], cfl_limit=ours["cfl_limit"])


def test_the_plain_reference_imports_nothing_of_the_program():
    text = (ROOT / "perfbench/references/shallow-water-monitored.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+mpi4jax_tpu", text, re.M)
    assert not re.search(r"^\s*(import|from)\s+perfbench", text, re.M)


@pytest.mark.parametrize("cell, chips, mesh, refine", [
    (CELLS[0], 1, [1, 1], 4), (CELLS[1], 4, [2, 2], 8)])
def test_a_workload_file_says_what_benchmark_json_says(cell, chips, mesh, refine):
    benchmark = files.load_benchmark(ROOT)
    entry = files.find_cell(benchmark, cell)
    workload = files.load_json("workloads", cell)
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: entry[k] for k in ("config", "chips", "traffic", "why")}
    assert entry["config"] == "shallow-water-monitored" and entry["chips"] == chips
    assert len(entry["why"]) <= 200
    assert workload["mesh"] == mesh and workload["grid"]["refine"] == refine
    # the same block a chip on both sides of the weak-scaling pair, and
    # the published domain on both: refined, never doubled at fixed dx
    bench = files.load_json("workloads", "sw-bench-1chip")["grid"]
    grid = workload["grid"]
    assert [grid["ny"] // mesh[0], grid["nx"] // mesh[1]] == [bench["ny"], bench["nx"]]
    assert grid["ny"] // refine == 1800 and grid["nx"] // refine == 3600
    assert workload["rows"] == files.load_json("workloads", "sw-job-1chip")["rows"] == [
        {"name": "multistep", "slots": 1, "reps": 4, "trace_batches": 3}]


def test_the_cells_are_appended_and_nothing_before_them_moved():
    benchmark = files.load_benchmark(ROOT)
    cells = [c["name"] for c in benchmark["workloads"]]
    assert cells[:8] == ACCEPTED_CELLS + CELLS
    configs = [c["name"] for c in benchmark["configs"]]
    assert configs[:7] == [
        "shallow-water", "collectives", "shallow-water-job", "shallow-water-restart",
        "shallow-water-as-written", "shallow-water-output-restart",
        "shallow-water-monitored"]
    readers = [m["name"] for m in benchmark["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + 5] == NEW_READERS
    assert readers[first - 1] == "save_commit_period_ratio"  # PR 45's last
    listed = {m["name"]: m for m in benchmark["end_to_end"] + benchmark["per_layer"]}
    for name in NEW_READERS:
        entry = listed[name]
        assert entry["moves"] == "solver_rate" and set(entry) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (ROOT / f"perfbench/layer_metrics/{name}.py").is_file()
    assert [listed[n]["workloads"] for n in NEW_READERS] == [
        CELLS, CELLS, CELLS, CELLS[1:], CELLS[1:]]
    assert [listed[n]["layer"] for n in NEW_READERS] == [
        "programs", "kernels", "programs", "op surface", "op surface"]
    solver_cells = [c for c in ACCEPTED_CELLS if c != "coll-2x2"]
    for name in APPENDED_TO:
        assert listed[name]["workloads"] == solver_cells + CELLS
    for name in READ_TRUE:
        assert listed[name]["workloads"] == JOB_CELLS + CELLS
    for name in LEFT_OUT:
        assert not set(listed[name]["workloads"]) & set(CELLS)
    # no other accepted metric lists the new cells
    assert {m["name"] for m in listed.values()
            if set(m.get("workloads", ())) & set(CELLS)} == set(
                NEW_READERS + APPENDED_TO + READ_TRUE)
    chips = [c["chips"] for c in benchmark["workloads"]]
    assert chips.count(4) == 2 <= max(1, len(chips) // 4)  # the driver's share


# -- small cells, end to end ---------------------------------------------


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with two cells of ``shallow-water-monitored``
    more: 32x64 cells a chip on 1x1 and on 2x2, the published toy
    domain refined twice as far on the mesh, as the real pair is."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_monitored"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / "configs/shallow-water-monitored.json").read_text())
    config["name"] = "shallow-water-monitored-toy"
    config["check"].update(calls=2, row_blocks=3)
    (bench / "configs/shallow-water-monitored-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == "shallow-water-monitored")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-monitored-toy",
        file="perfbench/configs/shallow-water-monitored-toy.json"))
    for name, mesh in zip(TOY, ([1, 1], [2, 2])):
        cell = {
            "config": "shallow-water-monitored-toy", "traffic": name,
            "chips": mesh[0] * mesh[1], "why": "a test cell", "mesh": mesh,
            "grid": {"ny": 32 * mesh[0], "nx": 64 * mesh[1], "refine": 2 * mesh[0]},
            "rows": [{"name": "multistep", "slots": 1, "reps": 3}],
        }
        (bench / f"workloads/{name}.json").write_text(json.dumps(cell))
        benchmark["workloads"].append({
            k: cell[k] for k in ("config", "traffic", "chips", "why")
        } | {"name": name})
    for section in ("end_to_end", "per_layer"):
        for metric in benchmark[section]:
            if CELLS[1] in metric.get("workloads", []):
                metric["workloads"] += TOY
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _session(copy, cell, seed=2**31 + 51):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    session = driver.setup(
        run.Context(config, workload, seed, jax.devices(), bench))
    for row in workload["rows"]:
        session.batch(row["name"])
    return session


@pytest.fixture(scope="module")
def sessions(copy):
    made = {}

    def session(cell):
        if cell not in made:
            made[cell] = _session(copy, cell)
        return made[cell]

    return session


@pytest.mark.parametrize("cell", TOY)
def test_the_cell_runs_and_every_check_is_beside_its_limit(copy, cell, capsys):
    result = run.run_cell(
        cell_args(cell), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    assert set(result["checks"]) == CHECKS
    # a window of two batches or more reads a line four calls late
    assert 2 <= result["checks"]["max_lag_calls"]["value"] <= 4
    assert result["checks"]["max_lag_calls"]["limit"] == 4
    assert result["checks"]["lines_unread"]["value"] == 0
    assert "the comparison's fullest chip by reckoning" in capsys.readouterr().out


@pytest.mark.parametrize("cell", TOY)
def test_both_controls_are_not_correct(sessions, cell):
    session = sessions(cell)
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert {c["name"] for c in sound} == CHECKS
    control = {c["name"]: c for c in session.control()}

    def refused(name):
        return control[name]["value"] > control[name]["limit"]

    # the reference in bfloat16 in the program's place: its fields, its lines
    assert all(refused(f"max_abs_diff_{k}") for k in "huv"), control
    assert refused("line_cfl") and refused("line_h_min"), control
    # a line one call stale, against the job's own final fields
    assert refused("stale_line_cfl") and refused("stale_line_h_min"), control


def test_a_line_out_of_order_fails_the_batch(copy):
    session = _session(copy, TOY[0], seed=3)
    session._expected += 10  # as if one had been skipped
    with pytest.raises(RuntimeError, match="out of order"):
        session.batch("multistep")


def test_a_band_of_a_sharded_field_is_the_fields_rows(copy):
    driver = files.load_module("drivers", "shallow_water_monitored", copy[1])
    mesh = jax.make_mesh(
        (2, 2), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:4])
    whole = np.arange(24 * 40, dtype=np.float32).reshape(24, 40)
    field = jax.device_put(whole, jax.NamedSharding(mesh, jax.P("y", "x")))
    for lo, hi in ((0, 5), (3, 12), (10, 17), (12, 24), (0, 24)):
        for device in jax.devices()[:4]:
            band = driver.band_of(field, lo, hi, device)
            assert band.devices() == {device}
            np.testing.assert_array_equal(np.asarray(band), whole[lo:hi])


@pytest.mark.parametrize("cell", CELLS)
def test_the_comparisons_fullest_chip_is_reckoned_under_a_chips_memory(cell):
    """At the real cells' sizes, from shapes alone: what
    ``Session.check_bytes`` adds up, the control's kept reference
    included, against a v5e's 16e9 bytes."""
    driver = files.load_module("drivers", "shallow_water_monitored")
    workload = files.load_json("workloads", cell)
    config = files.load_json("configs", workload["config"])
    made = types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=config),
        ny=workload["grid"]["ny"], nx=workload["grid"]["nx"],
        chips=workload["chips"], steps_per_call=10,
        ref=files.load_module("references", config["reference"]))
    made._check_steps = lambda: driver.Session._check_steps(made)
    parts = driver.Session.check_bytes(made, kept_references=1)
    share = 3 * 7200 * 14400 * 4
    assert parts["seeded fields, a chip's share"] == share
    assert parts["references kept whole"] == share
    band = (workload["grid"]["ny"] // 16 + 2 * 6 * 41) * workload["grid"]["nx"] * 4
    assert parts["the reference's walk, two states and its temporaries"] == 23 * band
    assert sum(parts.values()) < 0.75 * 16e9
    # and never the 20.6e9 that the whole fields on one chip would be
    assert max(parts.values()) < 4e9


# -- the per-layer readers on made-up traces ------------------------------


def _lines(text):
    return {name: line.strip() for line, name in
            re.findall(r"^\s*(?:ROOT\s+)?(%([\w.\-]+) = .*)$", text, re.M)}


def _pick(text, want):
    lines = _lines(text)
    for name, origin in scopes.origins(text).items():
        if want(origin, lines[name]):
            return lines[name]
    raise AssertionError("the program has no such instruction")


def _trace(executions, chips=("/device:TPU:0",)):
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
            t += 7.0
    return made


def _view(session, made, batches=1, samples=()):
    return types.SimpleNamespace(
        session=session, trace=made, facts=session.facts(),
        peaks={"hbm_gbps": 819.0}, samples=list(samples),
        traced=[run.Sample("multistep", 0.0, 1.0)] * batches)


def _reader(copy, name):
    return files.load_module("layer_metrics", name, copy[1])


def _monitor_lines(session):
    """Event names of the monitor program as this backend compiled it:
    a local reduction, and an all-reduce under the op's scope."""
    text = session.compiled_text("monitor")
    local = _pick(text, lambda o, line: "sw/monitor" in (o.op_name or "")
                  and "mpi4jax_tpu.allreduce" not in o.op_name
                  and "reduce" in line.split(" = ")[1])
    reduced = _pick(text, lambda o, line: " all-reduce(" in line
                    and "sw/monitor/mpi4jax_tpu.allreduce" in (o.op_name or ""))
    return text, local, reduced


def test_the_monitor_programs_readers_on_a_hand_made_trace(copy, sessions, capsys):
    session = sessions(TOY[1])
    multi = session.compiled_text("multistep")
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    text, local, reduced = _monitor_lines(session)
    assert session.programs() == ("multistep", "monitor")
    # a batch of three calls on two chips: the multistep 900 ns, the
    # monitor program 60 ns of reductions and two all-reduces of 20 ns
    call = [[(step, 900)], [(local, 60), (reduced, 20), (reduced, 20)]]
    made = _trace(call * 3, chips=("/device:TPU:0", "/device:TPU:1"))
    view = _view(session, made)
    capsys.readouterr()
    assert _reader(copy, "monitor_device_share.sw").read(view) == pytest.approx(
        100 * 100 / 1000)
    out = capsys.readouterr().out
    assert "takes 0.100 us of device time a call (6 whole executions on 2 chips)" in out
    assert "local reductions 0.060 us, allreduce 0.040 us, neither 0.000 us" in out
    # three padded fields in, a line out, held against 100 ns
    least = scopes.signature(text)
    assert least.taken == 3 * 36 * 68 * 4 and least.handed_back == 16
    assert _reader(copy, "monitor_hbm_roofline_share").read(view) == pytest.approx(
        100 * (least.bytes / 819e9) / 100e-9)
    assert _reader(copy, "monitor_allreduce_us_per_call").read(view) == pytest.approx(
        0.040)
    assert "the monitor's all-reduces: 2 a call" in capsys.readouterr().out


def test_a_trace_cut_inside_the_last_monitor_program_is_read_without_it(
        copy, sessions, capsys):
    session = sessions(TOY[1])
    multi = session.compiled_text("multistep")
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    _, local, reduced = _monitor_lines(session)
    whole = [[(step, 900)], [(local, 60), (reduced, 40)]]
    made = _trace(whole * 2 + [[(step, 900)], [(local, 60)]])  # the profiler stopped
    capsys.readouterr()
    assert _reader(copy, "monitor_device_share.sw").read(
        _view(session, made)) == pytest.approx(100 * 200 / 2900)
    assert "the readers leave that execution out" in capsys.readouterr().out


def test_the_wait_share_reads_the_jobs_own_spans(copy, sessions, capsys):
    session = sessions(TOY[0])
    spans = [s for s in session.job.spans() if s.name == "job/monitor_wait"]
    assert spans
    first, last = spans[0], spans[-1]
    window = [run.Sample("multistep", first.start_ns / 1e9 - 1e-3,
                         last.end_ns / 1e9 + 1e-3)]
    view = _view(session, Trace(), batches=0, samples=window)
    want = 100 * sum(s.seconds for s in spans) / window[0].seconds
    assert _reader(copy, "monitor_wait_share.sw").read(view) == pytest.approx(want)
    out = capsys.readouterr().out
    assert f"{len(spans)} lines read inside the window's batches" in out
    assert "monitor_stops 0" in out


def test_the_readers_say_so_where_there_is_no_monitor(copy, capsys):
    """A session whose call has no monitor program (a job cell's), a
    job without the counters, and a trace that lacks the program's
    executions: a printed reason and nothing, never a zero."""
    bare = types.SimpleNamespace(
        programs=lambda: ("multistep",),
        job=types.SimpleNamespace(
            stats=lambda: {"output_wait_s": 0.0}, spans=lambda: [],
            trace=types.SimpleNamespace(dropped=0)),
        facts=lambda: {"steps_per_call": 10, "cells": 64},
        ctx=types.SimpleNamespace(bench_dir=copy[1]))
    view = _view(bare, _trace([[("%x.1 = f32[] add(%a, %b)", 10)]]))
    capsys.readouterr()
    for name in ("monitor_device_share.sw", "monitor_hbm_roofline_share",
                 "monitor_allreduce_us_per_call"):
        assert _reader(copy, name).read(view) is None
        assert "no monitor program; nothing is reported" in capsys.readouterr().out
    assert _reader(copy, "monitor_wait_share.sw").read(view) is None
    assert "the job keeps no monitor" in capsys.readouterr().out
    # the program is the call's, the trace holds none of its executions
    watched = types.SimpleNamespace(
        programs=lambda: ("multistep", "monitor"), ctx=bare.ctx, facts=bare.facts,
        traced_programs=lambda trace, traced: (trace, ["multistep"]))
    view = _view(watched, _trace([[("%x.1 = f32[] add(%a, %b)", 10)]]))
    assert _reader(copy, "monitor_device_share.sw").read(view) is None
    assert "holds no execution of the monitor program" in capsys.readouterr().out


def _mesh_multistep_text(rows=36, cols=68):
    """A multistep as the TPU backend compiles the solver's on a mesh:
    a copy of a whole block that carries no scope (XLA's, to cut the
    column slabs), the slab's slice under ``pack``, a
    ``collective-permute`` pair and a layout copy under ``wire``, and
    the kernel call."""
    F = f"f32[{rows},{cols}]{{1,0:T(8,128)}}"
    T = f"f32[{rows},{cols}]{{0,1:T(8,128)}}"
    S = f"f32[{rows},2]{{0,1:T(2,128)S(1)}}"
    wire = ('metadata={op_name="jit(local_fn)/mpi4jax_tpu.halo_slabs_2d/wire/'
            'mpi4jax_tpu.sendrecv/ppermute" stack_frame_id=3}')
    lines = [f"  %state_h.1 = {F} parameter(0)",
             f"  %copy.7 = {T} copy(%state_h.1)",
             f"  %slice.9 = {S} slice(%copy.7), slice={{[0:{rows}], [2:4]}}, {PACK}",
             f"  %collective-permute-start.1 = ({S}, {S}) "
             f"collective-permute-start(%slice.9), channel_id=1, {wire}",
             f"  %collective-permute-done.1 = {S} "
             f"collective-permute-done(%collective-permute-start.1), {wire}",
             f"  %copy.8 = f32[{rows},2]{{1,0:T(8,128)S(1)}} "
             f"copy(%collective-permute-done.1), {wire}",
             f"  ROOT %wide_step.3 = {F} custom-call(%state_h.1, %copy.8), {KERNEL}"]
    return ("HloModule jit_local_fn, is_scheduled=true\n" + TABLES
            + f"\nENTRY %main.5 (state_h.1: f32[{rows},{cols}]) -> f32[{rows},{cols}] {{\n"
            + "\n".join(lines) + "\n}\n")


def test_the_halo_reader_books_each_part_of_the_exchange_on_its_own_line(
        copy, capsys):
    text = _mesh_multistep_text()
    names = _lines(text)
    session = types.SimpleNamespace(
        compiled_text={"multistep": text}.__getitem__,
        traced_programs=lambda trace, traced: (trace, ["multistep"] * 2),
        facts=lambda: {"steps_per_call": 10, "cells": 32 * 64 * 2})
    a_call = [(names["copy.7"], 100), (names["slice.9"], 10),
              (names["collective-permute-start.1"], 10),
              (names["collective-permute-done.1"], 30), (names["copy.8"], 20),
              (names["wide_step.3"], 830)]
    made = _trace([a_call] * 2, chips=("/device:TPU:0", "/device:TPU:1"))
    capsys.readouterr()
    share = _reader(copy, "halo_wire_device_share.sw").read(_view(session, made))
    assert share == pytest.approx(100 * 170 / 1000)
    out = capsys.readouterr().out
    for line in ("permute start | 0.001 | 1.000", "permute done | 0.003 | 3.000",
                 "wire other | 0.002 | 2.000", "pack | 0.001 | 1.000",
                 "unpack | 0.000 | 0.000", "block copy | 0.010 | 10.000"):
        assert line in out, out
    # one chip: the compiler elides the exchange, and the reader says so
    alone = types.SimpleNamespace(
        compiled_text=lambda key: text.replace("collective-permute", "elided"),
        traced_programs=session.traced_programs, facts=session.facts)
    assert _reader(copy, "halo_wire_device_share.sw").read(
        _view(alone, made)) is None
    assert "holds no collective-permute" in capsys.readouterr().out


def test_the_halo_reader_on_the_toy_mesh_cells_own_text(copy, sessions):
    """On this backend the step is array code and its exchange is
    ``halo_exchange_2d``: the reader books its permutes and its pack
    and unpack all the same."""
    session = sessions(TOY[1])
    multi = session.compiled_text("multistep")
    permute = _pick(multi, lambda o, line: "collective-permute" in line
                    and any(s.startswith("mpi4jax_tpu.halo_") for s in o.scopes))
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    _, local, _ = _monitor_lines(session)
    made = _trace([[(step, 700), (permute, 200)], [(local, 100)]] * 3)
    assert _reader(copy, "halo_wire_device_share.sw").read(
        _view(session, made)) == pytest.approx(100 * 600 / 3000)
