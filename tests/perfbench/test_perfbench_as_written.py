"""The as-written cell on the CPU's virtual devices: two small cells of
the configuration ``shallow-water-as-written`` added as new files to a
copy of the benchmark and run end to end (1x1 and 2x2); the control and
a step that does nothing, which have to come out not correct; the three
new readers on made-up traces whose values are computed by hand (a text
as the TPU backend prints the as-written step's instructions: an
in-place write, the exchange's strips of several fields in one fusion,
a fusion handed less than it hands back, an asynchronous copy; and a
text with a kernel call); and the real cell's files."""

import json
import types

import jax
import pytest

from perfbench import run
from perfbench.harness import files, scopes

from perfbench_fixtures import (
    ROOT, TABLES, a_step, cell_args, event_lines, made_trace, make_copy,
    multistep_text)

CELL = "sw-as-written-1chip"
CELLS = ["sw-as-written-toy-1x1", "sw-as-written-toy-2x2"]
NEW_READERS = ["sw_field_passes_per_step", "sw_hbm_roofline_share.as_written",
               "sw_exchange_device_share.as_written"]
ACCEPTED = ["device_idle_share.sw", "op_surface_device_share.sw",
            "sw_device_ops_per_step"]
CHECKS = {"nonfinite_after_window"} | {
    f"{kind}_{k}" for kind in ("max_abs_diff", "ghost_columns_diff")
    for k in "huv"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with two cells of ``shallow-water-as-written``
    more, 32x64 cells; the configuration's file is the real one but for
    the reference's bands of rows."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_as_written"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    path = bench / "configs/shallow-water-as-written.json"
    config = json.loads(path.read_text())
    config["check"].update(row_blocks=2)
    path.write_text(json.dumps(config))
    for name, mesh in zip(CELLS, ([1, 1], [2, 2])):
        cell = {
            "config": "shallow-water-as-written", "traffic": name,
            "chips": mesh[0] * mesh[1], "why": "a test cell", "mesh": mesh,
            "grid": {"ny": 32, "nx": 64, "refine": 2},
            "rows": [{"name": "multistep", "slots": 1, "reps": 2,
                      "trace_batches": 2}],
        }
        (bench / f"workloads/{name}.json").write_text(json.dumps(cell))
        benchmark["workloads"].append({
            k: cell[k] for k in ("config", "traffic", "chips", "why")
        } | {"name": name})
    for section in ("end_to_end", "per_layer"):
        for metric in benchmark[section]:
            if CELL in metric.get("workloads", []):
                metric["workloads"] += CELLS
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _session(copy, cell, seed=2**31 + 5):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    return driver.setup(
        run.Context(config, workload, seed, jax.devices(), bench))


@pytest.mark.parametrize("cell", CELLS)
def test_the_as_written_cell_runs_and_every_check_is_beside_its_limit(copy, cell):
    result = run.run_cell(
        cell_args(cell), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    assert set(result["checks"]) == CHECKS
    limits = files.load_json("configs", "shallow-water-as-written")["check"]["limits"]
    for name, c in result["checks"].items():
        assert c["limit"] == limits.get(name[-1], 0), name


@pytest.fixture(scope="module")
def session(copy):
    return _session(copy, CELLS[0])


def test_the_state_is_the_librarys_and_upstreams_arrays(session):
    # built by make_state: (ny + 2, nx + 2) a block, padded tendencies
    assert {a.shape for a in session.state} == {(34, 66)}
    assert session.ghost == 1 and session.units("multistep") == 20
    # what the comparison takes: the domain's rows at all their columns
    got = session._interior(session.state.h, session.state.u, session.state.v)
    assert {g.shape for g in got} == {(32, 66)}


def test_the_control_and_a_step_that_does_nothing_are_not_correct(copy):
    session = _session(copy, CELLS[0], seed=7)
    session.batch("multistep")
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    control = {c["name"]: c for c in session.control()}
    assert set(control) == CHECKS - {"nonfinite_after_window"}
    for kind in ("max_abs_diff", "ghost_columns_diff"):
        # the reference in bfloat16 fails on the cells and on the columns
        assert any(control[f"{kind}_{k}"]["value"] > control[f"{kind}_{k}"]["limit"]
                   for k in "huv"), control
    # a multistep that hands its state back unchanged
    session.state = session._initial_state()
    session.multi = lambda state: state
    idle = {c["name"]: c for c in session.check()}
    assert any(idle[f"max_abs_diff_{k}"]["value"] > idle[f"max_abs_diff_{k}"]["limit"]
               for k in "huv"), idle


# -- the per-layer readers on made-up traces ------------------------------

F, S, I = "f32[36,68]{1,0:T(8,128)}", "f32[36,1]{1,0:T(8,128)S(1)}", "f32[34,66]{1,0:T(8,128)}"
STRIP = "f32[36,8]{1,0:T(8,128)}"
FIELD, SLAB, INTERIOR, A_STRIP = 36 * 68 * 4, 36 * 4, 34 * 66 * 4, 36 * 8 * 4


def _meta(scope, primitive, frame=1):
    return (f'metadata={{op_name="jit(local_fn)/while/body/closed_call/{scope}/'
            f'{primitive}" stack_frame_id={frame}}}')


HALO = "mpi4jax_tpu.halo_exchange_2d"


def as_written_text():
    """A loop body as the TPU backend compiles the as-written step's,
    cut to one instruction of each kind the readers' rules are about."""
    unpack = _meta(f"sw/exchange.ke/{HALO}/unpack", "dynamic_update_slice", 3)
    pack = _meta(f"sw/exchange.h/{HALO}/pack", "slice", 3)

    def strip(i, field, slab):
        return [
            f"  %pad.{i} = {STRIP} pad(%{slab}, %zero), padding=0_0x0_7",
            f"  %slice.{i} = {STRIP} slice(%{field}), slice={{[0:36], [0:8]}}",
            f"  %select.{i} = {STRIP} select(%mask, %pad.{i}, %slice.{i})",
            f"  %dus.{i} = {F} dynamic-update-slice(%{field}, %select.{i}, %at, %at)",
        ]

    return "HloModule jit_local_fn, is_scheduled=true\n" + TABLES + "\n".join([
        f"%strips (p0: f32[36,68], p1: f32[36,1], p2: f32[36,68], p3: f32[36,1]) "
        f"-> (f32[36,68], f32[36,68]) {{",
        f"  %p0 = {F} parameter(0)", f"  %p1 = {S} parameter(1)",
        f"  %p2 = {F} parameter(2)", f"  %p3 = {S} parameter(3)",
        "  %zero = f32[]{:T(128)} constant(0)", "  %at = s32[]{:T(128)} constant(0)",
        "  %mask = pred[36,8]{1,0:T(8,128)(4,1)} constant({...})",
        *strip(1, "p0", "p1"), *strip(2, "p2", "p3"),
        f"  ROOT %tuple.1 = ({F}, {F}) tuple(%dus.1, %dus.2)",
        "}", "",
        "%gradients (g0: f32[36,68]) -> (f32[34,66], f32[34,66]) {",
        f"  %g0 = {F} parameter(0)",
        f"  %gx = {I} slice(%g0), slice={{[1:35], [1:67]}}",
        f"  %gy = {I} slice(%g0), slice={{[1:35], [1:67]}}",
        f"  ROOT %tuple.2 = ({I}, {I}) tuple(%gx, %gy)",
        "}", "",
        "%columns (c0: f32[36,68]) -> f32[36,1] {",
        f"  %c0 = {F} parameter(0)",
        f"  ROOT %col = {S} slice(%c0), slice={{[0:36], [66:67]}}, {pack}",
        "}", "",
        f"%body (arg: (s32[], {F}, {F}, {I}, {S}, {S})) -> (s32[], {F}) {{",
        f"  %arg = (s32[]{{:T(128)}}, {F}, {F}, {I}, {S}, {S}) parameter(0)",
        "  %one = s32[]{:T(128)} constant(1)",
        "  %nought = f32[]{:T(128)} constant(0)",
        f"  %a = {F} get-tuple-element(%arg), index=1",
        f"  %b = {F} get-tuple-element(%arg), index=2",
        f"  %inner = {I} get-tuple-element(%arg), index=3",
        f"  %sa = {S} get-tuple-element(%arg), index=4",
        f"  %sb = {S} get-tuple-element(%arg), index=5",
        f"  %pad.5 = {F} pad(%inner, %nought), padding=1_1x1_1, "
        + _meta("sw/friction", "scatter"),
        f"  %fusion.1 = ({F}, {F}) fusion(%a, %sa, %b, %sb), kind=kLoop, "
        f"calls=%strips, {unpack}",
        f"  %dynamic-update-slice.3 = {F} dynamic-update-slice(%a, %inner, %one, "
        "%one), " + _meta("sw/ab2", "scatter-add"),
        f"  %fusion.2 = ({I}, {I}) fusion(%a), kind=kLoop, calls=%gradients, "
        + _meta("sw/friction", "sub"),
        f"  %copy-start.1 = ({S}, {S}, u32[]{{:S(2)}}) copy-start(%sa)",
        f"  %copy-done.1 = {S} copy-done(%copy-start.1)",
        f"  %copy.1 = {F} copy(%a)",
        f"  %fusion.3 = {S} fusion(%b), kind=kLoop, calls=%columns, {pack}",
        f"  ROOT %tuple.9 = (s32[]{{:T(128)}}, {F}) tuple(%one, %copy.1)",
        "}", "",
        f"ENTRY %main.5 (state_h.1: f32[36,68]) -> f32[36,68] {{",
        f"  %state_h.1 = {F} parameter(0)",
        f"  ROOT %out = {F} copy(%state_h.1)",
        "}", ""])


# one trip of the body: the instruction and the ns it ran
TRIP = [("pad.5", 100), ("fusion.1", 7), ("dynamic-update-slice.3", 90),
        ("fusion.2", 150), ("copy-start.1", 1), ("copy-done.1", 2),
        ("copy.1", 80), ("fusion.3", 10)]
TRIP_NS = sum(ns for _, ns in TRIP)


def _made_session(text, reps=2, steps_per_call=10):
    ctx = types.SimpleNamespace(
        bench_dir=files.BENCH_DIR, workload={"mesh": [1, 1]},
        config={"model": {"dtype": "float32"}})
    return types.SimpleNamespace(
        ctx=ctx, ny=34, nx=66, ghost=1, rows={"multistep": {"reps": reps}},
        units=lambda row: reps * steps_per_call,
        compiled_text=lambda key: text)


def _view(session, made, batches=1):
    return types.SimpleNamespace(
        session=session, trace=made, peaks={"hbm_gbps": 819.0},
        traced=[run.Sample("multistep", 0.0, 1.0)] * batches)


def _reader(name):
    return files.load_module("layer_metrics", name)


def test_the_as_written_readers_on_a_hand_made_trace(capsys):
    text = as_written_text()
    lines = event_lines(text)
    # a batch of two calls of ten steps: two executions of ten trips
    call = [(lines[name], ns) for name, ns in TRIP] * 10
    view = _view(_made_session(text), made_trace([call, call]))
    passes = _reader("sw_field_passes_per_step")
    assert passes.in_place_writes(text) == {
        "dus.1": A_STRIP, "dus.2": A_STRIP, "fusion.1": 2 * A_STRIP,
        "dynamic-update-slice.3": INTERIOR}
    a_trip = (2 * FIELD              # the pad: twice its result
              + 2 * 2 * A_STRIP      # two fields' strips, read and written
              + 2 * INTERIOR         # the in-place write: its update
              + FIELD + 2 * INTERIOR  # one field in, two gradients out
              + 0 + 2 * SLAB         # the copy's start nothing, its done
              + 2 * FIELD            # a copy of a field
              + 2 * SLAB)            # a column sliced out of a field
    assert passes.step_bytes(view) == (a_trip, 0)
    assert passes.field_bytes(view.session) == FIELD
    assert passes.read(view) == pytest.approx(a_trip / FIELD)
    assert _reader("sw_hbm_roofline_share.as_written").read(view) == (
        pytest.approx(100 * (a_trip / 819e9) / (TRIP_NS * 1e-9)))
    capsys.readouterr()
    share = _reader("sw_exchange_device_share.as_written").read(view)
    # under sw/exchange.*: the strips (ke, unpack) and the column (h, pack)
    assert share == pytest.approx(100 * (7 + 10) / TRIP_NS)
    out = capsys.readouterr().out
    for row in ("ke | 0.007", "h | 0.010", "unpack | 0.007", "pack | 0.010",
                "friction | 0.250", "ab2 | 0.090", "copy %copy.1 | 0.080",
                "copy-done %copy-done.1 | 0.002"):
        assert f"perfbench:   {row} | " in out, row
    # the same time seen by op: the accepted reader beside it
    view.session.rows = {"multistep": {"reps": 2}}
    accepted = _reader("op_surface_device_share.sw").read(view)
    assert accepted == pytest.approx(share)
    assert _reader("sw_device_ops_per_step").read(view) == len(TRIP)


def test_the_share_reports_whatever_implements_the_step(capsys):
    """A program that runs a kernel call a step is read by the call's
    signature, as ``sw_hbm_roofline_share`` reads it, and to the same
    number: the cell keeps a share whatever a later PR makes of its
    step.  Its text carries no exchange scope, and that reader says so."""
    text = multistep_text(36, 68)
    call = a_step(event_lines(text), kernel_ns=239) * 10
    session = _made_session(text, reps=1)
    session.ny, session.nx, session.ghost = 32, 64, 2
    view = _view(session, made_trace([call]))
    field, slab = 36 * 68 * 4, 36 * 2 * 4
    least = (12 * field + 6 * slab + 8 + 12) + 3 * 2 * 2 * slab + 2 * 8
    mine = _reader("sw_hbm_roofline_share.as_written").read(view)
    assert mine == pytest.approx(100 * (least / 819e9) / 270e-9)
    assert mine == pytest.approx(_reader("sw_hbm_roofline_share").read(view))
    assert _reader("sw_field_passes_per_step").read(view) == (
        pytest.approx(least / field))
    capsys.readouterr()
    assert _reader("sw_exchange_device_share.as_written").read(view) is None
    assert "carries no sw/exchange.<field> scope" in capsys.readouterr().out


def test_the_readers_report_nothing_where_there_is_nothing_to_read(capsys):
    text = as_written_text()
    lines = event_lines(text)
    view = _view(_made_session(text), made_trace([]), batches=0)
    assert _reader("sw_field_passes_per_step").read(view) is None
    assert _reader("sw_hbm_roofline_share.as_written").read(view) is None
    # a trace of another count of programs is refused, never guessed at
    call = [(lines[name], ns) for name, ns in TRIP]
    view = _view(_made_session(text), made_trace([call] * 3))
    assert _reader("sw_exchange_device_share.as_written").read(view) is None
    assert "do not belong together" in capsys.readouterr().out
    # an event the text does not have
    view = _view(_made_session(text, reps=1),
                 made_trace([[("%fusion.77 = f32[2]{0} fusion(f32[2]{0} %x)", 5)]]))
    assert _reader("sw_field_passes_per_step").read(view) is None
    assert "has no fusion.77" in capsys.readouterr().out


def test_the_scopes_of_the_step_split_its_op_names():
    reader = _reader("sw_exchange_device_share.as_written")
    base = "jit(local_fn)/while/body/closed_call"
    assert reader.labels(f"{base}/sw/exchange.gx_u/{HALO}/unpack/dynamic_update_slice") == (
        "exchange", "gx_u", "unpack")
    assert reader.labels(f"{base}/sw/exchange.hc/{HALO}/wire/mpi4jax_tpu.sendrecv/ppermute") == (
        "exchange", "hc", "wire")
    assert reader.labels(f"{base}/sw/kinetic/jit(_pad)/concatenate") == (
        "phase", "kinetic", None)
    assert reader.labels(f"{base}/add") is None and reader.labels(None) is None
    # the program's own names are the ones the reader splits on
    from mpi4jax_tpu.models import shallow_water as sw

    assert reader.STEP_SCOPE == sw.STEP_SCOPE
    origin = scopes.Origin(op_name=f"{base}/sw/exchange.q/{HALO}/pack/slice",
                           scopes=scopes.scopes_of(
                               f"{base}/sw/exchange.q/{HALO}/pack/slice"))
    # and the harness, as it stands, books an exchange to the op surface
    # and a phase's array code to the line that emitted it
    assert origin.scopes == (HALO, "pack")
    assert scopes.scopes_of(f"{base}/sw/kinetic/mul") == ()


def test_the_real_session_hands_the_readers_its_multistep(session):
    """On the CPU the text is the CPU compiler's; it carries the scopes
    all the same, and every instruction of it has a signature."""
    text = session.compiled_text("multistep")
    reader = _reader("sw_exchange_device_share.as_written")
    known = {reader.labels(o.op_name) for o in scopes.origins(text).values()}
    fields = {label[1] for label in known if label and label[0] == "exchange"}
    phases = {label[1] for label in known if label and label[0] == "phase"}
    assert len(fields) == 12 and len(phases) == 7
    passes = _reader("sw_field_passes_per_step")
    assert passes.field_bytes(session) == 34 * 66 * 4
    assert all(nbytes > 0 for nbytes in passes.in_place_writes(text).values())


# -- the real cell's files ------------------------------------------------


def test_the_real_cell_lists_its_readers_and_the_accepted_ones_that_read_true():
    benchmark = files.load_benchmark(ROOT)
    mine = {m["name"] for m in files.metrics_of(benchmark, "per_layer", CELL)}
    assert mine == set(NEW_READERS) | set(ACCEPTED) | {
        "compile_s", "setup_after_chips_s"}
    for name in NEW_READERS:
        assert hasattr(files.load_module("layer_metrics", name), "read")
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "solver_rate"
    # the share of the roofline that needs a kernel call is not listed
    listed = next(m for m in benchmark["per_layer"]
                  if m["name"] == "sw_hbm_roofline_share")["workloads"]
    assert CELL not in listed
    assert {m["name"] for m in files.metrics_of(benchmark, "end_to_end", CELL)} == {
        "solver_rate", "solver_step_p95_us", "setup_s"}
    workload = files.load_json("workloads", CELL)
    cell = files.find_cell(benchmark, CELL)
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: cell[k] for k in ("config", "chips", "traffic", "why")}
    assert cell["chips"] == 1 and cell["traffic"] == "bench-domain-as-written-closed-loop"
    bench_cell = files.load_json("workloads", "sw-bench-1chip")
    assert workload["grid"] == bench_cell["grid"] == {"ny": 7200, "nx": 14400, "refine": 4}
    assert workload["mesh"] == bench_cell["mesh"] == [1, 1]
    # a call is 0.97 s on the chip: one makes a batch of 0.27 s or more
    assert workload["rows"] == [
        {"name": "multistep", "slots": 1, "reps": 1, "trace_batches": 3}]
    # appended after the cells PR 42 found, nothing before them moved;
    # what later PRs append after them is theirs to pin
    cells = [c["name"] for c in benchmark["workloads"]]
    assert cells[:cells.index(CELL) + 1] == [
        "sw-bench-1chip", "coll-2x2", "sw-job-1chip", "sw-restart-1chip", CELL]
    configs = [c["name"] for c in benchmark["configs"]]
    assert configs[:configs.index("shallow-water-as-written") + 1] == [
        "shallow-water", "collectives", "shallow-water-job",
        "shallow-water-restart", "shallow-water-as-written"]
    readers = [m["name"] for m in benchmark["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + 3] == NEW_READERS
    assert all(readers.index(name) < first for name in ACCEPTED)
    chips = [c["chips"] for c in benchmark["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 4)  # the driver's share


def test_the_configuration_is_upstreams_with_one_ghost_cell():
    config = files.load_json("configs", "shallow-water-as-written")
    accepted = files.load_json("configs", "shallow-water")
    model = dict(config["model"])
    assert model.pop("schedule") == "as_written" and model.pop("schedule_means")
    assert model == accepted["model"] | {"ghost": 1}
    assert config["architecture"] is None and config["reduced"] == []
    assert config["check"] == accepted["check"]
    assert {k: config["guarantees"][k] for k in accepted["guarantees"]} == (
        accepted["guarantees"])
    assert set(config["guarantees"]) - set(accepted["guarantees"]) == {"layout"}
    assert {k: config["assumed"][k] for k in ("perturbation", "run_length")} == {
        k: accepted["assumed"][k] for k in ("perturbation", "run_length")}
    assert set(config["assumed"]) == set(accepted["assumed"]) | {"exchange"}
    entry = next(c for c in files.load_benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["file"] == "perfbench/configs/shallow-water-as-written.json"
    # the program's default is this configuration's layout
    from mpi4jax_tpu.models import shallow_water as sw

    assert sw.SWConfig().ghost == config["model"]["ghost"] == 1
