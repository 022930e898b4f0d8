"""The cell of the linearised run on the CPU's virtual devices: the
configuration's file against ``shallow-water-adjoint``'s and the workload
file against ``BENCHMARK.json``; a small cell of the configuration added
as new files to a copy of the benchmark and run end to end; both
controls; and the four per-layer readers on made-up traces whose values
are computed by hand, and on a session that lacks the programs."""

import json
import re
import types

import jax
import pytest

from perfbench import run
from perfbench.harness import files
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import ROOT, TABLES, cell_args, make_copy

CELL = "sw-incremental-1chip"
TOY = "sw-incremental-toy"
ACCEPTED_CELLS = [
    "sw-bench-1chip", "coll-2x2", "sw-job-1chip", "sw-restart-1chip",
    "sw-as-written-1chip", "sw-output-restart-1chip", "sw-monitored-1chip",
    "sw-monitored-2x2-weak", "sw-adjoint-1chip"]
NEW_READERS = [
    "tangent_device_share.sw", "tangent_hbm_roofline_share",
    "tangent_exchange_device_share.sw", "incremental_memory_share"]
# the accepted metrics that read true on the new cell as they stand
APPENDED_TO = ["solver_rate", "solver_step_p95_us", "device_idle_share.sw",
               "sw_device_ops_per_step"]
PRODUCT_CHECKS = (
    {f"tangent_rel_l2_obs{k}_band{i}" for k in range(3) for i in range(4)}
    | {f"product_rel_l2_{k}_band{i}" for k in "huv" for i in range(4)})
# what holds the loop's last cost and its increment to the timed sweeps
LOOP_CHECKS = {"cost_off_the_increment", "step_along_the_increment_off_one",
               "misfit_after_over_before"}
FAULTS = ["increment_unchanged", "step_doubled", "no_conjugacy"]
CHECKS = {
    "nonfinite_after_window", "cost_rises", "curvatures_not_positive",
    "iterations_not_counted", "adjoint_test_rel",
    "max_abs_diff_h", "max_abs_diff_u", "max_abs_diff_v",
} | LOOP_CHECKS | PRODUCT_CHECKS


# -- the files ---------------------------------------------------------


def test_the_configuration_is_the_adjoints_window_linearised():
    config = files.load_json("configs", "shallow-water-incremental")
    adjoint = files.load_json("configs", "shallow-water-adjoint")
    accepted = files.load_json("configs", "shallow-water")
    assert config["model"] == adjoint["model"] == accepted["model"]
    assert config["architecture"] is None
    assert config["reduced"] == ["window.calls"]
    for key in ("calls", "steps", "control", "observed", "truth"):
        assert config["window"][key] == adjoint["window"][key]
    assert config["window"]["checkpoint"].startswith(adjoint["window"]["checkpoint"])
    assert config["window"]["calls"] == config["check"]["calls"] == 4
    assert config["check"]["limits"] == accepted["check"]["limits"]
    for key in ("bands", "band_rows", "row_blocks"):
        assert config["check"][key] == adjoint["check"][key]
    for key in ("precision", "every_step", "agreement"):
        assert config["guarantees"][key] == accepted["guarantees"][key]
    assert set(config["guarantees"]) - set(accepted["guarantees"]) == {
        "whole_window", "tangent", "product", "adjoint_test", "positive",
        "descent"}
    assumed = config["assumed"]
    for key in ("perturbation", "refinement", "observation_operator"):
        assert assumed[key] == adjoint["assumed"][key]
    assert set(assumed) == {
        "perturbation", "refinement", "run_length", "window_calls",
        "observation_operator", "observation_error", "first_guess",
        "background", "inner", "source_not_checked"}
    assert 0 < assumed["background"]["weight"] < 11.15  # under the data term's
    assert assumed["background"]["why"] and assumed["inner"]["iterations"] == 50
    check = config["check"]
    assert 0 < check["tangent_limit"] < 1 and 0 < check["adjoint_test_limit"] < 1
    assert set(check["product_limits"]) == {"h", "u", "v"}
    assert all(0 < v < 1 for v in check["product_limits"].values())
    assert set(check["limits_why"]) == {"tangent", "product", "adjoint_test", "loop"}
    # an increment left unchanged reads 1, 1 and 1 here: each limit is under it
    assert set(check["loop_limits"]) == LOOP_CHECKS
    assert all(0 < v < 1 for v in check["loop_limits"].values())
    entry = next(c for c in files.load_benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "perfbench/configs/shallow-water-incremental.json"
    assert entry["reduced"] == config["reduced"]


def test_the_plain_reference_imports_nothing_of_the_program():
    text = (ROOT / "perfbench/references/shallow-water-incremental.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+mpi4jax_tpu", text, re.M)
    assert not re.search(r"^\s*(import|from)\s+perfbench", text, re.M)
    # the accepted references, loaded by path, are the copy
    assert '_load("shallow-water-adjoint")' in text and "jax.jvp" in text


def test_the_workload_file_says_what_benchmark_json_says():
    benchmark = files.load_benchmark(ROOT)
    entry = files.find_cell(benchmark, CELL)
    workload = files.load_json("workloads", CELL)
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: entry[k] for k in ("config", "chips", "traffic", "why")}
    assert entry["config"] == "shallow-water-incremental" and entry["chips"] == 1
    assert entry["traffic"] == "bench-domain-dx2-inner-loop-closed-loop"
    assert len(entry["why"]) <= 200
    adjoint = files.load_json("workloads", "sw-adjoint-1chip")
    assert workload["grid"] == adjoint["grid"] and workload["mesh"] == [1, 1]
    assert workload["rows"] == [
        {"name": "iteration", "slots": 1, "reps": 1, "trace_batches": 2}]


def test_the_cell_is_appended_and_nothing_before_it_moved():
    benchmark = files.load_benchmark(ROOT)
    cells = [c["name"] for c in benchmark["workloads"]]
    assert cells[:10] == ACCEPTED_CELLS + [CELL]  # a later cell may follow
    configs = [c["name"] for c in benchmark["configs"]]
    assert configs[8] == "shallow-water-incremental" and configs[7] == (
        "shallow-water-adjoint")
    readers = [m["name"] for m in benchmark["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + 4] == NEW_READERS
    assert readers[first - 1] == "setup_unnamed_s"  # PR 56's last
    listed = {m["name"]: m for m in benchmark["end_to_end"] + benchmark["per_layer"]}
    for name in NEW_READERS:
        entry = listed[name]
        assert entry["moves"] == "solver_rate" and set(entry) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["workloads"][0] == CELL  # a later cell may follow
        assert (ROOT / f"perfbench/layer_metrics/{name}.py").is_file()
    assert [listed[n]["layer"] for n in NEW_READERS] == [
        "programs", "kernels", "op surface", "programs"]
    for name in APPENDED_TO:  # after what was there; a later cell may follow
        cells_of = listed[name]["workloads"]
        assert cells_of.index(CELL) == sum(c in ACCEPTED_CELLS for c in cells_of)
    assert {m["name"] for m in listed.values()
            if CELL in m.get("workloads", ())} == set(NEW_READERS + APPENDED_TO)
    chips = [c["chips"] for c in benchmark["workloads"][:10]]
    assert chips.count(4) == 2  # the share is spent: the new cell takes one chip


# -- a small cell, end to end ---------------------------------------------


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with a cell of ``shallow-water-incremental``
    more: 32x64 cells on one device, a window of two calls, observed over
    2x2 cells."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_incremental"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads(
        (bench / "configs/shallow-water-incremental.json").read_text())
    config["name"] = "shallow-water-incremental-toy"
    config["window"]["calls"] = 2
    config["check"].update(calls=2, row_blocks=1, band_rows=8)
    # a toy's loop runs to its cap many times a window and may have just
    # begun again: after one iteration its misfit reads 0.42 to 0.50
    config["check"]["loop_limits"]["misfit_after_over_before"] = 0.7
    (bench / "configs/shallow-water-incremental-toy.json").write_text(
        json.dumps(config))
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == "shallow-water-incremental")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-incremental-toy",
        file="perfbench/configs/shallow-water-incremental-toy.json"))
    cell = {
        "config": "shallow-water-incremental-toy", "traffic": TOY, "chips": 1,
        "why": "a test cell", "mesh": [1, 1],
        "grid": {"ny": 32, "nx": 64, "refine": 2},
        "rows": [{"name": "iteration", "slots": 1, "reps": 1}],
    }
    (bench / f"workloads/{TOY}.json").write_text(json.dumps(cell))
    benchmark["workloads"].append({
        k: cell[k] for k in ("config", "traffic", "chips", "why")} | {"name": TOY})
    for section in ("end_to_end", "per_layer"):
        for metric in benchmark[section]:
            if CELL in metric.get("workloads", []):
                metric["workloads"].append(TOY)
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


@pytest.fixture(scope="module")
def session(copy):
    root, bench = copy
    workload = files.load_json("workloads", TOY, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    made = driver.setup(
        run.Context(config, workload, 2**31 + 59, jax.devices(), bench))
    for _ in range(3):
        made.batch("iteration")
    return made


def test_the_cell_runs_and_every_check_is_beside_its_limit(copy, capsys):
    result = run.run_cell(
        cell_args(TOY, seconds=1.0), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    assert set(result["checks"]) == CHECKS
    for name in LOOP_CHECKS:
        assert result["checks"][name]["value"] <= result["checks"][name]["limit"] < 1
    out = capsys.readouterr().out
    assert "background weight" in out and "iterations run" in out


def test_the_controls_are_not_correct_and_the_program_is(session):
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert {c["name"] for c in sound} == CHECKS
    control = session.control()
    tests = {f"adjoint_test_rel_band{i}" for i in range(4)}
    assert {c["name"] for c in control} == PRODUCT_CHECKS | tests | {
        name + "_no_exchange_tangent" for name in PRODUCT_CHECKS | tests} | {
        f"{name}_{fault}" for name in LOOP_CHECKS for fault in FAULTS}
    # jax's own jvp and vjp of the float32 reference are each other's
    # transpose; of the bfloat16 one to bfloat16's rounding
    float32 = [c for c in sound if c["name"] == "adjoint_test_rel"]
    lower = [c for c in control if c["name"] in tests]
    assert float32[0]["value"] < 1e-5 < min(c["value"] for c in lower)
    # the reference carried in bfloat16 in the program's place, and the
    # reference without the tangent of its boundary code: each refused,
    # and by more than one band's one number
    for refused_by in ("", "_no_exchange_tangent"):
        refused = [c for c in control if c["value"] > c["limit"]
                   and c["name"].endswith(refused_by)
                   and (refused_by or "exchange" not in c["name"])]
        assert len(refused) >= 6, (refused_by, control)
    # the loop with a fault in its updates: an increment left where it was
    # by all three, a step twice as long by the cost and the line search,
    # steepest descent by the line search along its increment
    for fault, by in (("increment_unchanged", LOOP_CHECKS),
                      ("step_doubled", {"cost_off_the_increment",
                                        "step_along_the_increment_off_one"}),
                      ("no_conjugacy", {"step_along_the_increment_off_one"})):
        refused = {c["name"][:-len(fault) - 1] for c in control
                   if c["name"].endswith("_" + fault) and c["value"] > c["limit"]}
        assert by <= refused, (fault, control)


def test_the_loops_counters_and_costs(session):
    counted = session.facts()["incremental"]
    assert set(counted) == {
        "iterations", "window_steps", "trajectory_bytes", "vector_bytes",
        "costs", "curvatures", "run"}
    assert counted["window_steps"] == 21
    # set-up's warm batch and the three of the fixture; a check since
    # began the loop again, and `run` counts through it
    run, since = counted["run"], counted["iterations"]
    assert run >= 4 and since in (0, run)
    session.batch("iteration")
    session.batch("iteration")
    counted = session.facts()["incremental"]
    assert counted["iterations"] == since + 2 and counted["run"] == run + 2
    assert counted["iterations"] == session.fit.enqueued
    assert len(counted["costs"]) == since + 3
    assert len(counted["curvatures"]) == since + 2
    # here the step is array code: its tendencies are interior-shaped
    state = 3 * ((32 + 4) * (64 + 4) + 32 * 64) * 4
    assert counted["trajectory_bytes"] == (2 + 10) * state
    assert counted["vector_bytes"] == 4 * 3 * 32 * 64 * 4
    costs = counted["costs"]
    assert all(b < a for a, b in zip(costs, costs[1:])), costs
    assert all(c > 0 for c in counted["curvatures"])


def test_the_loop_is_capped_and_begins_again(copy):
    root, bench = copy
    workload = files.load_json("workloads", TOY, bench)
    config = files.load_json("configs", workload["config"], bench)
    config["assumed"]["inner"]["iterations"] = 2
    driver = files.load_module("drivers", config["driver"], bench)
    made = driver.setup(run.Context(config, workload, 59, jax.devices(), bench))
    for _ in range(4):  # set-up's is the first
        made.batch("iteration")
    assert made.iterations == 5 and made.fit.stats()["iterations"] == 1
    with pytest.raises(ValueError, match="capped at 2"):
        made.fit.iterate(2)


# -- the per-layer readers on made-up traces ------------------------------

TANGENT_WALK = ('metadata={op_name="jit(tangent)/sw/adjoint/tangent/jvp()/while/body/'
                'closed_call/jit(wide_step)" stack_frame_id=3}')
TANGENT_MUL = ('metadata={op_name="jit(tangent)/sw/adjoint/tangent/jvp()/while/body/'
               'closed_call/jvp()/mul" stack_frame_id=3}')
TANGENT_UNPACK = (
    'metadata={op_name="jit(tangent)/sw/adjoint/tangent/jvp()/while/body/closed_call/'
    'jvp(mpi4jax_tpu.halo_exchange_2d)/unpack/dynamic_update_slice" stack_frame_id=3}')
TANGENT_MEAN = ('metadata={op_name="jit(tangent)/sw/adjoint/tangent/reduce_window" '
                'stack_frame_id=3}')
RECOMPUTE = ('metadata={op_name="jit(adjoint)/transpose(jvp(sw/adjoint/recompute))'
             '/while/body/wide_step" stack_frame_id=3}')
STEP_VJP = ('metadata={op_name="jit(adjoint)/transpose(jvp(sw/adjoint/recompute))'
            '/while/body/sw/adjoint/step_vjp/mul" stack_frame_id=3}')
ADJOINT_UNPACK = (
    'metadata={op_name="jit(adjoint)/transpose(jvp(sw/adjoint/recompute))/while/'
    'body/sw/adjoint/step_vjp/transpose(jvp(mpi4jax_tpu.halo_exchange_2d))/'
    'transpose/unpack/dynamic_update_slice" stack_frame_id=3}')
SPREAD = 'metadata={op_name="jit(adjoint)/sw/adjoint/cost/dot_general" stack_frame_id=3}'
UPDATE = 'metadata={op_name="jit(step)/sw/adjoint/update/add" stack_frame_id=3}'

TANGENT_TEXT = f'''HloModule jit_tangent, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.0 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  %slice.1 = f32[32,2]{{1,0}} slice(%p0), slice={{[0:32], [2:4]}}, {TANGENT_UNPACK}
  %kernel.2 = f32[32,64]{{1,0}} custom-call(%p0, %slice.1), custom_call_target="tpu_custom_call", {TANGENT_WALK}
  %fusion.3 = f32[32,64]{{1,0}} fusion(%kernel.2, %p0), kind=kLoop, calls=%fused.3, {TANGENT_MUL}
  %fusion.4 = f32[32,64]{{1,0}} fusion(%fusion.3, %slice.1), kind=kLoop, calls=%fused.4, {TANGENT_UNPACK}
  %reduce.5 = f32[16,32]{{1,0}} reduce-window(%fusion.4), {TANGENT_MEAN}
  ROOT %copy.6 = f32[32,64]{{1,0}} copy(%fusion.4)
}}
'''
ADJOINT_TEXT = f'''HloModule jit_adjoint, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.1 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  %convolution.1 = f32[32,64]{{1,0}} fusion(%p0), kind=kLoop, calls=%fused.1, {SPREAD}
  %kernel.2 = f32[32,64]{{1,0}} custom-call(%p0, %convolution.1), custom_call_target="tpu_custom_call", {RECOMPUTE}
  %fusion.3 = f32[32,64]{{1,0}} fusion(%kernel.2, %p0), kind=kLoop, calls=%fused.3, {STEP_VJP}
  ROOT %fusion.4 = f32[32,64]{{1,0}} fusion(%fusion.3, %p0), kind=kLoop, calls=%fused.4, {ADJOINT_UNPACK}
}}
'''
UPDATE_TEXT = f'''HloModule jit_step, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.2 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  ROOT %fusion.1 = f32[32,64]{{1,0}} fusion(%p0), kind=kLoop, calls=%fused.1, {UPDATE}
}}
'''
# nanoseconds of the made programs' events, in the texts' order
TIMES = {
    "tangent": {"slice.1": 10, "kernel.2": 200, "fusion.3": 900, "fusion.4": 80,
                "reduce.5": 30, "copy.6": 60},
    "adjoint": {"convolution.1": 40, "kernel.2": 200, "fusion.3": 300,
                "fusion.4": 90},
    "update": {"fusion.1": 100},
}
BUSY = sum(ns for times in TIMES.values() for ns in times.values())


def _event_names(text):
    return {name: line.strip() for line, name in
            re.findall(r"^\s*(?:ROOT\s+)?(%([\w.\-]+) = .*)$", text, re.M)}


def _made(batches=2):
    """A session of three made-up programs and a trace of ``batches``
    batches of them on one chip."""
    driver = files.load_module("drivers", "shallow_water_incremental")
    texts = {"tangent": TANGENT_TEXT, "adjoint": ADJOINT_TEXT,
             "update": UPDATE_TEXT}
    made = Trace()
    chip = "/device:TPU:0"
    made.device_ops[chip], made.modules[chip] = [], []
    t = 0.0
    for _ in range(batches):
        for key, times in TIMES.items():
            lines = _event_names(texts[key])
            start = t
            for name, ns in times.items():
                made.device_ops[chip].append(Event(lines[name], t, float(ns)))
                t += ns
            made.modules[chip].append(Event("jit_local_fn(1)", start, t - start))
            t += 5.0
    session = types.SimpleNamespace(
        ctx=types.SimpleNamespace(bench_dir=files.BENCH_DIR),
        rows={"iteration": {"reps": 1}}, window_steps=41,
        compiled_text=texts.__getitem__,
        units=lambda row: 41)
    session.traced_programs = lambda traced: driver.Session.traced_programs(
        session, traced)
    session.traced_events = lambda view: driver.Session.traced_events(session, view)
    traced = [run.Sample("iteration", 0.0, 1.0) for _ in range(batches)]
    facts = {"incremental": {"iterations": 7, "window_steps": 41,
                             "trajectory_bytes": 14 * 100, "vector_bytes": 1200}}
    return run.View(session, facts, [], traced, made, {}, {},
                    {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})


def _reader(name):
    return files.load_module("layer_metrics", name)


def test_the_scopes_of_a_tangent_sweep_are_read_as_text():
    driver = files.load_module("drivers", "shallow_water_incremental")
    name = lambda metadata: re.search(r'op_name="([^"]*)"', metadata)[1]  # noqa: E731
    assert driver.phase_of(name(TANGENT_WALK)) == "tangent"
    assert driver.phase_of(name(TANGENT_UNPACK)) == "tangent"
    assert driver.phase_of(name(STEP_VJP)) == "step_vjp"  # the innermost
    assert driver.phase_of(name(SPREAD)) == "cost"
    assert driver.phase_of(name(UPDATE)) == "update"
    assert driver.exchange_of(name(TANGENT_UNPACK)) == (
        "halo_exchange_2d", False, "unpack")
    assert driver.exchange_of(name(ADJOINT_UNPACK)) == (
        "halo_exchange_2d", True, "unpack")
    assert driver.exchange_of(name(TANGENT_MUL)) is None
    assert "tangent" in driver.PHASES and "forward" not in driver.PHASES


def test_the_device_share_is_the_tangent_scope_over_busy(capsys):
    view = _made()
    # the program's copy carries no scope: printed, and left out
    want = 100.0 * (10 + 200 + 900 + 80 + 30) / BUSY
    assert _reader("tangent_device_share.sw").read(view) == pytest.approx(want)
    out = capsys.readouterr().out
    assert "sw/adjoint/tangent" in out
    assert "under no scope: tangent: copy %copy.6" in out
    assert "the tangent program under no scope | " in out
    assert "sw/adjoint/step_vjp" in out and "sw/adjoint/update" in out


def test_the_exchange_share_is_the_tangent_sweeps_exchanges(capsys):
    view = _made()
    got = _reader("tangent_exchange_device_share.sw").read(view)
    assert got == pytest.approx(100.0 * (10 + 80) / BUSY)  # not the adjoint's 90
    out = capsys.readouterr().out
    assert "tangent | halo_exchange_2d | forward | unpack" in out
    # the copy's 60 ns a batch of 41 steps, under no scope
    assert ("under no scope, where an exchange would be missed | "
            f"{60 / 41 / 1e3:.3f} | {100 * 60 * 2 / BUSY / 2:.3f}") in out
    assert "adjoint | halo_exchange_2d | transposed | unpack" in out


def test_the_roofline_share_counts_the_sweeps_bytes_over_its_time(capsys):
    view = _made()
    field, slab, coarse = 32 * 64 * 4, 32 * 2 * 4, 16 * 32 * 4
    # the tangent program's events: the slice, the two fusions, the
    # window's sums and the copy twice their result, the kernel call its
    # signature
    moved = (2 * slab + (2 * field + slab) + 2 * field + 2 * field
             + 2 * coarse + 2 * field)
    seconds = (10 + 200 + 900 + 80 + 30 + 60) / 1e9
    want = 100.0 * moved / 819e9 / seconds
    assert _reader("tangent_hbm_roofline_share").read(view) == pytest.approx(want)
    assert "the tangent sweep takes" in capsys.readouterr().out


def test_the_memory_share_is_the_fullest_program_and_what_is_held_beside_it(capsys):
    view = _made()
    sweep = types.SimpleNamespace(
        peak_memory_in_bytes=9_000_000_000, temp_size_in_bytes=5_000_000_000,
        argument_size_in_bytes=3_000_000_000, output_size_in_bytes=1_000_000_000)
    small = types.SimpleNamespace(
        peak_memory_in_bytes=2_000_000_000, temp_size_in_bytes=100_000_000,
        argument_size_in_bytes=1_200_000_000, output_size_in_bytes=900_000_000)
    view.session.compiled = lambda key: types.SimpleNamespace(
        memory_analysis=lambda: {"tangent": sweep, "adjoint": sweep,
                                 "update": small}[key])
    view.session.held_bytes = lambda: 3_600_000_000
    # a sweep's peak and the 0.6e9 held beside its arguments; the update's
    # is 2e9 + 2.4e9
    assert _reader("incremental_memory_share").read(view) == pytest.approx(60.0)
    out = capsys.readouterr().out
    assert "600000000 bytes held beside" in out and "vectors 1200 by shapes" in out


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name):
    """A session of another family (the parent's programs: no tangent
    sweep, no counters) reads ``None`` and does not raise."""
    bare = types.SimpleNamespace(
        ctx=types.SimpleNamespace(bench_dir=files.BENCH_DIR),
        rows={"multistep": {"reps": 1}}, units=lambda row: 10)
    view = run.View(bare, {"steps_per_call": 10}, [], [], Trace(), {}, {},
                    {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})
    assert _reader(name).read(view) is None


def test_a_reader_returns_nothing_on_a_trace_without_the_tangent_scope():
    """The adjoint cell's programs under this cell's readers: no
    ``sw/adjoint/tangent`` anywhere, so nothing is reported."""
    view = _made()
    texts = {"tangent": ADJOINT_TEXT, "adjoint": ADJOINT_TEXT, "update": UPDATE_TEXT}
    view.session.compiled_text = texts.__getitem__
    made = Trace()
    chip = "/device:TPU:0"
    made.device_ops[chip], made.modules[chip] = [], []
    t = 0.0
    for key in ("adjoint", "adjoint", "update"):
        lines = _event_names(texts[key])
        start = t
        for name, ns in TIMES[key].items():
            made.device_ops[chip].append(Event(lines[name], t, float(ns)))
            t += ns
        made.modules[chip].append(Event("jit_local_fn(1)", start, t - start))
        t += 5.0
    view = run.View(view.session, view.facts, [], view.traced[:1], made, {}, {},
                    view.peaks, {})
    for name in NEW_READERS[:3]:
        assert _reader(name).read(view) is None


def test_a_traced_toy_run_prints_every_listed_metric_or_a_reason(copy, session, capsys):
    """The readers on the toy session's real programs (the CPU's: array
    code, so no kernel call) and a trace made of their own instructions."""
    keys = ("tangent", "adjoint", "update")
    session.batch("iteration")  # vectors at hand, whatever ran before
    texts = {key: session.compiled_text(key) for key in keys}
    made = Trace()
    chip = "/device:CPU:0"
    made.device_ops[chip], made.modules[chip] = [], []
    t = 0.0
    for key in keys:
        start = t
        for name, line in _event_names(texts[key]).items():
            if re.search(r" (fusion|dynamic-update-slice|copy|slice)\(", line):
                made.device_ops[chip].append(Event(line, t, 10.0))
                t += 10.0
        made.modules[chip].append(Event("jit_local_fn(1)", start, t - start))
        t += 5.0
    view = run.View(session, session.facts(), [], [run.Sample("iteration", 0.0, 1.0)],
                    made, {}, {}, {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})
    values = {name: _reader(name).read(view) for name in NEW_READERS}
    out = capsys.readouterr().out
    assert 0 < values["tangent_device_share.sw"] <= 100
    assert values["tangent_exchange_device_share.sw"] > 0
    assert "tangent | halo_exchange_2d | forward" in out
    assert "transposed" in out and "sw/adjoint/step_vjp" in out
    assert values["tangent_hbm_roofline_share"] > 0
    # the CPU's compiled program has a peak or says why not
    assert values["incremental_memory_share"] or "gives no peak" in out
