"""The restarted-job cell on the CPU's virtual devices: two small cells
of the configuration ``shallow-water-restart`` added as new files to a
copy of the benchmark, run end to end (set-up saves, kills and resumes;
the window saves every few calls); their three controls; the readers on
made-up traces whose values are computed by hand; the real cell's
files; and the plain reference's saved-and-loaded walk against its
uninterrupted one."""

import json
import re
import types

import jax
import numpy as np
import pytest

from perfbench import run
from perfbench.harness import files, scopes
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import (
    ROOT, a_step, cell_args, event_lines, make_copy, multistep_text,
    program_text)

CHIP = "/device:TPU:0"
CELLS = ["sw-restart-toy-1x1", "sw-restart-toy-2x2"]
NEW_READERS = ["save_stall_share.sw", "save_commit_s",
               "checkpoint_device_share.sw",
               "checkpoint_stage_hbm_roofline_share", "resume_s"]
ACCEPTED = ["device_idle_share.sw", "state_copy_bytes_per_call.sw",
            "sw_hbm_roofline_share.job", "op_surface_device_share.job"]
# PR 38's readers of the job's spans, listed since PR 39: the first five
# on both job cells, the save's two here alone
HOST_SPANS = ["host_device_clock_bracket_us", "idle_in_sync_share.sw",
              "idle_in_job_share.sw", "idle_unnamed_share.sw",
              "job_issue_us_per_call.sw", "save_fetch_busy_share",
              "save_write_busy_share"]
STATE = ("h", "u", "v", "dh", "du", "dv")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with two cells of ``shallow-water-restart``
    more: 32x64 cells, a save every 3 calls in pieces of at most 2 KB."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_restart"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / "configs/shallow-water-restart.json").read_text())
    config["name"] = "shallow-water-restart-toy"
    config["restart"].update(every_calls=3, ahead_bytes=4096)
    config["check"].update(row_blocks=2)
    (bench / "configs/shallow-water-restart-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == "shallow-water-restart")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-restart-toy",
        file="perfbench/configs/shallow-water-restart-toy.json"))
    for name, mesh in zip(CELLS, ([1, 1], [2, 2])):
        cell = {
            "config": "shallow-water-restart-toy", "traffic": name,
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
            if "sw-restart-1chip" in metric.get("workloads", []):
                metric["workloads"] += CELLS
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _session(copy, cell, seed=2**31 + 5, batches=1):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    session = driver.setup(
        run.Context(config, workload, seed, jax.devices(), bench))
    for _ in range(batches):
        session.batch("multistep")
    return session


@pytest.mark.parametrize("cell", CELLS)
def test_the_restart_cell_runs_and_every_check_is_beside_its_limit(copy, cell):
    result = run.run_cell(
        cell_args(cell), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    names = {"saves_not_started", "saves_unacknowledged", "saves_out_of_order",
             "saves_kept_off", "temporaries_left", "nonfinite_after_window",
             "resaved_step_off"}
    names |= {f"{kind}_{k}" for kind in ("resaved_differing", "resumed_differing")
              for k in STATE}
    names |= {f"{kind}_{k}" for kind in ("max_abs_diff", "reference_restart_diff")
              for k in "huv"}
    assert set(result["checks"]) == names
    # the zeros are zeros: bit for bit, every save acknowledged in order
    for name, c in result["checks"].items():
        if name.startswith("reference_restart_diff"):
            assert c["value"] == 0 and 0 < c["limit"] <= 2e-6, name
        elif not name.startswith("max_abs_diff"):
            assert c == {"value": 0, "limit": 0}, name


def test_the_three_controls_of_a_restart_are_not_correct(copy, cell=CELLS[0]):
    session = _session(copy, cell, batches=2)
    # two batches of two calls from call 1: one save of the window's own
    assert session.job.stats()["saves_started"] == 1
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    control = {c["name"]: c for c in session.control()}

    def fails(name):
        return control[name]["value"] > control[name]["limit"]

    # the reference in bfloat16 in the resumed fields' place
    assert any(fails(f"bfloat16_diff_{k}") for k in "huv"), control
    for mistake in ("tendencies", "stale"):
        # not the uninterrupted run, and outside the reference's limits
        assert all(fails(f"{mistake}_differing_{k}") for k in "huv"), control
        assert any(fails(f"{mistake}_diff_{k}") for k in "huv"), control
    assert all(fails(f"reference_dropped_diff_{k}") for k in "huv"), control


def test_the_window_starts_from_a_resumed_job_and_the_kill_left_nothing(copy):
    session = _session(copy, CELLS[0], seed=11, batches=0)
    job = session.job
    # resumed after the one call of the job before it, nothing saved yet
    assert (job.step, job.calls) == (11, 1) and session.calls_at_setup == 1
    assert session.at_setup["saves_started"] == 0 and job.saves == []
    assert session.at_setup["restore_read_s"] > 0 and session.resume_s > 0
    assert job.series.steps() == [11] and not job.series.leftovers()
    session.batch("multistep")
    session.batch("multistep")  # calls 2-5: the save after call 3 is on its way
    assert [r["step"] for r in session.window_saves()] == [31]
    assert job.series.steps() == [11, 31]


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


def _trace(executions):
    made = Trace()
    t = 0.0
    made.device_ops[CHIP], made.modules[CHIP] = [], []
    for events in executions:
        start = t
        for name, ns in events:
            made.device_ops[CHIP].append(Event(name, t, float(ns)))
            t += ns
        made.modules[CHIP].append(Event("jit_local(1)", start, t - start))
        t += 7.0
    return made


@pytest.fixture(scope="module")
def session(copy):
    return _session(copy, CELLS[0], seed=19, batches=0)


def _view(session, made, batches=2):
    return types.SimpleNamespace(
        session=session, trace=made, facts=session.facts(),
        peaks={"hbm_gbps": 819.0}, samples=[],
        traced=[run.Sample("multistep", 0.0, 1.0)] * batches)


def _reader(copy, name):
    return files.load_module("layer_metrics", name, copy[1])


def _program_lines(session):
    multi = session.compiled_text("multistep")
    stage = session.compiled_text("stage")
    cut = _pick(stage, lambda o, line: o.scopes[:2] == (
        "mpi4jax_tpu.checkpoint", "stage"))
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    halo = _pick(multi, lambda o, line: o.scopes[:1] == (
        "mpi4jax_tpu.halo_exchange_2d",))
    return step, halo, cut


def test_the_traced_window_holds_one_save_between_two_calls(session):
    # two batches of two calls from call 1, a save every 3: after call 3
    traced = [run.Sample("multistep", 0.0, 1.0)] * 2
    assert session.traced_programs(None, traced)[1] == [
        "multistep", "multistep", "stage", "multistep", "multistep"]
    # the real cell: twelve batches of four from call 1, a save every 48
    workload = files.load_json("workloads", "sw-restart-1chip")
    real = types.SimpleNamespace(
        calls_at_setup=1, rows={r["name"]: r for r in workload["rows"]},
        every=files.load_json("configs", "shallow-water-restart")["restart"]["every_calls"])
    batches = workload["rows"][0]["trace_batches"]
    executions = type(session).traced_programs(real, None, traced[:1] * batches)[1]
    assert executions.count("stage") == 1 and executions.index("stage") == 47
    assert len(executions) == 49 and batches == 12


def test_checkpoint_readers_on_a_hand_made_trace(copy, session, capsys):
    step, halo, cut = _program_lines(session)
    call = [(step, 800), (halo, 100)]
    made = _trace([call, call, [(cut, 60), (cut, 40)], call, call])
    view = _view(session, made)
    assert _reader(copy, "checkpoint_device_share.sw").read(view) == (
        pytest.approx(100 * 100 / 3700))
    state_bytes = sum(a.nbytes for a in session.job.state)
    # on this backend the step is array code: interior-shaped tendencies
    assert state_bytes == 3 * (36 * 68 + 32 * 64) * 4
    # the staging program as the job makes it: the state in, its pieces
    # out, which is what its compiled text says
    assert scopes.signature(session.compiled_text("stage")) == (
        state_bytes, state_bytes)
    assert _reader(copy, "checkpoint_stage_hbm_roofline_share").read(view) == (
        pytest.approx(100 * (2 * state_bytes / 819e9) / 100e-9))
    # the accepted readers read the multistep's executions alone
    assert _reader(copy, "state_copy_bytes_per_call.sw").read(view) == 0.0
    assert _reader(copy, "op_surface_device_share.job").read(view) == (
        pytest.approx(100 * 100 / 900))
    # array code runs no kernel call: nothing says what a step's least is
    capsys.readouterr()
    assert _reader(copy, "sw_hbm_roofline_share.job").read(view) is None
    assert "ran no kernel call" in capsys.readouterr().out


def test_the_steps_floor_in_a_window_with_a_save_is_its_kernel_calls(
        copy, session, capsys):
    """The restarted job's programs as the TPU backend compiles them,
    made by hand (the CPU runs no kernel call): the step's least bytes
    are read from the multistep's executions alone, and a staging
    program that hands back half the state has a floor of what it is
    handed and what it hands back, not of twice the state."""
    texts = {"multistep": multistep_text(36, 68),
             "stage": program_text((36, 68), (18, 68), "mpi4jax_tpu.checkpoint/stage", 6)}
    made_session = types.SimpleNamespace(
        calls_at_setup=1, every=3, rows=session.rows,
        ctx=session.ctx, compiled_text=texts.__getitem__)
    made_session.traced_programs = (
        lambda *a: type(session).traced_programs(made_session, *a))
    call = a_step(event_lines(texts["multistep"]), kernel_ns=239) * 10
    cut = [(event_lines(texts["stage"])[f"out.{i}"], 25) for i in range(6)]
    view = _view(session, _trace([call, call, cut, call, call]))
    view.session = made_session
    field, slab = 36 * 68 * 4, 36 * 2 * 4
    least = (12 * field + 6 * slab + 8 + 12) + 3 * 2 * 2 * slab + 2 * 8
    assert _reader(copy, "sw_hbm_roofline_share.job").read(view) == (
        pytest.approx(100 * (least / 819e9) / 270e-9))
    assert _reader(copy, "checkpoint_stage_hbm_roofline_share").read(view) == (
        pytest.approx(100 * (9 * field / 819e9) / 150e-9))
    assert _reader(copy, "checkpoint_device_share.sw").read(view) == (
        pytest.approx(100 * 150 / (4 * 2700 + 150)))
    assert "do not belong together" not in capsys.readouterr().out


def test_a_traced_window_without_its_one_save_reports_nothing(copy, session, capsys):
    step, halo, cut = _program_lines(session)
    call = [(step, 800), (halo, 100)]
    made = _trace([call] * 2)
    view = _view(session, made, batches=1)
    # calls 2 and 3 of a job that saves every 32: no save yet
    view.session = types.SimpleNamespace(
        calls_at_setup=1, every=32, rows=session.rows,
        traced_programs=lambda *a: type(session).traced_programs(view.session, *a))
    assert _reader(copy, "checkpoint_device_share.sw").read(view) is None
    assert "not one" in capsys.readouterr().out
    assert _reader(copy, "checkpoint_stage_hbm_roofline_share").read(view) is None
    assert "ran no staging program" in capsys.readouterr().out
    # a trace of other programs is refused, never guessed at
    view = _view(session, _trace([call] * 3))
    assert _reader(copy, "checkpoint_device_share.sw").read(view) is None
    assert "do not belong together" in capsys.readouterr().out


def test_host_clock_readers_read_the_jobs_own_counters(copy, session):
    stats = dict(session.at_setup)
    stats["save_wait_s"] += 0.05
    saves = [{"step": s, "bytes": 8, "stage_s": 0.1, "commit_s": c}
             for s, c in ((31, 0.4), (61, 1.0), (91, 0.5))]
    fake = types.SimpleNamespace(
        at_setup=session.at_setup, resume_s=1.25, window_saves=lambda: saves,
        job=types.SimpleNamespace(stats=lambda: stats))
    view = types.SimpleNamespace(
        session=fake, samples=[run.Sample("multistep", 0.0, 1.5)],
        traced=[run.Sample("multistep", 2.0, 2.5)])
    assert _reader(copy, "save_stall_share.sw").read(view) == (
        pytest.approx(100 * 0.05 / 2.0))
    assert _reader(copy, "save_commit_s").read(view) == 0.5
    assert _reader(copy, "resume_s").read(view) == 1.25
    fake.window_saves = lambda: []
    view.samples, view.traced = [], []
    assert _reader(copy, "save_stall_share.sw").read(view) is None
    assert _reader(copy, "save_commit_s").read(view) is None
    # a program without the restart (the parent's) has nothing to read
    bare = types.SimpleNamespace(session=types.SimpleNamespace())
    assert _reader(copy, "resume_s").read(bare) is None


def test_the_real_cell_lists_its_readers_and_the_accepted_ones_that_read_true():
    benchmark = files.load_benchmark(ROOT)
    mine = {m["name"] for m in
            files.metrics_of(benchmark, "per_layer", "sw-restart-1chip")}
    assert mine == set(NEW_READERS) | set(ACCEPTED) | set(HOST_SPANS) | {
        "compile_s", "setup_after_chips_s"}
    for name in NEW_READERS + HOST_SPANS[5:]:
        assert hasattr(files.load_module("layer_metrics", name), "read")
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        # membership: a later cell that the reader reads true on lists it too
        assert "sw-restart-1chip" in entry["workloads"]
    assert {m["name"] for m in
            files.metrics_of(benchmark, "end_to_end", "sw-restart-1chip")} == {
                "solver_rate", "solver_step_p95_us", "setup_s"}
    workload = files.load_json("workloads", "sw-restart-1chip")
    cell = files.find_cell(benchmark, "sw-restart-1chip")
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: cell[k] for k in ("config", "chips", "traffic", "why")}
    bench_cell = files.load_json("workloads", "sw-bench-1chip")
    assert workload["grid"] == bench_cell["grid"]
    assert workload["mesh"] == bench_cell["mesh"]
    # a batch of four calls (0.16 s): the job's calls are enqueued ahead
    # and its saves fall between them; the bench cell's is eight (0.31 s)
    assert workload["rows"][0]["reps"] == 4 and bench_cell["rows"][0]["reps"] == 8
    assert cell["traffic"] == "bench-domain-save-every-48-calls"
    config = files.load_json("configs", "shallow-water-restart")
    assert config["model"] == files.load_json("configs", "shallow-water")["model"]
    assert config["architecture"] is None and list(config["reduced"]) == ["restart"]
    assert config["restart"] | {"what": 0} == {
        "every_calls": 48, "keep": 2, "asynchronous": True,
        "ahead_bytes": 160000000, "fields": list(STATE), "what": 0}
    assert config["check"]["bit_for_bit"] == 0
    chips = [c["chips"] for c in benchmark["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 4)  # the driver's share


# -- the reference ---------------------------------------------------------


@pytest.mark.parametrize("band", [(0, 24), (6, 24)])
def test_the_references_saved_and_loaded_walk_is_its_uninterrupted_one(
        tmp_path, band):
    ref = files.load_module("references", "shallow-water-restart")
    plain = files.load_module("drivers", "shallow_water")
    config = files.load_json("configs", "shallow-water-restart")
    modes = plain.mode_table(9, config["assumed"]["perturbation"])
    lo, hi = band
    start = tuple(a[lo:hi] for a in plain.make_fields(
        config["model"], 24, 48, 5000.0, 5000.0)(modes))
    params = ref.parameters(config["model"], 5000.0, 5000.0)
    want = ref.run(*start, params, 41, "float32", lo)
    got = ref.run_restarted(*start, params, 20, 20, tmp_path, "float32", lo)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # what it wrote is numpy's own: six arrays and the step count
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        [f"{k}.npy" for k in STATE] + ["step.npy"])
    state, step = ref.load(tmp_path)
    assert step == 21 and state[0].shape == (hi - lo + 2, 50)
    assert state[3].shape == (hi - lo, 48) and np.abs(state[4]).max() > 0
    # a load that drops the tendencies is seen by the comparison's limits
    bad = ref.run_restarted(
        *start, params, 20, 20, tmp_path, "float32", lo, drop_tendencies=True)
    limits = config["check"]["limits"]
    assert all(np.abs(np.asarray(b) - np.asarray(w)).max() > limits[k]
               for k, b, w in zip("huv", bad, want))
