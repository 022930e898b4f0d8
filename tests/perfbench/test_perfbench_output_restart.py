"""The cell of the job a user keeps and restarts, on the CPU's virtual
devices: two small cells of the configuration
``shallow-water-output-restart`` added as new files to a copy of the
benchmark and run end to end (set-up saves, kills and resumes a job that
writes output; the window writes a snapshot a call and saves every few
calls under one bound that bites); the controls, each seeded mistake not
correct; a traced window's executions; the three readers on made-up
spans and counters, and on a program that has none; the real cell's
files; and the plain reference's output across its own save and load."""

import json
import types

import jax
import numpy as np
import pytest

from perfbench import run
from perfbench.harness import files, peaks, trace as tracing
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import ROOT, cell_args, make_copy

CELL = "sw-output-restart-1chip"
CONFIG = "shallow-water-output-restart"
CELLS = ["sw-output-restart-toy-1x1", "sw-output-restart-toy-2x2"]
NEW_READERS = ["host_in_flight_share.sw", "transfer_wait_share.sw",
               "save_commit_period_ratio"]
# the accepted readers that read true in the cell unedited (my chip runs,
# PR 45), the save's five and PR 38's seven listed since PR 48
ACCEPTED = ["device_idle_share.sw", "sw_hbm_roofline_share.job",
            "op_surface_device_share.job", "state_copy_bytes_per_call.sw",
            "output_wait_share.sw", "snapshot_hbm_roofline_share",
            "snapshot_device_share.sw",
            "save_stall_share.sw", "save_commit_s", "checkpoint_device_share.sw",
            "checkpoint_stage_hbm_roofline_share", "resume_s",
            "host_device_clock_bracket_us", "idle_in_sync_share.sw",
            "idle_in_job_share.sw", "idle_unnamed_share.sw",
            "job_issue_us_per_call.sw", "save_fetch_busy_share",
            "save_write_busy_share"]
STATE = ("h", "u", "v", "dh", "du", "dv")
ONE = 3 * 16 * 32 * 4  # a toy cell's snapshot: h, u, v of 32x64 cells, 2x2 means
BOUND = int(2.5 * ONE)
RECORDED = ROOT / "perfbench/testdata"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with two cells of the new configuration more:
    32x64 cells, a save every 3 calls, a host that takes two and a half
    snapshots: two of them leave no room for a piece."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_output_restart"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / f"configs/{CONFIG}.json").read_text())
    config["name"] = CONFIG + "-toy"
    config["restart"].update(every_calls=3)
    config["host"].update(ahead_bytes=BOUND)
    config["check"].update(row_blocks=2, host_in_flight_max_bytes=BOUND)
    (bench / f"configs/{CONFIG}-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    benchmark["configs"].append(dict(
        entry, name=CONFIG + "-toy", file=f"perfbench/configs/{CONFIG}-toy.json"))
    for name, mesh in zip(CELLS, ([1, 1], [2, 2])):
        cell = {
            "config": CONFIG + "-toy", "traffic": name,
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
def test_the_cell_runs_and_every_check_is_beside_its_limit(copy, cell):
    result = run.run_cell(
        cell_args(cell), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    zeros = {"snapshots_undelivered", "snapshots_out_of_order_or_torn",
             "snapshots_after_restart_off", "snapshots_across_restart_differing",
             "saves_not_started", "saves_unacknowledged", "saves_out_of_order",
             "saves_kept_off", "temporaries_left", "nonfinite_after_window",
             "resaved_step_off"}
    zeros |= {f"{kind}_{k}" for kind in ("resaved_differing", "resumed_differing")
              for k in STATE}
    exact = {f"reference_restart_diff{part}_{k}" for part in ("", "_output")
             for k in "huv"}
    within = {f"{kind}_{k}" for k in "huv" for kind in (
        "max_abs_diff", "snapshot_max_abs_diff", "last_snapshot_diff")}
    assert set(result["checks"]) == zeros | exact | within | {
        "max_lag", "host_in_flight_max_bytes"}
    for name in zeros:
        assert result["checks"][name] == {"value": 0, "limit": 0}, name
    for name in exact:  # the reference stopped and started is the reference
        assert result["checks"][name]["value"] == 0 < result["checks"][name]["limit"]
    assert result["checks"]["max_lag"]["limit"] == 4
    # the bound bit: more than one copy was in flight, never more than it takes
    peak = result["checks"]["host_in_flight_max_bytes"]
    assert peak["limit"] == BOUND and 2 * ONE <= peak["value"] <= BOUND


def test_every_seeded_mistake_is_not_correct(copy, cell=CELLS[0]):
    session = _session(copy, cell, batches=2)
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    control = {c["name"]: c for c in session.control()}

    def fails(name):
        return control[name]["value"] > control[name]["limit"]

    # the restart driver's own: the reference in bfloat16; forward
    # Euler's start after a resume; a resume from an older save
    assert any(fails(f"bfloat16_diff_{k}") for k in "huv"), control
    for mistake in ("tendencies", "stale"):
        assert all(fails(f"{mistake}_differing_{k}") for k in "huv"), control
        assert any(fails(f"{mistake}_diff_{k}") for k in "huv"), control
    assert all(fails(f"reference_dropped_diff_{k}") for k in "huv"), control
    assert all(fails(f"reference_dropped_diff_output_{k}") for k in "huv"), control
    # a snapshot of the wrong step after a resume, the job's and the reference's
    assert fails("late_snapshots_differing"), control
    assert all(fails(f"reference_late_diff_output_{k}") for k in "huv"), control
    # a snapshot dropped while a save is waited for
    assert control["dropped_snapshots_undelivered"]["value"] == 1
    assert fails("dropped_snapshots_out_of_order_or_torn"), control
    # the two bounds of before added up: over what the host takes
    assert control["overrun_host_in_flight_max_bytes"]["value"] > BOUND
    assert fails("overrun_host_in_flight_max_bytes"), control
    # a torn save: not the uninterrupted run
    assert fails("torn_differing_h") and fails("torn_diff_h"), control
    assert session.violations == 0


def test_the_window_starts_from_a_resumed_job_that_goes_on_writing(copy):
    session = _session(copy, CELLS[0], seed=11, batches=0)
    job = session.job
    assert (job.step, job.calls) == (11, 1) and session.calls_at_setup == 1
    assert job.snap is not None and job.stage is not None
    assert job.ahead_bytes == BOUND == job.snapshot.ahead_bytes
    # the job before the kill delivered its one snapshot; this one none yet
    assert [step for step, _ in session.kept] == [11]
    assert session.at_setup["snapshots_delivered"] == 0
    assert session.at_setup["host_in_flight_max_bytes"] == 0
    session.batch("multistep")
    session.batch("multistep")
    session.batch("multistep")  # calls 2-7: saves after calls 3 and 6, lag 4
    job.drain()
    assert [step for step, _ in session.kept] == [11 + 10 * k for k in range(7)]
    assert [r["step"] for r in session.window_saves()] == [31, 61]
    assert 0 < job.stats()["host_in_flight_max_bytes"] <= BOUND
    assert session.violations == 0


def test_a_program_without_the_one_bound_fails_at_once(copy, monkeypatch):
    """The parent of PR 45 builds such a job and cannot give
    ``host_bound``: the driver says so before anything is compiled."""
    from mpi4jax_tpu.models import shallow_water as sw

    stats = sw.SolverJob.stats

    def before(self):
        had = stats(self)
        del had["host_in_flight_max_bytes"]
        return had

    monkeypatch.setattr(sw.SolverJob, "stats", before)
    with pytest.raises(RuntimeError, match="keeps no one bound"):
        _session(copy, CELLS[0], batches=0)


# -- a traced window's executions -----------------------------------------


def _made(counts, chip="/device:TPU:0"):
    """A trace of ``len(counts)`` executions of ``counts[i]`` operations."""
    made, t = Trace(), 0.0
    made.device_ops[chip], made.modules[chip] = [], []
    for n in counts:
        start = t
        for _ in range(n):
            made.device_ops[chip].append(Event("%op = f32[8]{0} add()", t, 10.0))
            t += 10.0
        made.modules[chip].append(Event("jit_local(1)", start, t - start))
        t += 7.0
    return made


def test_a_traced_window_holds_its_snapshots_and_one_save_between_two_calls(copy):
    session = _session(copy, CELLS[0], seed=19, batches=0)
    traced = [run.Sample("multistep", 0.0, 1.0)] * 2
    # two batches of two calls from call 1, a save every 3: after call 3
    want = ["multistep", "snapshot", "multistep", "snapshot", "stage",
            "multistep", "snapshot", "multistep", "snapshot"]
    assert session.traced_programs(None, traced)[1] == want
    assert session.programs() == ("multistep", "snapshot")
    # the real cell: twelve batches of four from call 1, a save every 48
    workload = files.load_json("workloads", CELL)
    real = types.SimpleNamespace(
        calls_at_setup=1, rows={r["name"]: r for r in workload["rows"]},
        every=files.load_json("configs", CONFIG)["restart"]["every_calls"])
    batches = workload["rows"][0]["trace_batches"]
    executions = type(session).traced_programs(real, None, traced[:1] * batches)[1]
    assert len(executions) == 97 and executions.count("stage") == 1
    assert executions[94:] == ["stage", "multistep", "snapshot"] and batches == 12
    # whole: as it is.  The last snapshot cut short, or not there: left out
    counts = [5, 3, 5, 3, 6, 5, 3, 5, 3]
    whole = _made(counts)
    assert session.traced_programs(whole, traced) == (whole, want)
    for cut in (counts[:-1] + [2], counts[:-1]):
        kept, executions = session.traced_programs(_made(cut), traced)
        assert executions == want[:-1]
        (modules,), (events,) = kept.modules.values(), kept.device_ops.values()
        assert len(modules) == 8 and len(events) == sum(counts[:-1])
    # a trace of other programs is handed on as it is, for the harness to refuse
    other = _made(counts[:5])
    assert session.traced_programs(other, traced) == (other, want)
    # the three programs' texts, under the keys the accepted readers ask for
    assert "mpi4jax_tpu.snapshot" in session.compiled_text("snapshot")
    assert "mpi4jax_tpu.checkpoint" in session.compiled_text("stage")
    assert session.compiled_text("multistep")


def test_a_traced_run_reports_the_new_readers_and_says_why_of_the_rest(
        copy, monkeypatch, capsys):
    """The harness finds the three readers by name; handed the recorded
    trace of other programs, the accepted readers that need the device's
    timeline say why they report nothing, and none raises."""
    monkeypatch.setattr(tracing, "find_xplane", lambda log_dir: str(
        next(RECORDED.glob("*.xplane.pb"))))
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: {"hbm_gbps": 819.0})
    result = run.run_cell(cell_args(CELLS[0], trace=1, seconds=1.0), jax.devices(),
                          root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_READERS) | {"output_wait_share.sw", "compile_s",
                               "setup_after_chips_s"} <= set(got)
    assert 100 * 2 * ONE / BOUND <= got["host_in_flight_share.sw"] <= 100
    assert got["transfer_wait_share.sw"] >= 0 and got["save_commit_period_ratio"] > 0
    out = capsys.readouterr().out
    assert "bytes on their way to the host, of the" in out
    assert "saves, committed after" in out


# -- the three readers on made-up spans ------------------------------------


def _span(name, start, seconds, thread="MainThread", key=None, **counts):
    return types.SimpleNamespace(
        name=name, thread=thread, key=key, counts=counts,
        start_ns=start * 1e9, end_ns=(start + seconds) * 1e9, seconds=seconds)


def _view(spans, stats, host=1000):
    job = types.SimpleNamespace(
        spans=lambda: spans, stats=lambda: stats, trace=types.SimpleNamespace(dropped=0))
    session = types.SimpleNamespace(
        job=job, ctx=types.SimpleNamespace(config={"host": {"ahead_bytes": host}}))
    return types.SimpleNamespace(
        session=session, samples=[run.Sample("multistep", 10.0, 14.0)],
        traced=[run.Sample("multistep", 8.0, 9.0)])


def _reader(name):
    return files.load_module("layer_metrics", name)


def test_the_readers_read_the_jobs_counter_and_spans(capsys):
    spans = [
        _span("job/ask_wait", 8.5, 0.02, held_by="save", bytes=600),
        _span("checkpoint/fetch_wait", 11.0, 0.05, "checkpoint-save", held_by="snapshot", bytes=40),
        _span("checkpoint/fetch_wait", 12.0, 0.03, "checkpoint-save", held_by="snapshot", bytes=40),
        _span("job/ask_wait", 2.0, 9.99, held_by="save", bytes=600),  # set-up's
        _span("checkpoint/save", 8.2, 0.9, "checkpoint-save", key=31),
        _span("checkpoint/save", 10.2, 1.5, "checkpoint-save", key=61),
        _span("checkpoint/save", 12.4, 1.1, "checkpoint-save", key=91),
        _span("checkpoint/save", 3.0, 0.1, "checkpoint-save", key=11),  # set-up's
    ]
    view = _view(spans, {"host_in_flight_max_bytes": 930, "transfer_wait_s": 10.09})
    assert _reader("host_in_flight_share.sw").read(view) == pytest.approx(93.0)
    assert _reader("transfer_wait_share.sw").read(view) == pytest.approx(100 * 0.10 / 5.0)
    out = capsys.readouterr().out
    assert "job/ask_wait held by save: 1 waits, 0.020000 s, 600 bytes" in out
    assert "checkpoint/fetch_wait held by snapshot: 2 waits, 0.080000 s, 80 bytes" in out
    # commits of 0.9, 1.5, 1.1 s, started 2.0 and 2.2 s apart
    assert _reader("save_commit_period_ratio").read(view) == pytest.approx(1.1 / 2.1)
    # one save in the window: no period
    view = _view(spans[:5], {"host_in_flight_max_bytes": 930, "transfer_wait_s": 0.0})
    assert _reader("save_commit_period_ratio").read(view) is None
    assert "no period" in capsys.readouterr().out
    # no batch: nothing to divide by
    view.samples, view.traced = [], []
    assert _reader("transfer_wait_share.sw").read(view) is None


def test_on_a_program_that_has_neither_the_readers_report_nothing(capsys):
    """The parent of PR 45: a job with spans and stats but no one bound;
    and a session with no job at all.  None of the three raises."""
    parent = _view([_span("checkpoint/save", 8.2, 0.9, "checkpoint-save")],
                   {"save_wait_s": 0.0})
    assert _reader("host_in_flight_share.sw").read(parent) is None
    assert _reader("transfer_wait_share.sw").read(parent) is None
    assert _reader("save_commit_period_ratio").read(parent) is None
    bare = types.SimpleNamespace(
        session=types.SimpleNamespace(ctx=types.SimpleNamespace(config={})),
        samples=[run.Sample("multistep", 0.0, 1.0)], traced=[])
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None
    assert capsys.readouterr().out.count("nothing is reported") >= 5


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
        assert entry["layer"] == "programs"
    assert {m["name"] for m in files.metrics_of(benchmark, "end_to_end", CELL)} == {
        "solver_rate", "solver_step_p95_us", "setup_s"}
    workload = files.load_json("workloads", CELL)
    cell = files.find_cell(benchmark, CELL)
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: cell[k] for k in ("config", "chips", "traffic", "why")}
    assert cell["chips"] == 1
    assert cell["traffic"] == "bench-domain-snapshot-every-call-save-every-48-calls"
    # the domain and the mesh of the other solver cells, the batch of the job cells
    for other in ("sw-bench-1chip", "sw-job-1chip", "sw-restart-1chip"):
        theirs = files.load_json("workloads", other)
        assert workload["grid"] == theirs["grid"] and workload["mesh"] == theirs["mesh"]
        assert theirs["rows"][0]["reps"] == (8 if other == "sw-bench-1chip" else 4)
    assert workload["rows"][0]["reps"] == 4
    # a traced window holds one save: sw-restart-1chip's placement
    assert workload["rows"] == files.load_json("workloads", "sw-restart-1chip")["rows"]
    # appended after what was there, in the order it was there
    names = [c["name"] for c in benchmark["workloads"]]
    assert names.index(CELL) > names.index("sw-as-written-1chip")
    assert names[:5] == ["sw-bench-1chip", "coll-2x2", "sw-job-1chip",
                         "sw-restart-1chip", "sw-as-written-1chip"]
    configs = [c["name"] for c in benchmark["configs"]]
    assert configs.index(CONFIG) > configs.index("shallow-water-as-written")
    readers = [m["name"] for m in benchmark["per_layer"]]
    assert readers[readers.index("sw_exchange_device_share.as_written") + 1:][:3] == NEW_READERS
    chips = [c["chips"] for c in benchmark["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 4)  # the driver's share
    assert len(benchmark["workloads"]) >= 6


def test_the_configuration_is_its_two_parents_under_one_bound():
    config = files.load_json("configs", CONFIG)
    job = files.load_json("configs", "shallow-water-job")
    saved = files.load_json("configs", "shallow-water-restart")
    entry = next(c for c in files.load_benchmark(ROOT)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and len(config["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == ["restart"]
    assert list(config["reduced_how"]) == ["restart"]
    assert config["architecture"] is None
    assert config["driver"] == "shallow_water_output_restart"
    assert config["reference"] == CONFIG
    assert config["model"] == files.load_json("configs", "shallow-water")["model"]
    # each block its parent's, less the bound, which is stated once
    assert config["output"] | {"ahead_bytes": 160000000} == job["output"]
    assert config["restart"] | {"ahead_bytes": 160000000} == saved["restart"]
    assert config["host"]["ahead_bytes"] == 160000000
    assert {"source_not_checked", "cadence", "perturbation", "refinement",
            "output_grid", "retention", "filesystem", "kill"} <= set(config["assumed"])
    assert set(config["guarantees"]) == {
        "precision", "every_step", "agreement", "finite", "delivery", "no_tearing",
        "durable", "ordered", "continuation", "host_bound", "seamless_output"}
    check = config["check"]
    assert check["limits"] == saved["check"]["limits"] == job["check"]["limits"]
    assert check["last_snapshot_limits"] == job["check"]["last_snapshot_limits"]
    assert check["reference_restart_limits"] == saved["check"]["reference_restart_limits"]
    assert check["bit_for_bit"] == 0 == check["snapshots_across_restart_differing"]
    assert check["host_in_flight_max_bytes"] == config["host"]["ahead_bytes"]
    # the cell's arithmetic: two snapshots and a piece fit, three snapshots do not
    one, piece = 3 * 1800 * 3600 * 4, 4 << 20
    assert one == 77_760_000 and 2 * one + piece <= 160000000 < 3 * one


# -- the reference ---------------------------------------------------------


@pytest.mark.parametrize("band", [(0, 24), (6, 24)])
def test_the_references_output_across_its_save_and_load_is_its_uninterrupted_one(
        tmp_path, band):
    ref = files.load_module("references", CONFIG)
    plain = files.load_module("drivers", "shallow_water")
    config = files.load_json("configs", CONFIG)
    modes = plain.mode_table(9, config["assumed"]["perturbation"])
    lo, hi = band
    start = tuple(a[lo:hi] for a in plain.make_fields(
        config["model"], 24, 48, 5000.0, 5000.0)(modes))
    params = ref.parameters(config["model"], 5000.0, 5000.0)
    walk = (*start, params, 20, 20, 10, 2)
    keep = (2, hi - lo - 2)
    want = ref.run_output(*walk, keep, "float32", lo)
    got = ref.run_output_restarted(*walk, tmp_path, keep, "float32", lo)
    assert len(want) == len(got) == 2
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            assert a.shape == ((hi - lo - 4) // 2, 24) and a.dtype == np.float32
            np.testing.assert_array_equal(a, b)
    # the last block means are those of the solver's own walk to step 41
    whole = ref.run(*start, params, 41, "float32", lo)
    for a, field in zip(want[-1], whole):
        np.testing.assert_array_equal(a, ref.block_mean(field[2:hi - lo - 2], 2))
    # and, to a float32 sum's rounding, the job reference's in jax.numpy
    means = ref.run_block_means(*start, params, [31, 41], 2, keep, "float32", lo)
    for a, b in zip(want[-1], means[-1]):
        assert np.abs(a - np.asarray(b)).max() < 3e-5
    # what it wrote is numpy's own: six arrays and the step count
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        [f"{k}.npy" for k in STATE] + ["step.npy"])
    # the mistakes are seen by the limits the check holds the reference to
    limits = config["check"]["reference_restart_limits"]
    for mistake in ({"drop_tendencies": True}, {"late": True}):
        bad = ref.run_output_restarted(
            *walk, tmp_path, keep, "float32", lo, **mistake)
        for i, k in enumerate("huv"):
            assert max(np.abs(b[i] - w[i]).max() for b, w in zip(bad, want)) > limits[k]
    with pytest.raises(ValueError, match="no whole number"):
        ref.run_output(*start, params, 20, 15, 10, 2)


def test_the_reference_imports_nothing_of_the_job():
    text = (ROOT / f"perfbench/references/{CONFIG}.py").read_text()
    code = text.split('"""', 2)[2]  # past the module's docstring
    assert "mpi4jax_tpu" not in code and "checkpoint" not in code
    ref = files.load_module("references", CONFIG)
    for name in ("parameters", "row_blocks", "run", "run_restarted", "block_mean",
                 "run_block_means", "run_output", "run_output_restarted"):
        assert callable(getattr(ref, name))
