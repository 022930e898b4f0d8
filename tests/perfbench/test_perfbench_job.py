"""The solver-job cell on the CPU's virtual devices: a small cell of the
configuration ``shallow-water-job`` added as new files to a copy of the
benchmark, run end to end; its two controls; a snapshot taken a call
late, which has to come out not correct; its per-layer readers on
made-up traces whose values are computed by hand, a trace that the
profiler cut inside the last snapshot among them; and the reference's
two block means against each other."""

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
    ROOT, a_step, cell_args, event_lines, made_job_session, make_copy,
    multistep_text, program_text)

CHIP = "/device:TPU:0"
JOB_CELLS = ["sw-job-toy-1x1", "sw-job-toy-2x2"]
NEW_READERS = ["snapshot_device_share.sw", "snapshot_hbm_roofline_share",
               "output_wait_share.sw", "state_copy_bytes_per_call.sw",
               "sw_hbm_roofline_share.job", "op_surface_device_share.job"]
# PR 38's readers of the job's spans, listed since PR 39
HOST_SPANS = ["host_device_clock_bracket_us", "idle_in_sync_share.sw",
              "idle_in_job_share.sw", "idle_unnamed_share.sw",
              "job_issue_us_per_call.sw"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with two cells of ``shallow-water-job`` more:
    32x64 cells cut 2x finer than a 16x32 grid, so ``coarsen`` is 2."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_job"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / "configs/shallow-water-job.json").read_text())
    config["name"] = "shallow-water-job-toy"
    config["check"].update(calls=2, row_blocks=2)
    (bench / "configs/shallow-water-job-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"] if c["name"] == "shallow-water-job")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-job-toy",
        file="perfbench/configs/shallow-water-job-toy.json"))
    for name, mesh in zip(JOB_CELLS, ([1, 1], [2, 2])):
        cell = {
            "config": "shallow-water-job-toy", "traffic": name,
            "chips": mesh[0] * mesh[1], "why": "a test cell", "mesh": mesh,
            "grid": {"ny": 32, "nx": 64, "refine": 2},
            "rows": [{"name": "multistep", "slots": 1, "reps": 3}],
        }
        (bench / f"workloads/{name}.json").write_text(json.dumps(cell))
        benchmark["workloads"].append({
            k: cell[k] for k in ("config", "traffic", "chips", "why")
        } | {"name": name})
    for section in ("end_to_end", "per_layer"):
        for metric in benchmark[section]:
            if "sw-job-1chip" in metric.get("workloads", []):
                metric["workloads"] += JOB_CELLS
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def _session(copy, cell, seed=2**31 + 5):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    session = driver.setup(
        run.Context(config, workload, seed, jax.devices(), bench))
    for row in workload["rows"]:
        session.batch(row["name"])
    return session


@pytest.mark.parametrize("cell", JOB_CELLS)
def test_the_job_cell_runs_and_every_check_is_beside_its_limit(copy, cell):
    result = run.run_cell(
        cell_args(cell), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    assert set(result["checks"]) == {
        "snapshots_undelivered", "snapshots_out_of_order_or_torn", "max_lag",
        "nonfinite_after_window",
        "last_snapshot_diff_h", "last_snapshot_diff_u", "last_snapshot_diff_v",
        "max_abs_diff_h", "max_abs_diff_u", "max_abs_diff_v"}
    assert result["checks"]["max_lag"] == {"value": 4, "limit": 4}
    assert result["checks"]["snapshots_undelivered"]["value"] == 0


@pytest.mark.parametrize("cell", JOB_CELLS)
def test_both_controls_of_the_job_are_not_correct(copy, cell):
    session = _session(copy, cell)
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    control = {c["name"]: c for c in session.control()}
    # the reference in bfloat16 in the snapshots' place
    assert any(control[f"max_abs_diff_{k}"]["value"]
               > control[f"max_abs_diff_{k}"]["limit"] for k in "huv"), control
    # a snapshot one call stale, by every field's own limit
    assert all(control[f"stale_snapshot_diff_{k}"]["value"]
               > control[f"stale_snapshot_diff_{k}"]["limit"] for k in "hu"), control


def test_a_snapshot_taken_after_the_next_call_is_not_correct(copy, monkeypatch):
    """The hazard the cell exists to catch: a job whose snapshot of call
    k sees call k + 1's values delivers every snapshot on time, whole
    and in order, and fails both comparisons."""
    from mpi4jax_tpu.models import shallow_water as sw

    root, bench = copy
    workload = files.load_json("workloads", JOB_CELLS[0], bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    session = driver.setup(
        run.Context(config, workload, 7, jax.devices(), bench))
    job = session.job
    a_call_on = sw.make_multistep(job.cfg, job.comm, job.num_multisteps)

    def late(self, calls=1, *, keep_input=False):
        for _ in range(calls):
            self.state = self._multi(self.state)
            self.step += self.num_multisteps
            ahead = a_call_on(self.state)
            parts = self._snap(*(getattr(ahead, k) for k in self.snapshot.fields))
            self._pending.append((self.step, parts))
            self._stats["snapshots_produced"] += 1
            self._ask()
            self._deliver(self.snapshot.lag)
        return self.state

    monkeypatch.setattr(sw.SolverJob, "advance", late)
    session.batch("multistep")
    checks = {c["name"]: c for c in session.check()}
    for name in ("snapshots_undelivered", "snapshots_out_of_order_or_torn"):
        assert checks[name]["value"] == 0
    assert checks["max_lag"]["value"] <= checks["max_lag"]["limit"]
    for name in ("last_snapshot_diff_h", "max_abs_diff_h"):
        assert checks[name]["value"] > checks[name]["limit"], checks


def test_a_snapshot_out_of_order_fails_the_batch(copy):
    session = _session(copy, JOB_CELLS[0], seed=3)
    session._expected += 1  # as if one had been skipped
    with pytest.raises(RuntimeError, match="out of order"):
        session.batch("multistep")


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
def job_session(copy):
    return _session(copy, JOB_CELLS[0], seed=19)


def _view(session, made, batches=1):
    return types.SimpleNamespace(
        session=session, trace=made, facts=session.facts(),
        peaks={"hbm_gbps": 819.0}, samples=[],
        traced=[run.Sample("multistep", 0.0, 1.0)] * batches)


def _reader(copy, name):
    return files.load_module("layer_metrics", name, copy[1])


def test_snapshot_readers_on_a_hand_made_trace(copy, job_session, capsys):
    multi = job_session.compiled_text("multistep")
    snap = job_session.compiled_text("snapshot")
    coarse = _pick(snap, lambda o, line: o.scopes[:2] == (
        "mpi4jax_tpu.snapshot", "coarsen"))
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    # a batch of three calls: the multistep 900 ns, the snapshot 100 ns
    made = _trace([[(step, 900)], [(coarse, 60), (coarse, 40)]] * 3)
    view = _view(job_session, made)
    # the snapshot program as the job makes it: three padded fields in,
    # three coarse fields out, which is what its compiled text says
    least = scopes.signature(snap)
    assert least == (3 * 36 * 68 * 4, 3 * 16 * 32 * 4)
    reader = _reader(copy, "snapshot_hbm_roofline_share")
    assert reader.output_bytes(job_session) == [
        ("snapshot", f"the snapshot program ({least.taken} in, "
         f"{least.handed_back} out)", least.bytes)]
    # a program that is all output's is held against its own device time
    assert reader.read(view) == pytest.approx(100 * (least.bytes / 819e9) / 100e-9)
    assert _reader(copy, "state_copy_bytes_per_call.sw").read(view) == 0.0
    # on this backend the step is array code: no kernel call, no period
    capsys.readouterr()
    assert _reader(copy, "snapshot_device_share.sw").read(view) is None
    assert "ran no kernel call" in capsys.readouterr().out


def test_a_programs_text_is_lowered_with_what_the_job_handed_it(job_session, capsys):
    """``compiled_text`` asks the job nothing about what its programs
    take: what the loop handed each at its first call was kept."""
    driver = files.load_module("drivers", "shallow_water_job")
    seen = job_session.job._snap.handed
    assert [(a.shape, a.dtype) for a in seen] == [((36, 68), np.float32)] * 3
    assert all(a.sharding == job_session.job.state.h.sharding for a in seen)
    state, = job_session.job._multi.handed
    assert [a.shape for a in state] == [a.shape for a in job_session.job.state]
    # a job whose last step hands its snapshot program coarse sums, and
    # not the fields the state holds
    job = types.SimpleNamespace(
        multi=jax.jit(lambda s: s), snap=jax.jit(lambda *sums: tuple(a / 4 for a in sums)),
        stage=None, state=job_session.job.state)
    job._multi, job._snap, job._stage = job.multi, job.snap, None
    driver.watch(job)
    job._snap(*(jax.numpy.ones((16, 32), "float32"),) * 3)
    assert scopes.signature(driver.text_of(job, "snapshot")) == (
        3 * 16 * 32 * 4, 3 * 16 * 32 * 4)
    assert "by assumption" not in capsys.readouterr().out
    # a program the loop was not seen calling: the state at hand, and said
    assert scopes.signature(driver.text_of(job, "multistep")).taken == sum(
        a.nbytes for a in job.state)
    assert "by assumption" in capsys.readouterr().out
    with pytest.raises(KeyError, match="holds no 'stage' program"):
        driver.text_of(job, "stage")


def _step_and_snapshot_lines(job_session):
    multi = job_session.compiled_text("multistep")
    snap = job_session.compiled_text("snapshot")
    coarse = _pick(snap, lambda o, line: o.scopes[:2] == (
        "mpi4jax_tpu.snapshot", "coarsen"))
    step = _pick(multi, lambda o, line: o.source
                 and "models/shallow_water.py" in o.source and not o.scopes)
    halo = _pick(multi, lambda o, line: o.scopes[:1] == (
        "mpi4jax_tpu.halo_exchange_2d",))
    return step, halo, coarse


@pytest.fixture(scope="module")
def made():
    """The job's two programs as the TPU backend compiles them, made by
    hand at the toy cell's shapes (the CPU runs no kernel call): a
    session round their texts, a call's multistep (ten steps of 270 ns,
    30 of them the sent slabs' fusions) and its snapshot (300 ns)."""
    texts = {"multistep": multistep_text(36, 68),
             "snapshot": program_text((36, 68), (16, 32))}
    means = event_lines(texts["snapshot"])
    return (made_job_session(texts),
            a_step(event_lines(texts["multistep"]), kernel_ns=239) * 10,
            [(means["out.0"], 180), (means["out.1"], 120)])


def test_the_steps_own_readers_leave_the_snapshot_out(copy, made, capsys):
    """``sw_hbm_roofline_share`` and ``op_surface_device_share.sw`` read
    one program a call; the job's own read the multistep's executions."""
    session, multistep, snapshot = made
    view = _view(session, _trace([multistep, snapshot] * 3))
    assert _reader(copy, "op_surface_device_share.job").read(view) == (
        pytest.approx(100 * 300 / 2700))
    # a step's least bytes are the kernel call's signature (its operands
    # and its results, once) and twice every other instruction's result
    field, slab = 36 * 68 * 4, 36 * 2 * 4
    least = (12 * field + 6 * slab + 8 + 12) + 3 * 2 * 2 * slab + 2 * 8
    assert least == (scopes.signature(
        session.compiled_text("multistep"), "wide_step.3").bytes + 12 * slab + 16)
    assert _reader(copy, "sw_hbm_roofline_share.job").read(view) == (
        pytest.approx(100 * (least / 819e9) / (2700e-9 / 10)))
    assert f"a step moves {least} bytes at the least" in capsys.readouterr().out
    # the accepted one reads one program a call: it finds the snapshot's
    # events in no text of the step's and books them to nobody
    assert _reader(copy, "sw_hbm_roofline_share").read(view) is None
    assert "the multistep's text has no out.0" in capsys.readouterr().out
    assert _reader(copy, "snapshot_device_share.sw").read(view) == pytest.approx(10.0)


@pytest.mark.parametrize("recorded", ["part", "nothing", "all"])
def test_a_last_snapshot_the_profiler_cut_is_left_out(
        copy, made, capsys, recorded):
    """A batch ends when its last call's state is ready, so the profiler
    stops while the window's last snapshot runs.  What the trace has of
    it counts for nothing: every reader reads what it reads from the
    whole executions, a program's time over the executions it has."""
    session, multistep, snapshot = made
    last = {"part": snapshot[:1], "nothing": [], "all": snapshot}[recorded]
    trace = _trace([multistep, snapshot] * 2 + [multistep, last])
    if recorded == "nothing":  # not even its execution
        trace.modules[CHIP].pop()
    view = _view(session, trace)
    whole, executions = session.traced_programs(trace, view.traced)
    assert len(executions) == (6 if recorded == "all" else 5)
    assert len(whole.modules[CHIP]) == len(executions)
    assert len(whole.device_ops[CHIP]) == (  # fifty events and two a call
        3 * 50 + 2 * (3 if recorded == "all" else 2))
    assert _reader(copy, "snapshot_device_share.sw").read(view) == pytest.approx(10.0)
    said = "leave that execution out" in capsys.readouterr().out
    assert said == (recorded != "all")
    least = scopes.signature(session.compiled_text("snapshot")).bytes
    assert least == 3 * (36 * 68 + 16 * 32) * 4
    assert _reader(copy, "snapshot_hbm_roofline_share").read(view) == (
        pytest.approx(100 * (least / 819e9) / 300e-9))
    assert _reader(copy, "state_copy_bytes_per_call.sw").read(view) == 0.0
    assert _reader(copy, "op_surface_device_share.job").read(view) == (
        pytest.approx(100 * 300 / 2700))


def test_a_state_copied_in_every_call_is_counted(copy, job_session):
    reader = _reader(copy, "state_copy_bytes_per_call.sw")
    copied = "%copy.7 = f32[36,68]{1,0:T(8,128)} copy(f32[36,68]{1,0} %p.1)"
    done = "%copy-done.2 = bf16[4,8]{1,0} copy-done((bf16[4,8], bf16[4,8], u32[]) %cs)"
    assert reader.moved_bytes(copied) == 2 * 36 * 68 * 4
    assert reader.moved_bytes(done) == 2 * 4 * 8 * 2
    call = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0), kind=kLoop"
    made = _trace([[(copied, 5)] * 6 + [(call, 50)], [(call, 5)]] * 3)
    assert reader.read(_view(job_session, made)) == 6 * 2 * 36 * 68 * 4


def test_readers_refuse_a_trace_of_other_programs(copy, job_session, capsys):
    call = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0), kind=kLoop"
    made = _trace([[(call, 50)]] * 4)  # a batch of three calls is six programs
    view = _view(job_session, made)
    for name in NEW_READERS[:2] + NEW_READERS[3:]:
        assert _reader(copy, name).read(view) is None
    assert "do not belong together" in capsys.readouterr().out


def test_output_wait_share_reads_the_jobs_own_counters(copy, job_session):
    reader = _reader(copy, "output_wait_share.sw")
    stats = dict(job_session.at_setup)
    stats["output_wait_s"] += 0.03
    stats["callback_s"] += 0.01
    fake = types.SimpleNamespace(
        at_setup=job_session.at_setup,
        job=types.SimpleNamespace(stats=lambda: stats))
    view = types.SimpleNamespace(
        session=fake, samples=[run.Sample("multistep", 0.0, 1.5)],
        traced=[run.Sample("multistep", 2.0, 2.5)])
    assert reader.read(view) == pytest.approx(100 * 0.04 / 2.0)
    view.samples, view.traced = [], []
    assert reader.read(view) is None


def test_the_real_cell_lists_its_readers_and_the_accepted_ones_that_read_true():
    benchmark = files.load_benchmark(ROOT)
    mine = {m["name"] for m in files.metrics_of(benchmark, "per_layer", "sw-job-1chip")}
    # `sw_device_ops_per_step` counts what the trace has of a cut snapshot
    assert mine == set(NEW_READERS) | set(HOST_SPANS) | {
        "compile_s", "setup_after_chips_s", "device_idle_share.sw"}
    for name in NEW_READERS + HOST_SPANS:
        assert hasattr(files.load_module("layer_metrics", name), "read")
    workload = files.load_json("workloads", "sw-job-1chip")
    cell = files.find_cell(benchmark, "sw-job-1chip")
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: cell[k] for k in ("config", "chips", "traffic", "why")}
    assert workload["grid"] == files.load_json("workloads", "sw-bench-1chip")["grid"]
    config = files.load_json("configs", "shallow-water-job")
    assert config["model"] == files.load_json("configs", "shallow-water")["model"]
    assert config["reduced"] == [] and config["architecture"] is None


# -- the reference ---------------------------------------------------------


@pytest.fixture(scope="module")
def job_reference():
    return files.load_module("references", "shallow-water-job")


def test_the_references_two_block_means_agree(job_reference):
    rng = np.random.default_rng(5)
    a = (100 + rng.normal(size=(24, 40))).astype(np.float32)
    want = job_reference.block_mean(a, 4)
    assert want.shape == (6, 10) and want.dtype == np.float32
    by_hand = np.array([[a[4 * i:4 * i + 4, 4 * j:4 * j + 4].astype(np.float64).mean()
                         for j in range(10)] for i in range(6)])
    np.testing.assert_allclose(want, by_hand, rtol=0, atol=1e-5)
    got = np.asarray(job_reference._block_mean(jax.numpy.asarray(a), 4))
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-5)
    with pytest.raises(ValueError, match="does not divide"):
        job_reference.block_mean(a, 5)


def test_the_reference_walked_once_is_the_reference_run_to_each_step(job_reference):
    plain = files.load_module("drivers", "shallow_water")
    config = files.load_json("configs", "shallow-water-job")
    model = config["model"]
    modes = plain.mode_table(9, config["assumed"]["perturbation"])
    start = plain.make_fields(model, 24, 48, 5000.0, 5000.0)(modes)
    params = job_reference.parameters(model, 5000.0, 5000.0)
    walked = job_reference.run_block_means(*start, params, [3, 7], 2, (0, 24))
    for n, means in zip((3, 7), walked):
        fields = job_reference.run(*start, params, n)
        for got, field in zip(means, fields):
            np.testing.assert_allclose(
                np.asarray(got), job_reference.block_mean(field, 2),
                rtol=0, atol=2e-5)
    # a band of rows keeps what it is asked to keep
    band = job_reference.run_block_means(
        *(a[:20] for a in start), params, [3], 2, (0, 8))
    np.testing.assert_allclose(
        np.asarray(band[0][0]), np.asarray(walked[0][0])[:4], rtol=0, atol=0)
    # a band may start anywhere in the rows it is handed: what it keeps
    # counts, from a row of the domain that no block straddles
    off = job_reference.run_block_means(
        *(a[3:] for a in start), params, [3], 2, (13, 21), first_row=3)
    np.testing.assert_allclose(
        np.asarray(off[0][0]), np.asarray(walked[0][0])[8:12], rtol=0, atol=2e-5)
    with pytest.raises(ValueError, match="ascending"):
        job_reference.run_block_means(*start, params, [7, 3], 2, (0, 24))
    with pytest.raises(ValueError, match="does not divide"):
        job_reference.run_block_means(*start, params, [3], 2, (1, 24))
    with pytest.raises(ValueError, match="does not divide"):
        job_reference.run_block_means(*start, params, [3], 5, (0, 20))
