"""``harness/hostspans.py``: a job's host spans on the device's clock.
On made-up traces whose every number is set by hand, on the trace
recorded on the chip (``perfbench/testdata/README.md``), and on a
program that keeps no spans."""

import json
import types

import jax
import pytest

from mpi4jax_tpu.utils.spans import Span
from perfbench import run
from perfbench.harness import files, hostspans, trace
from perfbench.harness.trace import Event, Trace
from perfbench_fixtures import cell_args, make_copy

RECORDED = files.BENCH_DIR / "testdata" / "solver-1chip.xplane.pb"
CHIP = "/device:TPU:0"
READERS = ["host_device_clock_bracket_us", "idle_in_sync_share.sw",
           "idle_in_job_share.sw", "idle_unnamed_share.sw",
           "job_issue_us_per_call.sw", "save_fetch_busy_share",
           "save_write_busy_share"]
MAIN = "MainThread"
# the made-up clocks: the device's + 1000 ns is the profiler's host
# clock, the job's (perf_counter) + 5000 ns is
SHIFT, JOB = 1000, 5000


def _span(name, start, end, ident, thread=MAIN, key=None, cause=None, **counts):
    """A span of the job, its times given on the host's clock."""
    return Span(name, ident, thread, cause, key, counts, start - JOB, end - JOB)


def _ev(name, start, end):
    return Event(name, float(start), float(end - start))


def _made():
    """Two traced batches of two calls in a window of 110 us.  The
    device runs [2500, 22500], [27000, 37000] and [37100, 48000] on the
    host's clock: a head of 12.5 us, a gap of 4.5 us round the second
    batch's enqueue, a gap of 0.1 us, a tail of 52 us."""
    programs = [(2500, 12500), (12500, 22500), (27000, 37000), (37100, 48000)]
    made = Trace()
    made.modules[CHIP] = [_ev("jit_local(1)", a - SHIFT, b - SHIFT) for a, b in programs]
    made.device_ops[CHIP] = [_ev("%step = f32[] fusion()", a - SHIFT, b - SHIFT)
                             for a, b in programs]
    made.host = {
        "traced_window": [_ev("traced_window", -10000, 100000)],
        "enqueue": [_ev("enqueue", 1000, 3000), _ev("enqueue", 24000, 26000)],
        "sync": [_ev("sync", 3000, 24000), _ev("sync", 26000, 50000)]}
    spans = [
        _span("job/advance", 1100, 2900, 1, key=11, calls=2),
        _span("job/enqueue", 1200, 1700, 2, key=11, cause=1, program="multi"),
        _span("job/enqueue", 1800, 2300, 3, key=21, cause=1, program="multi"),
        _span("job/advance", 24100, 25900, 4, key=31, calls=2),
        _span("job/fetch", 24200, 25200, 5, key=11, cause=4, bytes=64),
        _span("job/enqueue", 25300, 25800, 6, key=31, cause=4, program="multi"),
        # another thread's span is not the main thread's business
        _span("checkpoint/fetch", 22000, 28000, 7, thread="checkpoint-save", key=21),
    ]
    return made, spans


def _view(made, spans, dropped=0, **session):
    job = types.SimpleNamespace(spans=lambda: list(spans),
                                trace=types.SimpleNamespace(dropped=dropped))
    traced = [run.Sample("multistep", (e.start_ns - JOB) / 1e9, (s.end_ns - JOB) / 1e9)
              for e, s in zip(made.host["enqueue"], made.host["sync"])]
    return types.SimpleNamespace(
        session=types.SimpleNamespace(**{
            "job": job, "rows": {"multistep": {"reps": 2}},
            "traced_programs": lambda tr, batches: (tr, ["multistep"] * 4)} | session),
        trace=made, traced=traced, samples=[])


def test_the_bracket_comes_from_both_sides_and_its_middle_is_used(capsys):
    made, spans = _made()
    found = hostspans.split(_view(made, spans))
    # no program before its batch's enqueue span starts: 1000 - 1500 and
    # 24000 - 26000; the waited-for one over when its sync ends:
    # 24000 - 21500 and 50000 - 47000
    assert (found.lower_ns, found.upper_ns, found.width_ns) == (-500, 2500, 3000)
    assert (found.lower_ns + found.upper_ns) / 2 == SHIFT
    assert (found.host_offset_ns, found.host_offset_range_ns) == (JOB, 0)
    assert found.window_ns == 110000 and found.idle_ns == 110000 - 40900
    assert found.outside_ns == 0
    out = capsys.readouterr().out
    assert "a bracket 3.0 us wide" in out and "job/fetch, 11" in out


def test_idle_is_split_by_what_the_main_thread_was_in(capsys):
    made, spans = _made()
    found = hostspans.split(_view(made, spans))
    head, gap, short, tail = found.stretches
    assert [(st.where, st.ns) for st in found.stretches] == [
        ("head", 12500), ("gap", 4500), ("gap", 100), ("tail", 52000)]
    # the head: no span until the job's advance starts at 1100, then
    # its self time and its two enqueues up to the first program at 2500
    assert head.names == {hostspans.NO_SPAN: 11100, "job/advance": 400,
                          "job/enqueue multi": 1000}
    # a gap under a program span: most of it is the fetch's
    assert gap.names == {"sync": 1500 + 1000, hostspans.NO_SPAN: 100 + 100,
                         "job/advance": 300, "job/fetch": 1000, "job/enqueue multi": 500}
    assert (gap.span.name, gap.span.key) == ("job/fetch", 11)
    # a gap below the bracket's width carries no name, whatever lay over it
    assert short.names == {hostspans.BELOW: 100} and short.span is None
    assert tail.names == {"sync": 2000, hostspans.NO_SPAN: 50000}
    for st in found.stretches:
        assert sum(st.names.values()) == st.ns
        assert st.ns >= found.width_ns or set(st.names) == {hostspans.BELOW}
    by = found.seconds()
    assert by["sync"] == [pytest.approx(4500e-9), 2]
    assert by["job/fetch"] == [pytest.approx(1000e-9), 1]


def test_the_three_shares_sum_to_the_idle_share(capsys):
    made, spans = _made()
    found = hostspans.split(_view(made, spans))
    assert found.share("in_sync") == pytest.approx(100 * 4500 / 110000)
    assert found.share("in_job") == pytest.approx(100 * 3200 / 110000)
    assert found.share("unnamed") == pytest.approx(100 * 61400 / 110000)
    assert sum(found.share(k) for k in ("in_sync", "in_job", "unnamed")) == pytest.approx(
        trace.idle_share(made))


def test_device_work_outside_the_window_is_counted_busy_and_said_so(capsys):
    """The profiler stops inside the last snapshot: what the trace holds
    of it lies after the window's end, ``device_idle_share.sw`` counts it
    busy, and the rest of the idle is less by it, so the sum still holds."""
    made, spans = _made()
    made.host["traced_window"] = [_ev("traced_window", -10000, 50000)]
    made.device_ops[CHIP].append(_ev("%late = f32[] fusion()", 50100 - SHIFT, 50900 - SHIFT))
    made.modules[CHIP].append(_ev("jit_snap(2)", 50100 - SHIFT, 50900 - SHIFT))
    view = _view(made, spans, traced_programs=lambda tr, batches: (
        tr, ["multistep"] * 4 + ["snapshot"]))
    found = hostspans.split(view)
    assert found.outside_ns == 800 and found.stretches[-1].ns == 2000
    assert found.idle_ns == 60000 - 41700
    assert sum(found.share(k) for k in ("in_sync", "in_job", "unnamed")) == pytest.approx(
        trace.idle_share(made))
    assert "0.000001 s of device work lies outside the window" in capsys.readouterr().out


def test_hop_one_is_the_median_and_prints_its_range():
    traced = [run.Sample("m", 0.0, t / 1e9) for t in (1000, 2000, 3000)]
    syncs = [_ev("sync", t - 10, t + JOB + d) for t, d in ((1000, 0), (2000, 40), (3000, 10))]
    offset, spread = hostspans.host_offset(traced, syncs[::-1])  # in any order
    assert (offset, spread) == (pytest.approx(JOB + 10), pytest.approx(40))


def test_a_batchs_executions_are_found_among_the_programs_of_a_window(capsys):
    # a save's staging program between two calls; a snapshot after each
    assert hostspans.batch_executions(["m", "m", "stage", "m", "m"], [2, 2]) == [
        (0, 1), (3, 4)]
    assert hostspans.batch_executions(["m", "s"] * 4, [2, 2]) == [(0, 2), (4, 6)]
    assert hostspans.batch_executions(["m", "s"] * 3, [2, 2]) is None
    assert "holds 3 calls" in capsys.readouterr().out


def test_innermost_and_self_time():
    spans = [_span("a", 0, 100, 1), _span("b", 10, 40, 2), _span("c", 20, 30, 3),
             _span("b", 50, 60, 4), _span("a", 200, 210, 5),
             _span("w", 5, 95, 6, thread="other")]
    mine = [s for s in spans if s.thread == MAIN]
    cut = [(a + JOB, b + JOB, s.id) for a, b, s in hostspans.innermost(mine)]
    assert cut == [(0, 10, 1), (10, 20, 2), (20, 30, 3), (30, 40, 2), (40, 50, 1),
                   (50, 60, 4), (60, 100, 1), (200, 210, 5)]
    assert hostspans.self_times(spans) == {
        (MAIN, "a"): [pytest.approx(70e-9), 2], (MAIN, "b"): [pytest.approx(30e-9), 2],
        (MAIN, "c"): [pytest.approx(10e-9), 1], ("other", "w"): [pytest.approx(90e-9), 1]}
    # only the spans that start inside
    assert hostspans.self_times(spans, 150 - JOB, 300 - JOB) == {
        (MAIN, "a"): [pytest.approx(10e-9), 1]}
    # an enqueue is told apart by the program it enqueued
    assert set(hostspans.self_times(_made()[1])) == {
        (MAIN, "job/advance"), (MAIN, "job/enqueue multi"), (MAIN, "job/fetch"),
        ("checkpoint-save", "checkpoint/fetch")}


def test_the_recorded_traces_bracket_overlaps_the_one_its_run_ids_give():
    """On the trace recorded on the chip: the bracket from the
    harness's two batches (three calls, then five) against the one that
    ``run_id`` gives, program by program: enqueued on the host before it
    starts, completion seen after it ends."""
    from jax.profiler import ProfileData

    recorded = trace.read_xplane(str(RECORDED), ("enqueue", "sync"))
    lower, upper = hostspans.bracket(recorded, CHIP, [(0, 2), (3, 7)])
    assert (lower, upper) == (837396.0, 1821309.0)
    started, ended, enqueued, completed = {}, {}, {}, {}
    for plane in ProfileData.from_file(str(RECORDED)).planes:
        for line in plane.lines:
            for e in line.events:
                run_id = dict(e.stats).get("run_id")
                if plane.name == CHIP and line.name == trace.MODULES_LINE:
                    started[run_id] = e.start_ns
                    ended[run_id] = e.start_ns + e.duration_ns
                elif e.name == "DoEnqueueProgram":
                    enqueued[run_id] = e.start_ns
                elif e.name == "CompleteCallbacks":
                    completed[run_id] = e.start_ns
    assert len(started) == 8 and set(started) == set(enqueued) == set(completed)
    # the run PERF.md quotes: 1.21 and 1.96 ms
    first = min(started)
    assert enqueued[first] - started[first] == 1205432.0
    assert completed[first] - ended[first] == 1961532.0
    exact = (max(enqueued[r] - started[r] for r in started),
             min(completed[r] - ended[r] for r in started))
    assert exact[0] <= exact[1]
    assert lower <= exact[0] and exact[1] <= upper  # it holds the truth
    assert lower <= 1961532.0 and 1205432.0 <= upper  # and overlaps PERF.md's
    # today's `idle_gaps` uses the first lower bound alone
    first_alone = (recorded.host["enqueue"][0].start_ns
                   - recorded.modules[CHIP][0].start_ns)
    assert first_alone == 697438.0 and first_alone < exact[0]


def test_a_program_without_spans_or_with_dropped_ones_reports_nothing(capsys):
    made, spans = _made()
    parent = _view(made, spans)
    del parent.session.job.spans, parent.session.job.trace
    dropped = _view(made, spans, dropped=3)
    bare = types.SimpleNamespace(session=types.SimpleNamespace(), trace=made,
                                 traced=parent.traced, samples=[])
    for view in (parent, dropped, bare):
        for name in READERS:
            assert files.load_module("layer_metrics", name).read(view) is None
    out = capsys.readouterr().out
    assert "keeps no host spans" in out and "dropped 3 spans" in out
    # a trace that does not match the batches: said, never guessed at
    made.host["sync"].pop()
    assert hostspans.split(_view(made, spans)) is None
    made, spans = _made()
    short = _view(made, spans, traced_programs=lambda tr, batches: (tr, ["multistep"] * 3))
    assert hostspans.split(short) is None
    assert "executed 4 programs" in capsys.readouterr().out


def _window():
    """Three batches of the window on the job's clock, 1 ms each from
    0, 2 and 4 ms, the last one traced; the second holds a save."""
    ms = 1_000_000
    spans, ident = [], iter(range(1, 1000))

    def add(name, start, end, **kw):
        spans.append(Span(name, next(ident), kw.pop("thread", MAIN), None,
                          kw.pop("key", None), kw, int(start), int(end)))

    for b, start in enumerate((0, 2 * ms, 4 * ms)):
        add("job/advance", start + 1000, start + 101000, key=b, calls=2)
        for k in range(2):
            at = start + 2000 + 40000 * k
            add("job/enqueue", at, at + 20000, program="multi")
            add("job/ask", at + 21000, at + 23000, bytes=8)
            add("job/fetch", at + 24000, at + 30000, bytes=8)
    # the second batch's save: 30 us inside advance, left out of a call's cost
    add("job/save", 2 * ms + 90000, 2 * ms + 100000, key=21)
    add("job/save_start", 2 * ms + 91000, 2 * ms + 99000, key=21)
    add("job/enqueue", 2 * ms + 92000, 2 * ms + 97000, key=21, program="stage")
    # its threads: 4 ms from start to rename, fetches cover 2 ms of it,
    # the two writers 1.5 ms and 1.5 ms of which 0.5 ms at the same time
    add("checkpoint/save", 2.1 * ms, 6.1 * ms, key=21, thread="checkpoint-save")
    for at in (2.2 * ms, 3.2 * ms):
        add("checkpoint/fetch", at, at + ms, key=21, thread="checkpoint-save", bytes=4)
    add("checkpoint/write", 2.5 * ms, 4.0 * ms, key=21, thread="checkpoint-write-0", bytes=4)
    add("checkpoint/write", 3.5 * ms, 5.0 * ms, key=21, thread="checkpoint-write-1", bytes=4)
    # a save from before the window is none of its saves
    add("checkpoint/save", -9 * ms, -8 * ms, key=1, thread="checkpoint-save")
    samples = [run.Sample("multistep", 0.0, 1e-3), run.Sample("multistep", 2e-3, 3.0045e-3)]
    traced = [run.Sample("multistep", 4e-3, 5e-3)]
    job = types.SimpleNamespace(spans=lambda: list(spans),
                                trace=types.SimpleNamespace(dropped=0))
    return types.SimpleNamespace(session=types.SimpleNamespace(job=job),
                                 samples=samples, traced=traced, trace=Trace())


def test_what_a_call_costs_the_loop_leaves_fetches_and_saves_out():
    view = _window()
    # a call: 20 us enqueueing, 2 us asking; advance's own time a batch is
    # 100 us less 2 x (20 + 2 + 6) and, in the second, less the save's 10
    own = (100 - 56) + (100 - 56 - 10) + (100 - 56)
    assert files.load_module("layer_metrics", "job_issue_us_per_call.sw").read(
        view) == pytest.approx((6 * 22 + own) / 6)


def test_a_saves_busy_shares_are_unions_over_its_own_span(capsys):
    view = _window()
    fetch = files.load_module("layer_metrics", "save_fetch_busy_share")
    write = files.load_module("layer_metrics", "save_write_busy_share")
    assert fetch.read(view) == pytest.approx(100 * 2.0 / 4.0)
    assert write.read(view) == pytest.approx(100 * 2.5 / 4.0)  # 0.5 ms in both
    out = capsys.readouterr().out
    assert "the save of step 21: 2 checkpoint/fetch spans cover 50.00 %" in out
    # the reader both job cells run prints the long batches: 4.5 us over
    # a batch without a save is not long; 3.2 ms over is
    issue = files.load_module("layer_metrics", "job_issue_us_per_call.sw")
    issue.read(view)
    assert "0 of 3 batches" in capsys.readouterr().out
    view.samples[1] = run.Sample("multistep", 2e-3, 6.2e-3)
    issue.read(view)
    out = capsys.readouterr().out
    assert "1 of 3 batches are 3 ms or more over the 1.000 ms" in out
    assert "batch 1, 4.200 ms (+3.200)" in out
    lines = [line.split(":   ")[1] for line in out.splitlines() if ":   " in line]
    # every thread's spans that overlap it, runs of a name merged
    assert "checkpoint-save | checkpoint/fetch | 2 | 21 | 8 | 0.200 | 2.200 | 1.000 at 0.200" in lines
    assert "checkpoint-write-1 | checkpoint/write | 1 | 21 | 4 | 1.500 | 3.000 | 1.500 at 1.500" in lines
    assert "checkpoint-save | checkpoint/save | 1 | 21 | 0 | 0.100 | 4.100 | 4.000 at 0.100" in lines
    assert f"{MAIN} | job/advance | 1 | 1 | 0 | 0.001 | 0.101 | 0.100 at 0.001" in lines
    assert f"{MAIN} | job/enqueue stage | 1 | 21 | 0 | 0.092 | 0.097 | 0.005 at 0.092" in lines
    assert not any("| 1 | -9" in line for line in lines)


def test_every_reader_has_its_file_and_the_job_cells_list_it():
    """The seven readers are files beside the accepted ones, and since
    PR 39 the cells whose trace and spans they read list them: the five
    of the loop's thread on both job cells, the save's two on the
    restarted one."""
    benchmark = files.load_benchmark()
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    for name in READERS:
        assert hasattr(files.load_module("layer_metrics", name), "read")
        assert listed[name]["moves"] == "solver_rate"
        # membership: a later cell that the reader reads true on lists it too
        assert set(listed[name]["workloads"]) >= (
            {"sw-restart-1chip"} if name.startswith("save_")
            else {"sw-job-1chip", "sw-restart-1chip"})


def test_the_harness_runs_the_readers_once_a_cell_lists_them(
        tmp_path, monkeypatch, capsys):
    """A copy of the benchmark with a small restarted-job cell that
    lists the seven readers, no file of the benchmark edited: a traced
    run reports the three that read the job's spans alone, and, handed
    the recorded trace of other programs, says why the four that need
    the device's clock report nothing, and does not raise."""
    root, bench = make_copy(tmp_path)
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / "configs/shallow-water-restart.json").read_text())
    config["name"] = "shallow-water-restart-toy"
    config["restart"].update(every_calls=3, ahead_bytes=4096)
    config["check"].update(row_blocks=2)
    (bench / "configs/shallow-water-restart-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"] if c["name"] == "shallow-water-restart")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-restart-toy",
        file="perfbench/configs/shallow-water-restart-toy.json"))
    cell = {"config": "shallow-water-restart-toy", "traffic": "toy", "chips": 1,
            "why": "a test cell", "mesh": [1, 1],
            "grid": {"ny": 32, "nx": 64, "refine": 2},
            "rows": [{"name": "multistep", "slots": 1, "reps": 2, "trace_batches": 2}]}
    (bench / "workloads/sw-spans-toy.json").write_text(json.dumps(cell))
    benchmark["workloads"].append(
        {k: cell[k] for k in ("config", "traffic", "chips", "why")} | {"name": "sw-spans-toy"})
    for metric in benchmark["end_to_end"]:
        if "sw-restart-1chip" in metric.get("workloads", []):
            metric["workloads"].append("sw-spans-toy")
    benchmark["per_layer"] += [
        {"name": name, "unit": "x", "better": "lower", "source": "host_clock",
         "layer": "programs", "moves": "solver_rate", "workloads": ["sw-spans-toy"]}
        for name in READERS]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    from perfbench.harness import peaks

    monkeypatch.setattr(trace, "find_xplane", lambda log_dir: str(RECORDED))
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: {"hbm_gbps": 819.0})
    result = run.run_cell(cell_args("sw-spans-toy", trace=1), jax.devices(),
                          root=root, bench_dir=bench)
    assert result["correct"] is True
    assert set(result["metrics"]) == {
        "compile_s", "setup_after_chips_s", "job_issue_us_per_call.sw",
        "save_fetch_busy_share", "save_write_busy_share"}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["job_issue_us_per_call.sw"] > 0
    assert 0 < got["save_fetch_busy_share"] < 100 and 0 < got["save_write_busy_share"] < 100
    out = capsys.readouterr().out
    assert "hostspans: 2 traced batches, 2 sync spans" not in out
    assert "scopes: trace and programs do not belong together" in out
    assert "batches are 3 ms or more over" in out
