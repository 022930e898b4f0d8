"""Set-up split by the program's build spans (``harness/setupspans.py``
and its four readers): the cutting on made-up spans, what is left out
and said, the trees and processes that get nothing, the agreement with
the harness's own ``compile_s``, the entries' place in ``BENCHMARK.json``
and a small cell run end to end in a process that built nothing before."""

import json
import os
import subprocess
import sys
import types

import pytest

from perfbench import run
from perfbench.harness import files, setupspans

from perfbench_fixtures import ROOT

READERS = ["setup_trace_s", "setup_lower_s", "setup_import_s", "setup_unnamed_s"]
LISTED = ["sw-monitored-1chip", "sw-monitored-2x2-weak"]
MAIN = "MainThread"
MS = 1_000_000
WINDOW_S = 100.0  # the first batch's start, on the spans' clock


def _span(name, start_ms, end_ms, thread=MAIN, **counts):
    """A span as the recorder keeps one, ``start_ms`` and ``end_ms``
    before the window's first batch (negative: after it began)."""
    first = round(WINDOW_S * 1e9)
    return types.SimpleNamespace(
        name=name, thread=thread, counts=counts, start_ns=first - start_ms * MS,
        end_ns=first - end_ms * MS, seconds=(start_ms - end_ms) / 1e3)


def _view(after_chips_s=0.2, compile_s=0.010, traced=1, samples=2):
    batch = lambda i: run.Sample("multistep", WINDOW_S + i, WINDOW_S + i + 0.5)  # noqa: E731
    return types.SimpleNamespace(
        traced=[batch(i) for i in range(traced)],
        samples=[batch(traced + i) for i in range(samples)],
        setup={"setup_s": after_chips_s + 3.0, "after_chips_s": after_chips_s},
        compile={"compile_s": compile_s, "compiles": 1})


def _in_end_order(*spans):
    return sorted(spans, key=lambda s: s.end_ns)


NESTED = _in_end_order(
    # a multistep's trace holds the trace of what it calls and an import
    _span("build/trace", 190, 90, program="multistep"),
    _span("build/trace", 180, 150, program="wide_step"),
    _span("build/import", 140, 110, module="jax.experimental.pallas"),
    _span("build/lower", 90, 70, program="jit(multistep)"),
    _span("build/compile", 70, 60, program="jit(multistep)", cached=True),
    _span("build/import", 200, 195, module="mpi4jax_tpu"))


def test_every_moment_goes_to_the_innermost_span(capsys):
    view = _view()
    found = setupspans.split(view, NESTED, MAIN)
    assert found.self_s == pytest.approx({
        "build/trace": 0.040 + 0.030, "build/lower": 0.020,
        "build/compile": 0.010, "build/import": 0.030 + 0.005})
    assert found.unnamed_s == pytest.approx(0.2 - 0.135)
    # by construction: the four and the compile's make up after_chips_s
    assert sum(found.self_s.values()) + found.unnamed_s == pytest.approx(0.2, abs=1e-12)
    assert [(k, n, round(s, 6)) for k, n, s in found.rows] == [
        (("multistep", "build/trace"), 1, 0.040),
        (("wide_step", "build/trace"), 1, 0.030),
        (("jax.experimental.pallas", "build/import"), 1, 0.030),
        (("jit(multistep)", "build/lower"), 1, 0.020),
        (("jit(multistep)", "build/compile"), 1, 0.010),
        (("mpi4jax_tpu", "build/import"), 1, 0.005)]
    assert (found.counted, found.cached) == (6, 1)
    assert found.elsewhere == found.inside == found.astride == []
    out = capsys.readouterr().out.splitlines()
    assert "6 build spans on the batches' thread before the window (1 executables" in out[0]
    assert out[1] == "perfbench:   multistep | build/trace | 1 | 0.040000"
    assert ("of after_chips_s 0.200000: build/trace 0.070000 build/lower 0.020000 "
            "build/compile 0.010000 build/import 0.035000 unnamed 0.065000") in out[7]
    # the first span began 200 ms before the window, as the set-up did
    assert "the first build began +0.000 s from the first batch less after_chips_s" in out[8]
    assert out[-1].endswith("0 builds began inside the window, 0 lay astride its start (limit 0)")
    # made once a view: a second reader neither cuts nor prints again
    assert setupspans.split(view) is found and capsys.readouterr().out == ""


def test_a_plain_sum_would_count_nested_seconds_twice():
    found = setupspans.split(_view(), NESTED, MAIN)
    plain = sum(s.seconds for s in NESTED if s.name == "build/trace")
    assert plain == pytest.approx(0.130) and found.self_s["build/trace"] == pytest.approx(0.070)


def test_a_child_that_begins_microseconds_before_its_parent_is_moved_up():
    """A span recorded after the fact begins its seconds before the
    callback: a child's start can come out before its parent's.  It
    keeps its time, its own children theirs, and the parent is not
    given it."""
    parent = _span("build/trace", 100, 10, program="outer")
    child = _span("build/trace", 100, 40, program="inner")
    child.start_ns -= 3_000  # 3 us before its parent
    grandchild = _span("build/import", 100, 70, module="pallas")
    grandchild.start_ns -= 1_000  # after the child's start, before the parent's
    found = setupspans.split(_view(), [grandchild, child, parent], MAIN)
    assert {k[0]: round(s, 6) for k, _, s in found.rows} == {
        "outer": 0.030, "inner": 0.030, "pallas": 0.030}
    assert sum(found.self_s.values()) == pytest.approx(0.090)


def test_a_sibling_that_ends_after_its_successor_began_keeps_its_seconds():
    """jax times on ``time.time()``, the recorder on ``perf_counter_ns()``:
    a span's start after the fact can come out before the end of the span
    before it.  That one began long before: a sibling, not a child."""
    lowered = _span("build/lower", 100, 60, program="jit(multistep)")
    compiled = _span("build/compile", 60, 10, program="jit(multistep)")
    compiled.start_ns -= 1_000  # 1 us before the lowering ended
    found = setupspans.split(_view(), [lowered, compiled], MAIN)
    assert found.self_s["build/lower"] == pytest.approx(0.040, abs=2e-6)
    assert found.self_s["build/compile"] == pytest.approx(0.050, abs=2e-6)
    assert sum(found.self_s.values()) == pytest.approx(0.090, abs=1e-9)
    # under a trace that holds both, each still keeps its own
    outer = _span("build/trace", 120, 5, program="outer")
    found = setupspans.split(_view(), [lowered, compiled, outer], MAIN)
    assert found.self_s["build/lower"] == pytest.approx(0.040, abs=2e-6)
    assert found.self_s["build/trace"] == pytest.approx(0.025, abs=2e-6)


def test_what_is_not_the_set_ups_is_said_and_not_counted(capsys):
    astride = _span("build/trace", 5, -20, program="late")
    inside = _span("build/compile", -600, -700, program="jit(recompiled)")
    after = _span("build/lower", -5000, -5100, program="jit(the_comparison)")
    other = _span("build/compile", 150, 120, thread="checkpoint-save", program="jit(staged)")
    other_inside = _span("build/trace", -100, -200, thread="reader", program="theirs")
    spans = _in_end_order(*NESTED, astride, inside, after, other, other_inside)
    found = setupspans.split(_view(), spans, MAIN)
    assert found.self_s == pytest.approx({
        "build/trace": 0.070, "build/lower": 0.020, "build/compile": 0.010,
        "build/import": 0.035})
    assert found.elsewhere == [other] and found.astride == [astride]
    assert found.inside == [other_inside, inside]  # the comparison's is nobody's
    out = capsys.readouterr().out
    assert "1 builds on other threads before the window, not counted:" in out
    assert "checkpoint-save | build/compile | jit(staged) | 0.030000 s at 0.050" in out
    assert "2 builds began inside the window, 1 lay astride its start:" in out
    assert "MainThread | build/compile | jit(recompiled) | 0.100000 s at 0.600" in out
    assert "jit(the_comparison)" not in out


def test_a_long_table_ends_in_one_line_for_the_rest(capsys):
    spans = [_span("build/trace", 1000 - i, 999 - i, program=f"op{i:03}")
             for i in range(setupspans.ROWS + 5)]
    found = setupspans.split(_view(after_chips_s=2.0), spans, MAIN)
    assert len(found.rows) == setupspans.ROWS + 5
    out = capsys.readouterr().out.splitlines()
    assert out[setupspans.ROWS + 1] == "perfbench:   5 more rows | | 5 | 0.005000"


# -- who gets nothing, and is told why --------------------------------------


def _reader(name):
    return files.load_module("layer_metrics", name)


@pytest.fixture
def recorder(monkeypatch):
    """The process's recorder, an empty one in its place."""
    from mpi4jax_tpu.utils import spans

    fresh = spans.Recorder("mpi4jax_tpu.")
    monkeypatch.setattr(spans, "builds", fresh)
    return fresh


def test_a_tree_without_the_recorder_reports_nothing(monkeypatch, capsys):
    from mpi4jax_tpu.utils import spans

    monkeypatch.delattr(spans, "builds")
    for name in READERS:
        assert _reader(name).read(_view()) is None
    out = capsys.readouterr().out
    assert out.count("the program keeps no spans of what it builds; nothing is reported") == 4


def test_dropped_spans_report_nothing(monkeypatch, capsys):
    from mpi4jax_tpu.utils import spans

    small = spans.Recorder(bound=2)
    for _ in range(3):
        small.record("build/trace", 1e-3, program="f")
    monkeypatch.setattr(spans, "builds", small)
    view = _view()
    assert [_reader(name).read(view) for name in READERS] == [None] * 4
    out = capsys.readouterr().out
    # said once a view: the split is made once, found or not
    assert out.count("the recorder of builds dropped 1 spans; nothing is reported") == 1


def test_a_window_without_a_batch_reports_nothing(recorder, capsys):
    assert _reader("setup_trace_s").read(_view(traced=0, samples=0)) is None
    assert "the window holds no batch" in capsys.readouterr().out


def test_nothing_is_cut_at_the_front_and_the_first_build_is_printed(capsys):
    """``run.py`` builds nothing before it loads the driver; a reader of
    the log sees that hold, or not, in one printed line."""
    spans = [_span("build/import", 800, 700, module="mpi4jax_tpu"),
             _span("build/trace", 400, 300, program="multistep")]
    found = setupspans.split(_view(after_chips_s=0.9), spans, MAIN)
    assert found.self_s["build/import"] == pytest.approx(0.1)
    assert found.unnamed_s == pytest.approx(0.7)
    assert ("the first build began +0.100 s from the first batch less "
            "after_chips_s") in capsys.readouterr().out
    # a process that built before this set-up began: counted, and the line says so
    found = setupspans.split(_view(after_chips_s=0.5), spans, MAIN)
    assert found.unnamed_s == pytest.approx(0.3)
    assert "the first build began -0.300 s from" in capsys.readouterr().out


# -- the four readers ---------------------------------------------------------


def test_the_readers_read_the_process_recorder_and_agree_with_compile_s(
        recorder, capsys):
    """The recorder's clock is the batches': spans recorded now, a window
    that begins a moment later."""
    import time

    recorder.record("build/import", 0.030, module="jax.experimental.pallas")
    recorder.record("build/trace", 0.050, program="multistep")  # holds the import
    time.sleep(0.030)
    recorder.record("build/lower", 0.020, program="jit(multistep)")
    time.sleep(0.020)
    recorder.record("build/compile", 0.010, program="jit(multistep)", cached=True)
    now = time.perf_counter()
    view = _view(after_chips_s=0.5, compile_s=0.010)
    view.traced = [run.Sample("multistep", now, now + 0.1)]
    view.samples = [run.Sample("multistep", now + 0.1, now + 0.2)]
    got = {name: _reader(name).read(view) for name in READERS}
    assert got == pytest.approx({
        "setup_trace_s": 0.020, "setup_lower_s": 0.020, "setup_import_s": 0.030,
        "setup_unnamed_s": 0.5 - 0.080}, abs=1e-4)
    out = capsys.readouterr().out
    assert out.count("program | phase | count | self s") == 1  # the first to run prints
    assert ("build/compile's self time 0.010000 s, the harness's compile_s 0.010000: "
            "0.000 ms apart\n") in out
    # a compile the spans did not see on this thread is said, and still reported
    view = types.SimpleNamespace(**{**vars(view), "compile": {"compile_s": 0.3}})
    del view._setup_split
    assert _reader("setup_unnamed_s").read(view) == pytest.approx(0.42, abs=1e-4)
    assert "290.000 ms apart (MORE THAN 10 ms" in capsys.readouterr().out


def test_the_four_entries_are_appended_after_what_per_layer_held():
    benchmark = files.load_benchmark(ROOT)
    readers = [m["name"] for m in benchmark["per_layer"]]
    first = readers.index(READERS[0])
    assert readers[first:first + 4] == READERS
    # after what was there, in the order it was there; a later PR may follow
    assert readers[first - 4:first] == [
        "adjoint_device_share.sw", "adjoint_memory_share",
        "adjoint_exchange_device_share.sw", "adjoint_hbm_roofline_share"]
    assert readers[:3] == ["compile_s", "sw_device_ops_per_step", "sw_hbm_roofline_share"]
    assert len(readers) == len(set(readers)) and first == 52
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    for name in READERS:
        entry = dict(listed[name])
        # the two cells no passing test holds to a fixed list of readers; the
        # `benchmark` PR that relaxes the others' appends them (`ROADMAP.md` M21)
        assert entry.pop("workloads")[:2] == LISTED
        assert entry == {"name": name, "unit": "s", "better": "lower",
                         "source": "program_span", "layer": "entry", "moves": "setup_s"}
        assert hasattr(_reader(name), "read")
    for cell in benchmark["workloads"]:
        mine = {m["name"] for m in files.metrics_of(benchmark, "per_layer", cell["name"])}
        assert mine & set(READERS) == (
            set(READERS) if cell["name"] in listed[READERS[0]]["workloads"] else set())
    assert {m["layer"] for m in benchmark["per_layer"] if m["moves"] == "setup_s"} == {"entry"}


# -- a small cell, end to end -------------------------------------------------

END_TO_END = """
import json, sys
sys.path.insert(0, {tests!r})
import jax
from perfbench import run
from perfbench.harness import files, peaks, trace
from perfbench_fixtures import cell_args, make_copy
recorded = files.BENCH_DIR / "testdata" / "solver-1chip.xplane.pb"
trace.find_xplane = lambda log_dir: str(recorded)
peaks.peaks_for = lambda kind: {{"hbm_gbps": 819.0}}
root, bench = make_copy({tmp!r})
benchmark = json.loads((root / "BENCHMARK.json").read_text())
benchmark["per_layer"] += [
    {{"name": name, "unit": "s", "better": "lower", "source": "program_span",
      "layer": "entry", "moves": "setup_s", "workloads": ["sw-toy-1x1"]}}
    for name in {readers!r}]
(root / "BENCHMARK.json").write_text(json.dumps(benchmark))
result = run.run_cell(cell_args("sw-toy-1x1", trace=1), jax.devices(), root=root, bench_dir=bench)
print("RESULT " + json.dumps(result))
from mpi4jax_tpu.utils import spans
first = min(s.start_ns for s in spans.builds.spans())
print("FIRST", first / 1e9 - run._T0)
"""


def test_a_traced_run_in_a_fresh_process_splits_its_set_up(tmp_path):
    """The benchmark's command builds nothing before the driver loads:
    run so, on the CPU's devices, the four are reported, make up
    ``setup_after_chips_s`` with the compile's self time, and that
    agrees with the harness's own ``compile_s``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    code = END_TO_END.format(tests=str(ROOT / "tests" / "perfbench"), tmp=str(tmp_path),
                             readers=READERS)
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    out = done.stdout
    result = json.loads(out.split("RESULT ", 1)[1].splitlines()[0])
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(READERS) <= set(got) and all(
        result["metrics"][name]["unit"] == "s" for name in READERS)
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    assert got["setup_import_s"] > 0 and got["setup_unnamed_s"] > 0
    line = next(l for l in out.splitlines() if "build/compile's self time" in l)
    compiled = float(line.split("self time ")[1].split()[0])
    assert "MORE THAN" not in line
    assert compiled == pytest.approx(got["compile_s"], abs=1e-5)
    assert sum(got[name] for name in READERS) + compiled == pytest.approx(
        got["setup_after_chips_s"], abs=1e-5)
    assert "0 builds began inside the window, 0 lay astride its start (limit 0)" in out
    assert "mpi4jax_tpu | build/import | 1 |" in out
    # nothing was built before the driver was loaded: the first span is the package's
    assert float(out.split("FIRST ")[1].split()[0]) > 0
