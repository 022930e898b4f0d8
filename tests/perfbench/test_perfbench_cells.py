"""A run of the benchmark end to end on the CPU's virtual devices: small
cells added as new files, both references against the library, the
controls and broken timed paths that have to come out not correct."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import run
from perfbench.harness import files
from perfbench.harness.compilemeter import CompileMeter

from perfbench_fixtures import ROOT, cell_args, make_copy

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
TOY_CELLS = ["sw-toy-1x1", "sw-toy-2x2", "coll-toy"]
TABLE_ROWS = ["allreduce-4MiB", "allreduce-64MiB", "allgather-4MiB", "alltoall-4MiB",
              "bcast-4MiB", "sendrecv-4MiB", "halo-1804x3604"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(tmp_path_factory.mktemp("perfbench"))


def _run(copy, cell, **kw):
    root, bench = copy
    return run.run_cell(cell_args(cell, **kw), jax.devices(), root=root, bench_dir=bench)


def _session(copy, cell, seed=11):
    root, bench = copy
    workload = files.load_json("workloads", cell, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    session = driver.setup(
        run.Context(config, workload, seed, jax.devices(), bench))
    for row in workload["rows"]:
        session.batch(row["name"])
    return session


@pytest.mark.parametrize("cell", TOY_CELLS)
def test_a_cell_added_as_new_files_runs_and_is_correct(copy, cell, capsys):
    result = _run(copy, cell)
    assert set(result) == LINE_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    benchmark = files.load_benchmark(copy[0])
    wanted = {m["name"] for m in files.metrics_of(benchmark, "end_to_end", cell)}
    assert set(result["metrics"]) == wanted and "setup_s" in wanted
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in result["metrics"].values())
    # every number compared is printed beside its limit: last on standard
    # error, and under the line's last key
    err = capsys.readouterr().err.splitlines()
    assert list(result)[-1] == "checks" and result["checks"]
    assert len(err) >= len(result["checks"])
    for line, (name, c) in zip(err[-len(result["checks"]):], result["checks"].items()):
        assert f"check {name}: {c['value']!r} against the limit {c['limit']!r}: ok" in line
    json.dumps(result)


@pytest.fixture
def recorded_trace(monkeypatch):
    """A traced run needs a chip's trace and the chip's peaks: hand the
    reduction the recorded one."""
    from perfbench.harness import peaks, trace

    recorded = files.BENCH_DIR / "testdata" / "solver-1chip.xplane.pb"
    monkeypatch.setattr(trace, "find_xplane", lambda log_dir: str(recorded))
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: {"hbm_gbps": 819.0})


@pytest.mark.parametrize("cell,has,lacks", [
    # the recorded trace is of another program than the toy cell's: the
    # readers that hold an event against its program's text report nothing
    ("sw-toy-1x1", {"toy_batches", "compile_s", "setup_after_chips_s",
                    "sw_device_ops_per_step", "device_idle_share.sw"},
     "sw_hbm_roofline_share"),
    ("coll-toy", {"compile_s", "setup_after_chips_s", "allreduce_tax_large",
                  "allreduce_tax_small", "coll_row_busbw",
                  "device_idle_share.coll", "coll_table_tax"}
     | {f"row_tax.{row}" for row in TABLE_ROWS}, "toy_batches"),
])
def test_a_traced_run_reports_the_cells_per_layer_metrics(
        copy, recorded_trace, cell, has, lacks):
    result = _run(copy, cell, trace=1)
    assert set(result) == LINE_KEYS | {"breakdown"}
    assert list(result)[-1] == "checks"
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert set(result["metrics"]) == has and lacks not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    assert result["correct"] is True


@pytest.mark.parametrize("cell", TOY_CELLS)
def test_the_control_in_the_programs_place_is_not_correct(copy, cell):
    session = _session(copy, cell)
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    control = session.control()
    assert any(c["value"] > c["limit"] for c in control), control


def test_a_step_that_returns_its_state_unchanged_is_not_correct(copy, monkeypatch):
    from mpi4jax_tpu.models import shallow_water as sw

    monkeypatch.setattr(
        sw, "make_multistep", lambda cfg, comm, n, donate=False: lambda state: state)
    result = _run(copy, "sw-toy-1x1")
    assert result["correct"] is False and result["failed"] == 0


def test_an_allreduce_that_leaves_out_the_exchange_is_not_correct(copy, monkeypatch):
    import mpi4jax_tpu as m

    monkeypatch.setattr(m, "allreduce", lambda x, op, comm=None: (x, None))
    result = _run(copy, "coll-toy")
    assert result["correct"] is False


# -- the table's other rows: their mean, and every row's plain program --


@pytest.fixture(scope="module")
def coll_session(copy):
    return _session(copy, "coll-toy")


def _batches(session, seconds_a_call):
    """Two batches a row whose times a call average to the given ones."""
    return [run.Sample(name, 0.0, per_call * share * session.units(name))
            for name, per_call in seconds_a_call.items() for share in (0.5, 1.5)]


def test_the_tables_geometric_mean_on_hand_made_samples(coll_session):
    table = coll_session.ctx.workload["roles"]["table"]
    assert table == TABLE_ROWS
    made = {name: 2.0 ** (i - 20) for i, name in enumerate(table)}  # 1 .. 64 x 2**-20 s
    made["allreduce-2GiB"] = made["allreduce-8B"] = 1.0  # no row of the table
    got = coll_session.end_to_end(_batches(coll_session, made))
    # the middle row's time, not the arithmetic mean, which the longest row owns
    assert got["coll_table_geomean_us"] == pytest.approx(8 * 2.0 ** -20 * 1e6, rel=1e-14)


@pytest.mark.parametrize("missing", ["allreduce-64MiB", "halo-1804x3604"])
def test_a_table_row_with_no_sample_leaves_the_mean_out(coll_session, missing):
    made = {name: 1e-5 for name in coll_session.rows if name != missing}
    got = coll_session.end_to_end(_batches(coll_session, made))
    assert "coll_table_geomean_us" not in got
    assert {"coll_busbw", "coll_lat_p95_us"} <= set(got)


def test_a_table_row_with_no_slot_fails_the_run(tmp_path):
    """A cell added as new files whose table lists a row that never
    comes: no mean over fewer rows, and no result line."""
    root, bench = make_copy(tmp_path)
    cell = json.loads((bench / "workloads/coll-toy.json").read_text())
    cell["traffic"] = "coll-toy-gap"
    for row in cell["rows"]:
        if row["name"] == "bcast-4MiB":
            row["slots"] = 0
    (bench / "workloads/coll-toy-gap.json").write_text(json.dumps(cell))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    benchmark["workloads"].append(
        {k: cell[k] for k in ("config", "traffic", "chips", "why")}
        | {"name": "coll-toy-gap"})
    for metric in benchmark["end_to_end"]:
        if "coll-toy" in metric.get("workloads", []):
            metric["workloads"].append("coll-toy-gap")
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    with pytest.raises(RuntimeError, match="no coll_table_geomean_us"):
        run.run_cell(cell_args("coll-toy-gap"), jax.devices(), root=root,
                     bench_dir=bench)


def _plain_against_the_reference(session, row):
    """Mismatches of the row's plain program, chained as the probe
    chains it, with the numpy reference."""
    driver = files.load_module("drivers", "collectives")
    plain = session._program(row, driver.plain_op(row, session.grid))
    y = session.batch(row["name"], plain)
    assert y is not session.last[row["name"]]
    return session._mismatches(row["name"], row, y)


@pytest.mark.parametrize("name", ["allreduce-8B", "allreduce-2GiB"] + TABLE_ROWS)
def test_every_rows_plain_program_is_the_reference_bit_for_bit(coll_session, name):
    row = coll_session.rows[name]
    assert _plain_against_the_reference(coll_session, row) == 0
    # and the comparison tells the result from the input it was made of
    x, _ = coll_session._to_host(name, row, coll_session.inputs[name])
    assert coll_session.ref.mismatches(
        x, coll_session.ref.expected(row, x, coll_session.grid)) > 0


@pytest.mark.parametrize("periodic", [
    [False, True], [True, False], [False, False], [True, True]])
@pytest.mark.parametrize("width", [1, 2])
def test_the_plain_halo_on_periodic_and_walled_axes(coll_session, periodic, width):
    row = dict(coll_session.rows["halo-1804x3604"], periodic=periodic, width=width)
    assert _plain_against_the_reference(coll_session, row) == 0


@pytest.mark.parametrize("grid", [(2, 4), (4, 2), (1, 8), (8, 1)])
@pytest.mark.parametrize("periodic", [[False, True], [True, False]])
def test_the_plain_halo_on_grids_with_chips_between_walls(grid, periodic, coll_reference):
    """Chips with a neighbour on both sides of a walled axis, and an
    axis of one chip: ``plain_halo`` by itself on the 8 virtual devices."""
    driver = files.load_module("drivers", "collectives")
    py, px = grid
    mesh = jax.make_mesh(grid, driver.AXES, devices=jax.devices()[:py * px],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ly, lx, w = 10, 12, 2
    rng = np.random.default_rng(7)
    whole = rng.integers(-9, 9, (py * ly, px * lx)).astype(np.float32)
    spec = jax.P(*driver.AXES)
    got = jax.jit(jax.shard_map(
        driver.plain_halo(grid, w, tuple(periodic)), mesh=mesh,
        in_specs=spec, out_specs=spec))(whole)

    def blocks(a):
        return (np.asarray(a).reshape(py, ly, px, lx).transpose(0, 2, 1, 3)
                .reshape(py * px, ly, lx))

    want = coll_reference.expected(
        {"op": "halo", "width": w, "periodic": periodic}, blocks(whole), grid)
    assert coll_reference.mismatches(blocks(got), want) == 0
    assert coll_reference.mismatches(blocks(got), blocks(whole)) > 0


BROKEN_PLAIN = {
    # a ring shift that leaves out the exchange
    "sendrecv-4MiB": ("lax.ppermute(x, AXES, ring)", "x * 1.0"),
    # a halo exchange that forgets the walls: their ghosts take the
    # zeros a permute gives a chip nobody sends to
    "halo-1804x3604": (
        "jnp.where((source >= 0) & (source < size), got, kept)", "got"),
}


@pytest.mark.parametrize("row", sorted(BROKEN_PLAIN))
def test_a_plain_program_made_wrong_fails_the_probe_and_the_run(
        tmp_path, recorded_trace, row):
    root, bench = make_copy(tmp_path)
    path = bench / "drivers/collectives.py"
    sound, broken = BROKEN_PLAIN[row]
    assert path.read_text().count(sound) == 1
    path.write_text(path.read_text().replace(sound, broken))
    result = run.run_cell(cell_args("coll-toy", trace=1), jax.devices(),
                          root=root, bench_dir=bench)
    assert result["correct"] is False and result["failed"] == 0
    wrong = {k for k, c in result["checks"].items() if c["value"] > c["limit"]}
    assert wrong == {f"plain_mismatches_{row}"}
    # no time of a wrong program counts: its tax and the table's are left out
    taxes = {f"row_tax.{r}" for r in TABLE_ROWS}
    assert taxes - set(result["metrics"]) == {f"row_tax.{row}"}
    assert "coll_table_tax" not in result["metrics"]
    assert {"allreduce_tax_large", "allreduce_tax_small"} <= set(result["metrics"])


def test_a_probe_compares_every_plain_program_and_reads_every_tax(
        copy, recorded_trace):
    result = _run(copy, "coll-toy", trace=1)
    rows = ["allreduce-8B", "allreduce-2GiB"] + TABLE_ROWS
    assert {k for k in result["checks"] if k.startswith("plain_")} == {
        f"plain_mismatches_{r}" for r in rows}
    assert all(c == {"value": 0, "limit": 0} for c in result["checks"].values())
    values = {k: v["value"] for k, v in result["metrics"].items()
              if k.startswith("row_tax.")}
    assert set(values) == {f"row_tax.{r}" for r in TABLE_ROWS}
    assert all(v > 0 for v in values.values())
    # the table's tax is a mean of the rows' ratios, so it lies among them
    assert min(values.values()) <= result["metrics"]["coll_table_tax"]["value"] <= max(
        values.values())


def test_the_control_fails_every_row_of_the_table(coll_session):
    control = coll_session.control()
    assert [c["name"] for c in control] == [
        f"mismatches_{name}" for name in coll_session.rows]
    assert all(c["value"] > c["limit"] for c in control), control


def test_a_compilation_inside_the_window_is_counted():
    class Compiles:
        def batch(self, row):
            jax.block_until_ready(jax.jit(lambda x: x + len(seen))(jnp.zeros(3)))
            seen.append(row)

    seen = []
    meter = CompileMeter().start()
    _, samples, traced, failed, compiled = run.run_window(
        Compiles(), ["a"], 0.05, meter)
    assert failed == 0 and not traced
    assert compiled >= len(samples) > 0


def test_a_failed_batch_is_counted_and_ends_the_window():
    class Fails:
        def batch(self, row):
            raise RuntimeError("no")

    _, samples, _, failed, _ = run.run_window(
        Fails(), ["a"], 5.0, CompileMeter().start())
    assert failed == 1 and not samples


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp_path):
    # jax is imported before the look for a chip: its bytecode goes to tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPYCACHEPREFIX=str(tmp_path))
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "sw-bench-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert "metrics" not in done.stdout and "correct" not in done.stdout


def test_the_runtime_environment_keeps_what_the_caller_set(monkeypatch):
    monkeypatch.delenv("TPU_PREMAPPED_BUFFER_SIZE", raising=False)
    monkeypatch.delenv("TPU_LOG_DIR", raising=False)
    run.runtime_environment()
    assert os.environ["TPU_PREMAPPED_BUFFER_SIZE"] == str(256 << 20)
    assert os.environ["TPU_LOG_DIR"] == "disabled"
    monkeypatch.setenv("TPU_PREMAPPED_BUFFER_SIZE", "1024")
    run.runtime_environment()
    assert os.environ["TPU_PREMAPPED_BUFFER_SIZE"] == "1024"


IMPORTS_TWICE = """
import importlib, importlib.machinery, json, sys
from perfbench import run

root, modules = sys.argv[1:]
run.enable_bytecode_cache(root)
compiled = []
to_code = importlib.machinery.SourceFileLoader.source_to_code
def counting(self, data, path, **kw):
    if "throwaway" in path:
        compiled.append(path)
    return to_code(self, data, path, **kw)
importlib.machinery.SourceFileLoader.source_to_code = counting
sys.path.insert(0, modules)
import throwaway
first = len(compiled)
del sys.modules["throwaway"]
importlib.invalidate_caches()
import throwaway
print(json.dumps({"first": first, "second": len(compiled) - first,
                  "cached": throwaway.__cached__, "answer": throwaway.ANSWER,
                  "prefix": sys.pycache_prefix}))
"""


@pytest.mark.parametrize("callers", [False, True])
def test_the_bytecode_cache_is_written_once_and_read_after(tmp_path, callers):
    """The image's PYTHONDONTWRITEBYTECODE=1 is overridden; the cache is
    at a fixed path in the checkout unless the caller named a prefix."""
    modules = tmp_path / "modules"
    modules.mkdir()
    (modules / "throwaway.py").write_text("ANSWER = 6 * 7\n")
    root, theirs = tmp_path / "checkout", tmp_path / "theirs"
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1", PYTHONPATH=str(ROOT))
    env.pop("PYTHONPYCACHEPREFIX", None)
    if callers:
        env["PYTHONPYCACHEPREFIX"] = str(theirs)
    done = subprocess.run(
        [sys.executable, "-c", IMPORTS_TWICE, str(root), str(modules)],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    said = json.loads(done.stdout.splitlines()[-1])
    assert said["answer"] == 42
    assert (said["first"], said["second"]) == (1, 0)  # compiled once, then read
    prefix = theirs if callers else root / run.PYCACHE_DIR
    assert said["prefix"] == str(prefix)
    cached = pathlib.Path(said["cached"])
    assert cached.is_file() and prefix in cached.parents
    assert not (modules / "__pycache__").exists()
    assert (root / run.PYCACHE_DIR).exists() is not callers


def test_both_commands_turn_the_bytecode_cache_on_before_jax_starts(monkeypatch):
    from perfbench import control

    calls = []

    def stop(chips):
        raise SystemExit("no chips in this test")

    monkeypatch.setattr(run, "enable_bytecode_cache", lambda root: calls.append(root))
    monkeypatch.setattr(run, "runtime_environment", lambda: calls.append("runtime"))
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "require_chips", stop)
    for main, argv in ((run.main, ["--seed", "1", "--seconds", "1"]),
                       (control.main, ["--seeds", "1"])):
        with pytest.raises(SystemExit):
            main(["--workload", "sw-bench-1chip", *argv])
    assert calls == [files.ROOT, "runtime"] * 2


def test_set_up_after_the_chips_is_a_per_layer_metric_of_every_cell(
        copy, recorded_trace, capsys):
    benchmark = files.load_benchmark()
    entry = {m["name"]: m for m in benchmark["per_layer"]}["setup_after_chips_s"]
    assert entry == {"name": "setup_after_chips_s", "unit": "s", "better": "lower",
                     "source": "host_clock", "layer": "entry", "moves": "setup_s"}
    result = _run(copy, "sw-toy-2x2", trace=1)
    after = result["metrics"]["setup_after_chips_s"]["value"]
    out = capsys.readouterr().out
    whole = float(out.split("perfbench: setup_s = ")[1].split()[0])
    in_driver = float(out.split(" s in the driver")[0].rsplit(" ", 1)[1])
    # the printed half is rounded; the schedule and the trace directory lie between
    assert 0 < in_driver - 0.001 <= after < whole
    assert after - in_driver < 0.5


def test_the_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path):
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        run.enable_compile_cache(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        jax.config.update("jax_compilation_cache_dir", "/kept")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/kept")
        run.enable_compile_cache(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "/kept"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def test_a_cell_that_benchmark_json_does_not_name_is_refused(copy):
    with pytest.raises(files.BenchmarkFileError):
        _run(copy, "no-such-cell")


# -- the references by themselves -------------------------------------


@pytest.fixture(scope="module")
def sw_reference():
    return files.load_module("references", "shallow-water")


@pytest.fixture(scope="module")
def sw_start():
    driver = files.load_module("drivers", "shallow_water")
    config = files.load_json("configs", "shallow-water")
    modes = driver.mode_table(5, config["assumed"]["perturbation"])
    fields = driver.make_fields(config["model"], 96, 32, 5e3, 5e3)(modes)
    return config, fields


def test_the_reference_in_bands_of_rows_is_the_reference(sw_reference, sw_start):
    config, fields = sw_start
    params = sw_reference.parameters(config["model"], 5e3, 5e3)
    steps = 3
    whole = sw_reference.run(*fields, params, steps)
    bands = sw_reference.row_blocks(96, 3, steps)
    assert [b[2:] for b in bands] == [(0, 32), (32, 64), (64, 96)]
    assert bands[1][:2] == (32 - 18, 64 + 18)  # a real band, not the domain
    for lo, hi, keep_lo, keep_hi in bands:
        part = sw_reference.run(*(a[lo:hi] for a in fields), params, steps, "float32", lo)
        for w, p in zip(whole, part):
            np.testing.assert_allclose(
                p[keep_lo - lo:keep_hi - lo], w[keep_lo:keep_hi], rtol=0, atol=1e-6)


def test_the_reference_moves_and_bfloat16_moves_it_far(sw_reference, sw_start):
    config, fields = sw_start
    params = sw_reference.parameters(config["model"], 5e3, 5e3)
    h32, _, _ = sw_reference.run(*fields, params, 11)
    h16, _, _ = sw_reference.run(*fields, params, 11, "bfloat16")
    assert np.isfinite(np.asarray(h32)).all()
    assert float(jnp.max(jnp.abs(h32 - fields[0]))) > 1e-3  # eleven steps move h
    assert float(jnp.max(jnp.abs(h16 - h32))) > 0.1  # half a metre an ulp at 100 m


def test_seeded_modes_are_periodic_and_bounded():
    driver = files.load_module("drivers", "shallow_water")
    assumed = files.load_json("configs", "shallow-water")["assumed"]["perturbation"]
    a, b = driver.mode_table(2**31 + 9, assumed), driver.mode_table(2**31 + 9, assumed)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, driver.mode_table(3, assumed))
    assert a.shape == (3, 5)
    assert (a[:, 0] % 2 == 0).all() and (a[:, :2] >= 4).all() and (a[:, :2] <= 12).all()
    assert a[:, 4].sum() == pytest.approx(0.2)


@pytest.fixture(scope="module")
def coll_reference():
    return files.load_module("references", "collectives")


def test_collectives_reference_semantics(coll_reference):
    x = np.arange(4 * 8, dtype=np.float32).reshape(4, 8)
    ref = coll_reference.expected
    np.testing.assert_array_equal(
        ref({"op": "allreduce"}, x, (2, 2)), np.tile(x.sum(0), (4, 1)))
    np.testing.assert_array_equal(ref({"op": "bcast", "root": 2}, x, (2, 2)), np.tile(x[2], (4, 1)))
    np.testing.assert_array_equal(ref({"op": "sendrecv", "shift": 1}, x, (2, 2))[1], x[0])
    assert ref({"op": "allgather"}, x, (2, 2)).shape == (4, 4, 8)
    blocks = x.reshape(4, 4, 2)
    out = ref({"op": "alltoall"}, blocks, (2, 2))
    np.testing.assert_array_equal(out[3][1], blocks[1][3])
    with pytest.raises(ValueError):
        ref({"op": "gossip"}, x, (2, 2))


def test_collectives_reference_halo(coll_reference):
    # four 6x6 blocks with a ring of 1; block r is filled with r + 1
    x = np.stack([np.full((6, 6), r + 1.0, np.float32) for r in range(4)])
    row = {"op": "halo", "width": 1, "periodic": [False, True]}
    out = coll_reference.expected(row, x, (2, 2))
    top_left = out[0]
    assert top_left[2, 0] == 2 and top_left[2, -1] == 2  # x is periodic: both from rank 1
    assert top_left[-1, 2] == 3  # north neighbour
    assert top_left[0, 2] == 1  # a wall keeps its ghost row
    assert top_left[-1, -1] == 4  # the corner arrives through the second exchange
    assert coll_reference.mismatches(out, out) == 0
    assert coll_reference.mismatches(out, out + 1) == out.size
    # a result of another shape never broadcasts to a match
    assert coll_reference.mismatches(out[0], np.stack([out[0]] * 4)) == 4 * out[0].size


def test_payloads_sum_exactly_in_float32():
    lo, hi = files.load_json("configs", "collectives")["model"]["payload_values"]
    assert 4 * max(abs(lo), abs(hi)) <= 2**23
