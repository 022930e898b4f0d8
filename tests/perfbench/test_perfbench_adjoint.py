"""The cell of the differentiated run on the CPU's virtual devices: the
configuration's file against ``shallow-water``'s and the workload file
against ``BENCHMARK.json``; a small cell of the configuration added as
new files to a copy of the benchmark and run end to end; the control;
the reference's bands; and the four per-layer readers on made-up traces
whose values are computed by hand, and on a session that lacks the
programs."""

import json
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import run
from perfbench.harness import files
from perfbench.harness.trace import Event, Trace

from perfbench_fixtures import ROOT, TABLES, cell_args, make_copy

CELL = "sw-adjoint-1chip"
TOY = "sw-adjoint-toy"
ACCEPTED_CELLS = [
    "sw-bench-1chip", "coll-2x2", "sw-job-1chip", "sw-restart-1chip",
    "sw-as-written-1chip", "sw-output-restart-1chip", "sw-monitored-1chip",
    "sw-monitored-2x2-weak"]
NEW_READERS = [
    "adjoint_device_share.sw",
    "adjoint_memory_share", "adjoint_exchange_device_share.sw",
    "adjoint_hbm_roofline_share"]
# the accepted metrics that read true on the new cell as they stand
APPENDED_TO = ["solver_rate", "solver_step_p95_us", "device_idle_share.sw",
               "sw_device_ops_per_step"]
CHECKS = {
    "nonfinite_after_window", "cost_last_over_first", "gradients_not_counted",
    "max_abs_diff_h", "max_abs_diff_u", "max_abs_diff_v",
} | {f"gradient_rel_l2_{k}_band{i}" for k in "huv" for i in range(4)}


# -- the files ---------------------------------------------------------


def test_the_configuration_is_shallow_waters_with_a_window():
    config = files.load_json("configs", "shallow-water-adjoint")
    accepted = files.load_json("configs", "shallow-water")
    assert config["model"] == accepted["model"]
    assert config["architecture"] is None
    assert config["reduced"] == ["window.calls"]
    assert config["window"]["calls"] == config["check"]["calls"] == 4
    assert config["window"]["control"] == ["h0", "u0", "v0"]
    assert config["check"]["limits"] == accepted["check"]["limits"]
    assert {k: config["guarantees"][k] for k in ("precision", "every_step", "agreement")} == {
        k: accepted["guarantees"][k] for k in ("precision", "every_step", "agreement")}
    assert set(config["guarantees"]) - set(accepted["guarantees"]) == {
        "whole_window", "gradient", "descent"}
    assert {k: config["assumed"][k] for k in ("run_length",)} != {}
    assert config["assumed"]["perturbation"]["modes"] == (
        accepted["assumed"]["perturbation"]["modes"])
    limits = config["check"]["gradient_limits"]
    assert set(limits) == {"h", "u", "v"} == set(config["check"]["gradient_limits_why"])
    assert all(0 < v < 1 for v in limits.values())
    entry = next(c for c in files.load_benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "perfbench/configs/shallow-water-adjoint.json"
    assert entry["reduced"] == config["reduced"]


def test_the_plain_reference_imports_nothing_of_the_program():
    text = (ROOT / "perfbench/references/shallow-water-adjoint.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+mpi4jax_tpu", text, re.M)
    assert not re.search(r"^\s*(import|from)\s+perfbench", text, re.M)
    assert "custom_vjp" not in text.replace("no\n``custom_vjp``", "").replace(
        "``custom_vjp``", "")


def test_the_workload_file_says_what_benchmark_json_says():
    benchmark = files.load_benchmark(ROOT)
    entry = files.find_cell(benchmark, CELL)
    workload = files.load_json("workloads", CELL)
    assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
        k: entry[k] for k in ("config", "chips", "traffic", "why")}
    assert entry["config"] == "shallow-water-adjoint" and entry["chips"] == 1
    assert entry["traffic"] == "bench-domain-dx2-gradient-closed-loop"
    assert len(entry["why"]) <= 200
    grid = workload["grid"]
    assert (grid["ny"], grid["nx"], grid["refine"]) == (3600, 7200, 2)
    assert workload["mesh"] == [1, 1]
    assert workload["rows"] == [
        {"name": "gradient", "slots": 1, "reps": 1, "trace_batches": 2}]


def test_the_cell_is_appended_and_nothing_before_it_moved():
    benchmark = files.load_benchmark(ROOT)
    cells = [c["name"] for c in benchmark["workloads"]]
    assert cells[:9] == ACCEPTED_CELLS + [CELL]
    configs = [c["name"] for c in benchmark["configs"]]
    assert configs[7] == "shallow-water-adjoint" and configs[6] == (
        "shallow-water-monitored")
    readers = [m["name"] for m in benchmark["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + 4] == NEW_READERS
    assert readers[first - 1] == "monitor_allreduce_us_per_call"  # PR 51's last
    listed = {m["name"]: m for m in benchmark["end_to_end"] + benchmark["per_layer"]}
    for name in NEW_READERS:
        entry = listed[name]
        assert entry["moves"] == "solver_rate" and set(entry) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["workloads"][0] == CELL  # a later cell may follow
        assert (ROOT / f"perfbench/layer_metrics/{name}.py").is_file()
    assert [listed[n]["layer"] for n in NEW_READERS] == [
        "programs", "programs", "op surface", "kernels"]
    for name in APPENDED_TO:  # after what was there; a later cell may follow
        cells_of = listed[name]["workloads"]
        assert cells_of.index(CELL) == sum(c in ACCEPTED_CELLS for c in cells_of)
    assert {m["name"] for m in listed.values()
            if CELL in m.get("workloads", ())} == set(NEW_READERS + APPENDED_TO)
    chips = [c["chips"] for c in benchmark["workloads"][:9]]
    assert chips.count(4) == 2  # the share is spent: the new cell takes one chip


# -- a small cell, end to end ---------------------------------------------


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The fixtures' copy with a cell of ``shallow-water-adjoint`` more:
    32x64 cells on one device, a window of two calls, observed over 2x2
    cells."""
    root, bench = make_copy(tmp_path_factory.mktemp("perfbench_adjoint"))
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((bench / "configs/shallow-water-adjoint.json").read_text())
    config["name"] = "shallow-water-adjoint-toy"
    config["window"]["calls"] = 2
    config["check"].update(calls=2, row_blocks=1, band_rows=8)
    (bench / "configs/shallow-water-adjoint-toy.json").write_text(json.dumps(config))
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == "shallow-water-adjoint")
    benchmark["configs"].append(dict(
        entry, name="shallow-water-adjoint-toy",
        file="perfbench/configs/shallow-water-adjoint-toy.json"))
    cell = {
        "config": "shallow-water-adjoint-toy", "traffic": TOY, "chips": 1,
        "why": "a test cell", "mesh": [1, 1],
        "grid": {"ny": 32, "nx": 64, "refine": 2},
        "rows": [{"name": "gradient", "slots": 1, "reps": 1}],
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
        run.Context(config, workload, 2**31 + 54, jax.devices(), bench))
    for _ in range(3):
        made.batch("gradient")
    return made


def test_the_cell_runs_and_every_check_is_beside_its_limit(copy, capsys):
    result = run.run_cell(
        cell_args(TOY, seconds=1.0), jax.devices(), root=copy[0], bench_dir=copy[1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"solver_rate", "solver_step_p95_us", "setup_s"}
    assert set(result["checks"]) == CHECKS
    assert result["checks"]["cost_last_over_first"]["value"] < 1.0
    assert "step length" in capsys.readouterr().out


def test_the_control_is_not_correct_and_the_program_is(session):
    sound = session.check()
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert {c["name"] for c in sound} == CHECKS
    control = session.control()
    assert {c["name"] for c in control} == {
        f"gradient_rel_l2_{k}_band{i}" for k in "huv" for i in range(4)}
    # the reference carried in bfloat16 in the program's place: refused,
    # and by more than one band's one field
    refused = [c for c in control if c["value"] > c["limit"]]
    assert len(refused) >= 6, control


def test_the_fits_counters_and_costs(session):
    counted = session.facts()["adjoint"]
    # set-up's warm batch and the three here
    assert counted["gradients"] == len(counted["costs"]) == 4
    assert counted["window_steps"] == 21
    assert set(counted) == {
        "gradients", "window_steps", "trajectory_bytes", "costs"}
    # here the step is array code: its tendencies are interior-shaped
    state = 3 * ((32 + 4) * (64 + 4) + 32 * 64) * 4
    assert counted["trajectory_bytes"] == (2 + 10) * state
    costs = counted["costs"]
    assert all(b < a for a, b in zip(costs, costs[1:])), costs


def test_the_references_bands_cover_walls_and_jet_and_follow_the_seed():
    ref = files.load_module("references", "shallow-water-adjoint")
    bands = ref.bands(3600, 256, 2, 41, 7, 4)
    reach = 6 * 42
    assert bands[0] == (0, 256 + reach, 0, 256)
    assert bands[1] == (3600 - 256 - reach, 3600, 3600 - 256, 3600)
    assert bands[2] == (1672 - reach, 1928 + reach, 1672, 1928)
    assert all(edge % 2 == 0 for band in bands for edge in band)
    assert all(hi - lo == 256 for *_, lo, hi in bands)
    others = {ref.bands(3600, 256, 2, 41, seed, 4)[3] for seed in range(20)}
    assert len(others) > 10
    assert all(0 <= lo <= keep_lo < keep_hi <= hi <= 3600
               for lo, hi, keep_lo, keep_hi in others)


def test_a_bands_gradient_is_the_domains_on_the_rows_it_keeps():
    """The plain reference on a band of rows, widened by the window's
    reach, against the plain reference on the whole domain."""
    ref = files.load_module("references", "shallow-water-adjoint")
    model = files.load_json("configs", "shallow-water-adjoint")["model"]
    ny, nx, c, calls, per_call = 96, 16, 2, 1, 2
    params = ref.parameters(model, 2500.0, 2500.0)
    rng = np.random.default_rng(54)
    y = (np.arange(ny)[:, None] + 0.5) / ny
    h0 = jnp.asarray(100 + 0.2 * rng.normal(size=(ny, nx)), jnp.float32)
    u0 = jnp.asarray(10 * np.exp(-((y - 0.5) ** 2) / 0.02)
                     + 0.1 * rng.normal(size=(ny, nx)), jnp.float32)
    v0 = jnp.asarray(0.1 * rng.normal(size=(ny, nx)), jnp.float32)
    obs = jnp.asarray(100 + 0.2 * rng.normal(
        size=(calls + 1, ny // c, nx // c)), jnp.float32)
    whole = ref.gradient(h0, u0, v0, obs, params, calls, per_call, c)
    steps = 1 + calls * per_call
    for lo, hi, keep_lo, keep_hi in ref.bands(ny, 12, c, steps, 3, 4):
        assert (lo, hi) != (0, ny)  # a band, not the domain
        band = ref.gradient(
            h0[lo:hi], u0[lo:hi], v0[lo:hi], obs[:, lo // c:hi // c], params,
            calls, per_call, c, first_row=lo)
        for g, w in zip(band[1:], whole[1:]):
            np.testing.assert_allclose(
                np.asarray(g[keep_lo - lo:keep_hi - lo]),
                np.asarray(w[keep_lo:keep_hi]), rtol=2e-4, atol=1e-7)


# -- the per-layer readers on made-up traces ------------------------------

FORWARD = ('metadata={op_name="jit(local_fn)/sw/adjoint/forward/while/body/'
           'closed_call/mpi4jax_tpu.halo_slabs_2d/pack/slice" stack_frame_id=3}')
FORWARD_WALK = ('metadata={op_name="jit(local_fn)/sw/adjoint/forward/while/body/'
                'closed_call/wide_step" stack_frame_id=3}')
RECOMPUTE = ('metadata={op_name="jit(local_fn)/transpose(jvp(sw/adjoint/recompute))'
             '/while/body/wide_step" stack_frame_id=3}')
STEP_VJP = ('metadata={op_name="jit(local_fn)/transpose(jvp(sw/adjoint/recompute))'
            '/while/body/sw/adjoint/step_vjp/mul" stack_frame_id=3}')
ADJOINT_UNPACK = (
    'metadata={op_name="jit(local_fn)/transpose(jvp(sw/adjoint/recompute))/while/'
    'body/sw/adjoint/step_vjp/transpose(jvp(mpi4jax_tpu.halo_exchange_2d))/'
    'transpose/unpack/dynamic_update_slice" stack_frame_id=3}')
COST = 'metadata={op_name="jit(local_fn)/sw/adjoint/cost/reduce_sum" stack_frame_id=3}'
UPDATE = 'metadata={op_name="jit(local_fn)/sw/adjoint/update/sub" stack_frame_id=3}'

FORWARD_TEXT = f'''HloModule jit_local_fn, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.0 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  %slice.1 = f32[32,2]{{1,0}} slice(%p0), slice={{[0:32], [2:4]}}, {FORWARD}
  %reduce.5 = f32[] reduce(%p0), dimensions={{0,1}}, {COST}
  ROOT %kernel.7 = f32[32,64]{{1,0}} custom-call(%p0, %slice.1), custom_call_target="tpu_custom_call", {FORWARD_WALK}
}}
'''
BACKWARD_TEXT = f'''HloModule jit_backward, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.1 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  %slice.1 = f32[32,2]{{1,0}} slice(%p0), slice={{[0:32], [2:4]}}, {RECOMPUTE}
  %kernel.2 = f32[32,64]{{1,0}} custom-call(%p0, %slice.1), custom_call_target="tpu_custom_call", {RECOMPUTE}
  %fusion.3 = f32[32,64]{{1,0}} fusion(%kernel.2, %p0), kind=kLoop, calls=%fused.3, {STEP_VJP}
  %fusion.4 = f32[32,64]{{1,0}} fusion(%fusion.3, %slice.1), kind=kLoop, calls=%fused.4, {ADJOINT_UNPACK}
  ROOT %copy.6 = f32[32,64]{{1,0}} copy(%fusion.4)
}}
'''
UPDATE_TEXT = f'''HloModule jit_local_fn, entry_computation_layout={{(f32[32,64]{{1,0}})->f32[32,64]{{1,0}}}}

{TABLES}
ENTRY %main.2 (p0: f32[32,64]) -> f32[32,64] {{
  %p0 = f32[32,64]{{1,0}} parameter(0)
  ROOT %fusion.1 = f32[32,64]{{1,0}} fusion(%p0), kind=kLoop, calls=%fused.1, {UPDATE}
}}
'''
# nanoseconds of the made programs' events, in the texts' order
TIMES = {
    "forward": {"slice.1": 10, "reduce.5": 40, "kernel.7": 150},
    "backward": {"slice.1": 5, "kernel.2": 200, "fusion.3": 500, "fusion.4": 90,
                 "copy.6": 60},
    "update": {"fusion.1": 100},
}
BUSY = sum(ns for times in TIMES.values() for ns in times.values())


def _event_names(text):
    return {name: line.strip() for line, name in
            re.findall(r"^\s*(?:ROOT\s+)?(%([\w.\-]+) = .*)$", text, re.M)}


def _made(batches=2):
    """A session of three made-up programs and a trace of ``batches``
    batches of them on one chip."""
    driver = files.load_module("drivers", "shallow_water_adjoint")
    texts = {"forward": FORWARD_TEXT, "backward": BACKWARD_TEXT,
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
        rows={"gradient": {"reps": 1}}, window_steps=41,
        compiled_text=texts.__getitem__,
        units=lambda row: 41)
    session.traced_programs = lambda traced: driver.Session.traced_programs(
        session, traced)
    session.traced_events = lambda view: driver.Session.traced_events(session, view)
    traced = [run.Sample("gradient", 0.0, 1.0) for _ in range(batches)]
    facts = {"adjoint": {"gradients": 7, "window_steps": 41,
                         "trajectory_bytes": 14 * 100,
                         "costs": []}}
    return run.View(session, facts, [], traced, made, {}, {},
                    {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})


def _reader(name):
    return files.load_module("layer_metrics", name)


def test_the_scopes_of_a_backward_sweep_are_read_as_text():
    driver = files.load_module("drivers", "shallow_water_adjoint")
    name = lambda metadata: re.search(r'op_name="([^"]*)"', metadata)[1]  # noqa: E731
    assert driver.phase_of(name(FORWARD)) == "forward"
    assert driver.phase_of(name(RECOMPUTE)) == "recompute"
    assert driver.phase_of(name(STEP_VJP)) == "step_vjp"  # the innermost
    assert driver.phase_of(name(COST)) == "cost"
    assert driver.phase_of("jit(f)/mul") is None and driver.phase_of(None) is None
    assert driver.exchange_of(name(FORWARD)) == ("halo_slabs_2d", False, "pack")
    assert driver.exchange_of(name(ADJOINT_UNPACK)) == (
        "halo_exchange_2d", True, "unpack")
    assert driver.exchange_of(name(STEP_VJP)) is None
    assert driver.exchange_of(
        "jit(f)/mpi4jax_tpu.halo_exchange_2d/wire/mpi4jax_tpu.sendrecv/ppermute"
    ) == ("halo_exchange_2d", False, "wire")


def test_the_device_share_is_recompute_and_step_vjp_over_busy(capsys):
    view = _made()
    want = 100.0 * (5 + 200 + 500 + 90) / BUSY
    assert _reader("adjoint_device_share.sw").read(view) == pytest.approx(want)
    out = capsys.readouterr().out
    assert "sw/adjoint/forward" in out and "under no scope: copy %copy.6" in out
    assert "sw/adjoint/cost" in out


def test_the_exchange_share_splits_forward_from_transposed(capsys):
    view = _made()
    got = _reader("adjoint_exchange_device_share.sw").read(view)
    assert got == pytest.approx(100.0 * (10 + 90) / BUSY)
    out = capsys.readouterr().out
    assert "halo_exchange_2d | transposed | unpack" in out
    assert "halo_slabs_2d | forward | pack" in out


def test_the_roofline_share_counts_the_sweeps_bytes_over_its_time(capsys):
    view = _made()
    field, slab = 32 * 64 * 4, 32 * 2 * 4
    # the backward program's events under the sweep's scopes: the slice
    # and the two fusions twice their result, the kernel call its signature
    moved = 2 * slab + (2 * field + slab) + 2 * field + 2 * field
    seconds = (5 + 200 + 500 + 90) / 1e9
    want = 100.0 * moved / 819e9 / seconds
    assert _reader("adjoint_hbm_roofline_share").read(view) == pytest.approx(want)
    assert "1 kernel calls a step" not in capsys.readouterr().out  # 1/41 a step


def test_the_step_length_is_the_configurations_and_says_where_it_was_read():
    window = files.load_json("configs", "shallow-water-adjoint")["window"]
    # under 2 / L at the curvature the builder's chip run read, with room
    assert 0 < window["step_length"] * 11.15 <= 0.5
    why = window["step_length_why"]
    assert "11.15" in why and "PR 54" in why and "Descent.step_length" in why


def test_set_up_runs_the_warm_batch_and_no_gradient_more(copy, monkeypatch):
    """The step length is the configuration's: set-up searches for none."""
    from mpi4jax_tpu.models import shallow_water as sw

    def refused(*args, **kwargs):
        raise AssertionError("set-up asked for a step length")

    monkeypatch.setattr(sw.Descent, "step_length", refused)
    gradients = []
    iterate = sw.Descent.iterate
    monkeypatch.setattr(
        sw.Descent, "iterate",
        lambda self, n=1: (gradients.append(n), iterate(self, n))[1])
    root, bench = copy
    workload = files.load_json("workloads", TOY, bench)
    config = files.load_json("configs", workload["config"], bench)
    driver = files.load_module("drivers", config["driver"], bench)
    made = driver.setup(run.Context(config, workload, 54, jax.devices(), bench))
    assert made.rate == config["window"]["step_length"]
    assert gradients == [1] and made.fit.stats()["gradients"] == 1


def test_the_power_method_leaves_the_toys_window_its_room(session):
    """``Descent.step_length``, which the configuration's figure was
    read with: on the toy's window (21 steps) it finds a curvature the
    configuration's step length is well under 2 over."""
    rate, found, cost = session.fit.step_length(
        *session._fields(session.modes), session.obs, iterations=4)
    assert found == sorted(found) and rate == pytest.approx(0.5 / found[-1])
    assert session.rate * found[-1] < 1.0 and cost > 0


def test_the_memory_share_is_the_programs_peak_over_the_chips(capsys):
    view = _made()
    analysis = types.SimpleNamespace(
        peak_memory_in_bytes=9_600_000_000, temp_size_in_bytes=9_000_000_000,
        argument_size_in_bytes=400_000_000, output_size_in_bytes=300_000_000)
    small = types.SimpleNamespace(
        peak_memory_in_bytes=3_000_000_000, temp_size_in_bytes=100_000_000,
        argument_size_in_bytes=400_000_000, output_size_in_bytes=2_600_000_000)
    view.session.compiled = lambda key: types.SimpleNamespace(
        memory_analysis=lambda: {"forward": small, "backward": analysis}[key])
    # the fuller of the two
    assert _reader("adjoint_memory_share").read(view) == pytest.approx(60.0)
    assert "hold 1400 bytes by shapes" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name):
    """A session of another family (the parent's programs: no gradient,
    no counters) reads ``None`` and does not raise."""
    bare = types.SimpleNamespace(
        ctx=types.SimpleNamespace(bench_dir=files.BENCH_DIR),
        rows={"multistep": {"reps": 1}}, units=lambda row: 10)
    view = run.View(bare, {"steps_per_call": 10}, [], [], Trace(), {}, {},
                    {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})
    assert _reader(name).read(view) is None


def test_a_traced_toy_run_prints_every_listed_metric_or_a_reason(copy, session, capsys):
    """The readers on the toy session's real programs (the CPU's: array
    code, so no kernel call) and a trace made of their own instructions."""
    keys = ("forward", "backward", "update")
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
    view = run.View(session, session.facts(), [], [run.Sample("gradient", 0.0, 1.0)],
                    made, {}, {}, {"hbm_gbps": 819.0, "hbm_bytes": 16_000_000_000}, {})
    values = {name: _reader(name).read(view) for name in NEW_READERS}
    out = capsys.readouterr().out
    assert 0 < values["adjoint_device_share.sw"] <= 100
    assert values["adjoint_exchange_device_share.sw"] > 0
    assert "transposed" in out and "sw/adjoint/step_vjp" in out
    assert values["adjoint_hbm_roofline_share"] > 0
    # the CPU's compiled program has a peak or says why not
    assert values["adjoint_memory_share"] or "gives no peak" in out
