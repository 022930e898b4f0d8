"""The solver as a job that watches itself (``models.shallow_water``
``make_job(monitor=)``): every line against the benchmark's plain
reference on 1x1 and 2x2 CPU meshes, the chips' local parts adding up to
the uncut domain's line with no ghost cell counted, the stop within
``lag + 1`` calls, one line a call in order and across a save and a
resume, beside output and saves, and the bfloat16 control refused."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.parallel.halo import halo_exchange_2d
from perfbench.harness import files

NY, NX, CALLS, STEPS_A_CALL = 32, 64, 4, 10
STEPS = [1 + STEPS_A_CALL * (k + 1) for k in range(CALLS)]  # 11, 21, 31, 41
MESHES = [(1, 1), (2, 2)]
NUMBERS = ("nonfinite", "cfl", "h_min", "mass")


def _comm(shape):
    mesh = jax.make_mesh(
        shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:shape[0] * shape[1]])
    return m.MeshComm.from_mesh(mesh)


@pytest.fixture(scope="module")
def config():
    return files.load_json("configs", "shallow-water-monitored")


@pytest.fixture(scope="module")
def reference():
    return files.load_module("references", "shallow-water-monitored")


@pytest.fixture(scope="module")
def seeded(config):
    """Seeded interior fields ``(h0, u0, v0)``, as the benchmark makes them."""
    plain = files.load_module("drivers", "shallow_water")
    modes = plain.mode_table(2**31 + 51, config["assumed"]["perturbation"])
    cfg = sw.SWConfig(ny=NY, nx=NX)
    return tuple(np.asarray(a) for a in plain.make_fields(
        config["model"], NY, NX, cfg.dx, cfg.dy)(modes))


@pytest.fixture(scope="module")
def wanted(config, reference, seeded):
    """The plain reference's line at each of ``STEPS``, from one walk of
    the uncut domain, and its final fields."""
    cfg = sw.SWConfig(ny=NY, nx=NX)
    params = reference.parameters(config["model"], cfg.dx, cfg.dy)
    parts, fields = reference.run_lines(*seeded, params, STEPS, (0, NY))
    return params, [reference.line_of([p], params) for p in parts], fields


def _state(cfg, comm, fields):
    """The job's state at step 0 from interior fields: each device's
    block with its ghost ring, no tendencies yet."""
    G = cfg.ghost
    spec = jax.P(*comm.axes)

    def local(*blocks):
        padded = tuple(
            halo_exchange_2d(jnp.pad(a, G, mode="edge"), comm,
                             periodic=(False, True), width=G)[0]
            for a in blocks)
        return padded + tuple(jnp.zeros_like(a) for a in padded)

    return sw.SWState(*jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 6))(
            *fields))


def _job(cfg, comm, lines, monitor=sw.Monitor(), **more):
    return sw.make_job(cfg, comm, STEPS_A_CALL, monitor=monitor,
                       on_monitor=lines.append, **more)


def _run(cfg, comm, fields, calls=CALLS, monitor=sw.Monitor(), **more):
    lines = []
    job = _job(cfg, comm, lines, monitor, **more)
    job.start(_state(cfg, comm, fields))
    job.advance(calls)
    job.drain()
    return job, lines


def _differences(mine, want):
    """Each of a line's numbers beside the reference's, ``mass`` as a
    share: the names ``check.line_limits`` has."""
    return {"nonfinite": abs(mine["nonfinite"] - want["nonfinite"]),
            "cfl": abs(mine["cfl"] - want["cfl"]),
            "h_min": abs(mine["h_min"] - want["h_min"]),
            "mass_relative": abs(mine["mass"] - want["mass"]) / want["mass"]}


@pytest.mark.parametrize("ghost", [1, 2, 4])
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_every_line_against_the_plain_reference(
        config, seeded, wanted, mesh_shape, ghost):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    job, lines = _run(cfg, _comm(mesh_shape), seeded)
    assert [line["step"] for line in lines] == STEPS and job.step == STEPS[-1]
    assert all(set(line) == {"step", *NUMBERS} for line in lines)
    limits = config["check"]["line_limits"]
    for mine, want in zip(lines, wanted[1]):
        found = _differences(mine, want)
        assert all(found[k] <= limits[k] for k in limits), (mine, want, found)
    stats = job.stats()
    assert stats["monitor_lines"] == CALLS and stats["monitor_stops"] == 0
    # four calls and a drain: the first line was read three calls late
    assert stats["monitor_max_lag_calls"] == CALLS - 1 < sw.Monitor().lag
    assert job.stopped is None


def test_the_reference_in_numpy_and_its_walk_in_bands_agree(
        config, reference, seeded, wanted):
    """``monitor`` (numpy, float64 sums) of the walked fields is
    ``line_of`` the walk's own parts, uncut and cut into bands of rows
    that each keep their own; the mass to a float32 row sum's rounding."""
    params, lines, fields = wanted
    whole = reference.monitor(*fields, params)
    for k in ("nonfinite", "cfl", "h_min"):
        assert whole[k] == lines[-1][k]
    assert abs(whole["mass"] - lines[-1]["mass"]) <= 1e-7 * whole["mass"]
    banded = [[] for _ in STEPS]
    for lo, hi, keep_lo, keep_hi in reference.row_blocks(NY, 2, STEPS[-1]):
        parts, kept = reference.run_lines(
            *(a[lo:hi] for a in seeded), params, STEPS,
            (keep_lo - lo, keep_hi - lo), first_row=lo)
        for into, part in zip(banded, parts):
            into.append(part)
        np.testing.assert_allclose(kept[0], fields[0][keep_lo:keep_hi], atol=1e-5)
    limits = config["check"]["line_limits"]
    for parts, want in zip(banded, lines):
        found = _differences(reference.line_of(parts, params), want)
        assert all(found[k] <= limits[k] for k in limits), found


@pytest.mark.parametrize("ghost", [1, 2])
def test_the_chips_parts_add_up_to_the_uncut_domains_line(
        config, reference, seeded, wanted, ghost):
    """The share test: each of the four chips' own reductions (the
    monitor program on that chip's block alone, where the ``allreduce``
    is the identity) add up (``mass``, ``nonfinite``) and reduce
    (``cfl``, ``h_min``) to the plain reference's line of the whole
    domain, and to the line the mesh's own program hands every chip."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    comm = _comm((2, 2))
    job, lines = _run(cfg, comm, seeded)
    one = _comm((1, 1))
    local = sw.make_monitor(sw.SWConfig(ny=NY // 2, nx=NX // 2, ghost=ghost), one)
    on_first = jax.devices()[0]
    parts = []
    for shards in zip(*(a.addressable_shards for a in job.state[:3])):
        block = [jax.device_put(s.data, on_first) for s in shards]
        assert block[0].shape == (NY // 2 + 2 * ghost, NX // 2 + 2 * ghost)
        parts.append(dict(zip(NUMBERS, np.asarray(local(*block)).ravel().tolist())))
    assert len(parts) == 4
    together = {"nonfinite": sum(p["nonfinite"] for p in parts),
                "cfl": max(p["cfl"] for p in parts),
                "h_min": min(p["h_min"] for p in parts),
                "mass": sum(p["mass"] for p in parts)}
    want = wanted[1][-1]
    limits = config["check"]["line_limits"]
    found = _differences(together, want)
    assert all(found[k] <= limits[k] for k in limits), (together, want)
    # no chip holds all of it, and what the mesh hands out is the whole
    assert max(p["mass"] for p in parts) < 0.3 * want["mass"]
    assert together["cfl"] == lines[-1]["cfl"] and together["h_min"] == lines[-1]["h_min"]
    assert abs(together["mass"] - lines[-1]["mass"]) <= 1e-6 * want["mass"]
    every = np.asarray(job.mon(*job.state[:3])).reshape(4, 4)
    assert (every == every[0]).all()


@pytest.mark.parametrize("ghost", [1, 2, 4])
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_a_ghost_cell_is_counted_nowhere(seeded, mesh_shape, ghost):
    """Every ghost cell of every chip's block overwritten, with NaN in
    ``h``, a gale in ``u`` and a dry layer in ``v``'s place: the line is
    the same line, bit for bit."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    comm = _comm(mesh_shape)
    job, _ = _run(cfg, comm, seeded, calls=1)
    spec = jax.P(*comm.axes)
    G = ghost

    def poison(h, u, v):
        inner = (slice(G, -G), slice(G, -G))
        return tuple(jnp.full_like(a, bad).at[inner].set(a[inner])
                     for a, bad in ((h, jnp.nan), (u, 1e6), (v, -1e6)))

    poisoned = jax.jit(jax.shard_map(
        poison, mesh=comm.mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3))(
            *job.state[:3])
    assert np.isnan(np.asarray(poisoned[0])).sum() > 0
    np.testing.assert_array_equal(
        np.asarray(job.mon(*poisoned)), np.asarray(job.mon(*job.state[:3])))


@pytest.mark.parametrize("lag", [0, 2, 4])
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_a_nan_in_one_chips_block_stops_the_job_within_lag_plus_one_calls(
        seeded, mesh_shape, lag):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    lines = []
    job = _job(cfg, comm, lines, sw.Monitor(lag=lag))
    job.start(_state(cfg, comm, seeded))
    job.advance(2)
    h = job.state.h  # the last chip's block, three cells inside its corner
    job.state = job.state._replace(
        h=h.at[h.shape[0] - 5, h.shape[1] - 5].set(jnp.nan))
    bad_from, before = job.step + STEPS_A_CALL, job.calls
    with pytest.raises(sw.MonitorStop, match=f"step {bad_from} stops the job") as stop:
        for _ in range(lag + 2):
            job.advance()
    assert job.calls - before == lag + 1  # the bad call and `lag` more
    line = stop.value.line
    assert line is job.stopped and line["step"] == bad_from
    assert line["nonfinite"] > 0 and lines[-1] is line
    assert job.stats()["monitor_stops"] == 1
    # stopped stays stopped: no later call is enqueued
    with pytest.raises(sw.MonitorStop):
        job.advance()
    assert job.calls - before == lag + 1
    # the lines of the calls enqueued before the stop are still handed out
    job.drain()
    assert [l["step"] for l in lines] == [
        11 + STEPS_A_CALL * k for k in range(job.calls)]
    assert job.stats()["monitor_stops"] == 1
    # a state given anew starts a job anew
    job.start(_state(cfg, comm, seeded))
    job.advance()
    job.drain()
    assert job.stopped is None and lines[-1]["nonfinite"] == 0


@pytest.mark.parametrize("line, why", [
    ({"nonfinite": 3, "cfl": 0.04, "h_min": 35.0}, "3 values"),
    ({"nonfinite": 0, "cfl": 0.04, "h_min": 0.0}, "thinnest layer"),
    ({"nonfinite": 0, "cfl": 0.04, "h_min": -2.5}, "thinnest layer"),
    ({"nonfinite": 0, "cfl": 0.51, "h_min": 35.0}, "CFL number"),
    ({"nonfinite": 0, "cfl": float("nan"), "h_min": 35.0}, "CFL number"),
    ({"nonfinite": 0, "cfl": 0.5, "h_min": 1e-3}, None),
])
def test_what_stops_a_job(line, why):
    found = sw.Monitor(cfl_limit=0.5).why_bad(line)
    assert (found is None) if why is None else (why in found)


def test_a_cfl_number_over_the_limit_stops_a_sound_run(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    lines = []
    job = _job(cfg, _comm((2, 2)), lines, sw.Monitor(lag=1, cfl_limit=0.01))
    job.start(_state(cfg, _comm((2, 2)), seeded))
    with pytest.raises(sw.MonitorStop, match="CFL number .* is over 0.01"):
        job.advance(4)
    assert job.calls == 2 and lines[0]["step"] == 11 and len(lines) == 1


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_one_line_a_call_in_order_across_a_save_and_a_resume(
        seeded, tmp_path, mesh_shape):
    """Beside output and saves: a job with all three is dropped after
    its fifth call, that call's line still on its way (a save reads
    every line made so far first: the fourth call's save has read
    four); a new job resumed from the directory has no line pending, its first is of the resumed step
    plus one call, and from there on its lines are the uninterrupted
    job's, number for number."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    _, whole = _run(cfg, comm, seeded, calls=7)
    assert [line["step"] for line in whole] == [11 + 10 * k for k in range(7)]

    def with_all(lines, chunks):
        return _job(cfg, comm, lines, snapshot=sw.Snapshot(coarsen=2),
                    on_chunk=lambda s, step: chunks.append(step),
                    checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=2))

    before, chunks = [], []
    killed = with_all(before, chunks)
    killed.start(_state(cfg, comm, seeded))
    killed.advance(5)
    killed._settle()  # the save of step 41 acknowledged
    assert [line["step"] for line in before] == STEPS and len(killed._lines) == 1
    del killed
    after, chunks = [], []
    resumed = with_all(after, chunks)
    assert resumed.resume() == 41 and not resumed._lines
    resumed.advance(3)
    resumed.drain()
    assert chunks == [51, 61, 71]
    assert after == whole[4:]
    assert resumed.stats()["monitor_lines"] == 3


def test_every_calls_spaces_the_lines(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    job, lines = _run(cfg, _comm((1, 1)), seeded, calls=5,
                      monitor=sw.Monitor(every_calls=2, lag=1))
    assert [line["step"] for line in lines] == [21, 41]
    assert job.stats()["monitor_max_lag_calls"] == 1


def test_on_monitor_without_a_monitor_is_refused():
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    with pytest.raises(ValueError, match="`on_monitor` without a `monitor`"):
        sw.make_job(cfg, _comm((1, 1)), STEPS_A_CALL, on_monitor=print)
    assert sw.make_job(cfg, _comm((1, 1)), STEPS_A_CALL).mon is None


def _poisoned(job):
    """``job`` with a NaN in its last chip's block; the step whose line
    will say so."""
    h = job.state.h
    job.state = job.state._replace(
        h=h.at[h.shape[0] - 5, h.shape[1] - 5].set(jnp.nan))
    return job.step + STEPS_A_CALL


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_no_save_is_started_of_a_state_the_monitor_calls_bad(
        seeded, tmp_path, mesh_shape):
    """A save reads every line made so far first: with a save after
    every call the bad call's own save is refused, however long the
    ``lag``, the directory's newest save is of the step before, and a
    new job resumes from a state whose line stops nothing."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)

    def saving(lines):
        return _job(cfg, comm, lines, sw.Monitor(lag=4),
                    checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=1))

    lines = []
    job = saving(lines)
    job.start(_state(cfg, comm, seeded))
    job.advance(2)
    bad_from = _poisoned(job)
    with pytest.raises(sw.MonitorStop, match=f"step {bad_from} stops the job"):
        job.advance(3)
    assert job.step == bad_from and job.stats()["saves_started"] == 2
    with pytest.raises(sw.MonitorStop):
        job.save()  # a stopped job saves nothing
    job.drain()
    assert job.series.steps() == [11, 21] and job.series.leftovers() == []
    after = []
    resumed = saving(after)
    assert resumed.resume() == 21 < bad_from
    resumed.advance(2)
    resumed.drain()
    assert [line["nonfinite"] for line in after] == [0, 0]
    assert [line["step"] for line in after] == [31, 41]


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_a_stop_first_met_in_drain_leaves_nothing_half_done(
        seeded, tmp_path, mesh_shape):
    """``drain`` reads the lines last: where one stops the job the save
    on its way has been acknowledged and the spare files are gone; and
    ``start`` hands out what the run before left, counts its stop, and
    takes the new state all the same."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    lines = []
    job = _job(cfg, comm, lines, sw.Monitor(lag=4),
               checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=0))
    job.start(_state(cfg, comm, seeded))
    job.advance()
    job.save()
    bad_from = _poisoned(job)
    job.advance()  # its line is four calls from being read
    assert [line["step"] for line in lines] == [11]
    with pytest.raises(sw.MonitorStop, match=f"step {bad_from} stops the job"):
        job.drain()
    assert job._save is None and job.series.steps() == [11]
    assert job.series.leftovers() == [] and job.stats()["saves_acknowledged"] == 1
    # the same run met by `start`: nothing is raised, the stop is counted
    job.start(_state(cfg, comm, seeded))
    job.advance()
    _poisoned(job)
    job.advance(2)
    job.start(_state(cfg, comm, seeded))
    assert job.stopped is None and job.stats()["monitor_stops"] == 2
    assert [line["step"] for line in lines] == [11, 21, 11, 21, 31]
    assert [line["nonfinite"] > 0 for line in lines] == [
        False, True, False, True, True]
    job.advance()
    job.drain()
    assert lines[-1]["step"] == 11 and lines[-1]["nonfinite"] == 0


def test_the_monitors_times_are_the_sums_of_its_spans(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    job, lines = _run(cfg, _comm((2, 2)), seeded)
    spans = job.spans()
    waits = [s for s in spans if s.name == "job/monitor_wait"]
    assert [s.key for s in waits] == STEPS
    assert sum(s.seconds for s in waits) == pytest.approx(
        job.stats()["monitor_wait_s"])
    assert [s.key for s in spans if s.name == "job/monitor_callback"] == STEPS
    enqueued = [s for s in spans
                if s.name == "job/enqueue" and s.counts["program"] == "mon"]
    assert [s.key for s in enqueued] == STEPS


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_a_job_without_a_monitor_is_what_it_was_and_one_with_steps_the_same(
        seeded, mesh_shape):
    """The monitor is a program beside the step: the multistep of a job
    with one is, text for text, that of a job without, and the states
    after four calls are bit for bit the same."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    watched, _ = _run(cfg, comm, seeded)
    bare = sw.make_job(cfg, comm, STEPS_A_CALL)
    bare.start(_state(cfg, comm, seeded))
    bare.advance(CALLS)
    assert bare.mon is None and "monitor_lines" in bare.stats()
    assert bare.stats()["monitor_lines"] == 0
    state = bare.state
    assert watched.multi.lower(state).as_text() == bare.multi.lower(state).as_text()
    for a, b in zip(watched.state, bare.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the monitor program's own text: its scope, and the op's inside it
    text = watched.mon.lower(*state[:3]).as_text(debug_info=True)
    assert "sw/monitor" in text and "sw/monitor/mpi4jax_tpu.allreduce" in text
    assert text.count("stablehlo.all_reduce") == (
        3 if mesh_shape != (1, 1) else text.count("stablehlo.all_reduce"))


def test_the_reference_in_bfloat16_is_refused(config, reference, seeded, wanted):
    """The control: the plain reference carried in bfloat16 in the
    program's place fails the fields' limits and at least one of the
    line's."""
    params, lines, fields = wanted
    parts, low = reference.run_lines(*seeded, params, STEPS, (0, NY), "bfloat16")
    limits = config["check"]
    assert any(float(np.abs(np.asarray(a) - np.asarray(b)).max()) > limits["limits"][k]
               for k, a, b in zip("huv", low, fields))
    refused = set()
    for part, want in zip(parts, lines):
        found = _differences(reference.line_of([part], params), want)
        refused |= {k for k in found if found[k] > limits["line_limits"][k]}
    assert refused >= {"cfl", "h_min"}, refused


def test_the_example_prints_its_lines_and_stops_on_a_bad_one(capsys, monkeypatch):
    path = pathlib.Path(__file__).resolve().parents[1] / "examples/shallow_water.py"
    spec = importlib.util.spec_from_file_location("sw_example_monitor", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    example.main(["--check", "--force-cpu", "--mesh", "2", "2", "--multistep", "5",
                  "--monitor"])
    printed = [l for l in capsys.readouterr().err.splitlines()
               if l.startswith("monitor: step")]
    assert len(printed) >= 2 and "0 values not finite" in printed[0]
    # a limit the sound run is over: the example ends with the stop's words
    real = sw.Monitor
    monkeypatch.setattr(sw, "Monitor", lambda lag=4: real(lag=lag, cfl_limit=1e-3))
    with pytest.raises(SystemExit, match="stops the job: the CFL number"):
        example.main(["--check", "--force-cpu", "--mesh", "2", "2",
                      "--multistep", "5", "--monitor"])
