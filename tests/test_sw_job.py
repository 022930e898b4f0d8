"""The solver as a job that writes output (``models.shallow_water``
``make_job``): snapshots against the benchmark's plain reference, the
same on every mesh and schedule, donation on with output, delivery in
order and at most ``lag`` late, and ``make_solver`` as a loop over it."""

import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.parallel.halo import halo_exchange_2d
from perfbench.harness import files

NY, NX, CALLS, STEPS_A_CALL, COARSEN = 32, 64, 4, 10, 4
STEPS = [1 + STEPS_A_CALL * (k + 1) for k in range(CALLS)]  # 11, 21, 31, 41
FIELDS = ("h", "u", "v")
# as perfbench/configs/shallow-water.json: the solver against the plain
# reference after 41 steps (a mean of 16 cells differs by no more)
LIMITS = {"h": 5e-4, "u": 1e-4, "v": 1e-4}


def _comm(shape):
    mesh = jax.make_mesh(
        shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:shape[0] * shape[1]])
    return m.MeshComm.from_mesh(mesh)


@pytest.fixture(scope="module")
def reference():
    return files.load_module("references", "shallow-water-job")


@pytest.fixture(scope="module")
def seeded():
    """Seeded interior fields ``(h0, u0, v0)``, as the benchmark makes them."""
    plain = files.load_module("drivers", "shallow_water")
    config = files.load_json("configs", "shallow-water-job")
    modes = plain.mode_table(2**31 + 11, config["assumed"]["perturbation"])
    cfg = sw.SWConfig(ny=NY, nx=NX)
    return tuple(np.asarray(a) for a in plain.make_fields(
        config["model"], NY, NX, cfg.dx, cfg.dy)(modes))


def _state(cfg, comm, fields):
    """The job's state at step 0 from interior fields: each device's
    block with its ghost ring (walls edge-padded, the rest exchanged),
    no tendencies yet."""
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


def _interior(field, ghost, mesh_shape):
    arr = np.asarray(field)
    py, px = mesh_shape
    ly, lx = arr.shape[0] // py, arr.shape[1] // px
    g = ghost
    blocks = arr.reshape(py, ly, px, lx)[:, g:ly - g, :, g:lx - g]
    return blocks.reshape(py * (ly - 2 * g), px * (lx - 2 * g))


def _run(cfg, comm, fields, snapshot, calls=CALLS, on_chunk=None):
    got = []
    job = sw.make_job(
        cfg, comm, STEPS_A_CALL, snapshot,
        on_chunk or (snapshot and (lambda s, step: got.append((step, s)))))
    job.start(_state(cfg, comm, fields))
    job.advance(calls)
    job.drain()
    return job, got


@pytest.fixture(scope="module")
def on_one_device(seeded):
    """The snapshots of each schedule on a 1x1 mesh, made once."""
    made = {}

    def snapshots(ghost):
        if ghost not in made:
            cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
            made[ghost] = _run(
                cfg, _comm((1, 1)), seeded, sw.Snapshot(coarsen=COARSEN))[1]
        return made[ghost]

    return snapshots


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_snapshots_against_the_plain_reference(
        reference, seeded, on_one_device, ghost, mesh_shape):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    job, got = _run(cfg, _comm(mesh_shape), seeded, sw.Snapshot(coarsen=COARSEN))
    assert [step for step, _ in got] == STEPS and job.step == STEPS[-1]
    config = files.load_json("configs", "shallow-water-job")
    params = reference.parameters(config["model"], cfg.dx, cfg.dy)
    want = reference.run_block_means(
        *seeded, params, STEPS, COARSEN, (0, NY))
    for (_, snapshot), means, (_, single), (_, wide) in zip(
            got, want, on_one_device(ghost), on_one_device(2)):
        assert tuple(snapshot) == FIELDS
        for k, mean in zip(FIELDS, means):
            assert snapshot[k].shape == (NY // COARSEN, NX // COARSEN)
            assert snapshot[k].dtype == np.float32
            assert np.abs(snapshot[k] - np.asarray(mean)).max() <= LIMITS[k]
            # a mesh writes what one device writes, and a schedule what
            # another does, to the rounding of its own order of operations
            assert np.abs(snapshot[k] - single[k]).max() <= 1e-4
            assert np.abs(snapshot[k] - wide[k]).max() <= 1e-4
    # the last snapshot is the block mean of the state the job returns
    for k in FIELDS:
        whole = _interior(getattr(job.state, k), ghost, mesh_shape)
        assert np.abs(got[-1][1][k] - reference.block_mean(whole, COARSEN)
                      ).max() <= 3e-5


@pytest.mark.parametrize("ghost", [1, 2])
@pytest.mark.parametrize("coarsen", [1, 2, 8])
def test_coarsen_one_is_the_field_and_any_divisor_its_block_mean(
        reference, seeded, ghost, coarsen):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    job, got = _run(cfg, _comm((2, 2)), seeded,
                    sw.Snapshot(fields=("h", "v"), coarsen=coarsen), calls=1)
    (step, snapshot), = got
    assert step == 11 and tuple(snapshot) == ("h", "v")
    for k in snapshot:
        whole = _interior(getattr(job.state, k), ghost, (2, 2))
        want = reference.block_mean(whole, coarsen)
        # a sum of c x c float32 terms of 100 m, against the same in float64
        assert np.abs(snapshot[k] - want).max() <= (0 if coarsen == 1 else 1e-4)


def test_a_coarsening_that_does_not_divide_a_block_is_an_error():
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    with pytest.raises(ValueError, match="does not divide"):
        sw.make_job(cfg, _comm((2, 2)), 10, sw.Snapshot(coarsen=3))
    with pytest.raises(ValueError, match="does not divide"):
        sw.make_job(cfg, _comm((1, 1)), 10, sw.Snapshot(coarsen=0))
    # 32 rows divide by 32; over two devices a block has 16
    sw.make_job(cfg, _comm((1, 1)), 10, sw.Snapshot(coarsen=32))
    with pytest.raises(ValueError, match="16x32"):
        sw.make_job(cfg, _comm((2, 2)), 10, sw.Snapshot(coarsen=32))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_with_output_returns_the_state_of_a_job_without(
        seeded, mesh_shape):
    """Donation stays on with output, and output reads the state only."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    loud, got = _run(cfg, comm, seeded, sw.Snapshot(coarsen=COARSEN))
    quiet, _ = _run(cfg, comm, seeded, None)
    assert len(got) == CALLS and quiet.stats()["snapshots_produced"] == 0
    for a, b in zip(loud.state, quiet.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # both are the program a user builds by hand
    by_hand = sw.make_first_step(cfg, comm)(_state(cfg, comm, seeded))
    multi = sw.make_multistep(cfg, comm, STEPS_A_CALL)
    for _ in range(CALLS):
        by_hand = multi(by_hand)
    for a, b in zip(loud.state, by_hand):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_every_call_consumes_its_input_but_the_one_asked_to_keep_it(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    job, _ = _run(cfg, _comm((1, 1)), seeded, sw.Snapshot(coarsen=COARSEN),
                  calls=1)
    before = job.state
    held = [np.asarray(a).copy() for a in before]
    job.advance(keep_input=True)
    for a, b in zip(before, held):  # somebody's asynchronous save reads on
        np.testing.assert_array_equal(np.asarray(a), b)
    taken = job.state
    job.advance()
    job.drain()
    # output or not, the call took every array of its input
    assert all(a.is_deleted() for a in taken)


def test_order_count_and_lag_hold_under_a_slow_callback(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    seen = []

    def slow(snapshot, step):
        time.sleep(0.02)
        seen.append((step, job.stats()["snapshots_produced"]))

    job = sw.make_job(cfg, _comm((2, 2)), STEPS_A_CALL,
                      sw.Snapshot(coarsen=COARSEN, lag=2), slow)
    job.start(_state(cfg, job.comm, seeded))
    job.advance(3)
    assert seen == [(11, 3)]  # the first became due when the third was made
    job.advance(4)
    assert len(job._pending) == 2  # never more than `lag` wait for a later call
    assert job._asked == 2  # unbounded: asked for as they were produced
    assert [s for s, _ in seen] == [11 + 10 * k for k in range(5)]
    job.drain()
    assert [s for s, _ in seen] == [11 + 10 * k for k in range(7)]
    stats = job.stats()
    assert stats["snapshots_produced"] == stats["snapshots_delivered"] == 7
    assert stats["max_lag"] == 2
    assert stats["bytes_to_host"] == 7 * 3 * (NY // COARSEN) * (NX // COARSEN) * 4
    assert stats["callback_s"] >= 7 * 0.02 and stats["output_wait_s"] >= 0
    # each was handed over no more than `lag` behind the newest
    assert all(made - (step - 1) // 10 <= 2 for step, made in seen)


@pytest.mark.parametrize("ahead, at_once", [(None, 7), (2, 2), (2.5, 2), (0.5, 1)])
def test_copies_to_the_host_are_held_under_ahead_bytes(
        seeded, monkeypatch, ahead, at_once):
    """However many snapshots wait, the copies asked for and not yet
    fetched stay under ``ahead_bytes`` (a host's staging buffer is
    finite), but for the oldest's, which is always asked for; they are
    asked for oldest first and every snapshot still arrives, in order.
    Without a bound each is asked for as it is produced, ``lag + 1`` at
    most."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    one = (NY // COARSEN) * (NX // COARSEN) * 4
    seen, asked = [], []
    job = sw.make_job(
        cfg, _comm((1, 1)), STEPS_A_CALL,
        sw.Snapshot(fields=("h",), coarsen=COARSEN, lag=6,
                    ahead_bytes=ahead and int(ahead * one)),
        lambda s, step: seen.append(step))
    ask = sw.SolverJob._ask

    def counting(self):
        before = self._asked
        ask(self)
        asked.extend(step for step, _ in list(self._pending)[before:self._asked])
        assert self._asked <= at_once and self._asked_bytes == self._asked * one

    monkeypatch.setattr(sw.SolverJob, "_ask", counting)
    job.start(_state(cfg, job.comm, seeded))
    job.advance(6)
    assert len(job._pending) == 6 and job._asked == min(at_once, 6) and not seen
    assert asked == [11 + 10 * k for k in range(min(at_once, 6))]
    job.advance(2)
    assert seen == [11, 21]
    assert asked == [11 + 10 * k for k in range(min(at_once + 2, 8))]
    job.drain()
    assert seen == asked == [11 + 10 * k for k in range(8)]
    assert job._asked == job._asked_bytes == 0


def test_a_snapshot_taken_a_call_late_fails_the_comparison(seeded, reference):
    """The comparison the benchmark's cell makes sees a stale snapshot:
    ten steps move the fields by far more than a block mean's rounding."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    job, got = _run(cfg, _comm((1, 1)), seeded, sw.Snapshot(coarsen=COARSEN))
    on_time, stale = got[-1][1], got[-2][1]
    for k in ("h", "u"):
        want = reference.block_mean(
            _interior(getattr(job.state, k), 2, (1, 1)), COARSEN)
        assert np.abs(on_time[k] - want).max() <= 3e-5
        assert np.abs(stale[k] - want).max() > 30 * 3e-5


def test_on_chunk_alone_asks_for_whole_fields(seeded):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=1)
    got = []
    job, _ = _run(cfg, _comm((1, 1)), seeded, None, calls=2,
                  on_chunk=lambda s, step: got.append((step, s)))
    assert job.snapshot == sw.Snapshot() and [s for s, _ in got] == [11, 21]
    np.testing.assert_array_equal(
        got[-1][1]["u"], _interior(job.state.u, 1, (1, 1)))


def test_make_solver_with_output_and_checkpoints_resumes(comm2d, tmp_path):
    """``make_solver`` is a loop over the job: with ``on_chunk`` and
    ``checkpoint_dir`` an interrupted run resumed ends on the
    uninterrupted run's state, and between them the two runs hand out
    every chunk's snapshot once, in step order."""
    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    n = 5
    t_half = cfg.dt * (1 + n) + cfg.dt * n * 2
    t_full = t_half + cfg.dt * n * 2
    seen = []

    def solver(**kw):
        return sw.make_solver(
            cfg, comm2d, num_multisteps=n, snapshot=sw.Snapshot(coarsen=2, lag=1),
            on_chunk=lambda s, step: seen.append((step, s["h"].copy())), **kw)

    ck = tmp_path / "run"
    solver(checkpoint_dir=ck)(t_half)
    first = [step for step, _ in seen]
    assert first == [1 + n, 1 + 2 * n, 1 + 3 * n]
    state_b, _, steps_b = solver(checkpoint_dir=ck)(t_full)
    assert steps_b == 2 * n
    assert [step for step, _ in seen] == first + [1 + 4 * n, 1 + 5 * n]
    resumed = list(seen)
    del seen[:]
    state_c, _, _ = solver()(t_full)
    for b, c in zip(state_b, state_c):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
    assert [step for step, _ in seen] == [step for step, _ in resumed]
    for (_, b), (_, c) in zip(resumed, seen):
        np.testing.assert_array_equal(b, c)
    assert seen[-1][1].shape == (8, 16)


def test_the_example_animates_through_the_job(tmp_path):
    pytest.importorskip("matplotlib")
    import importlib.util

    path = pathlib.Path(__file__).resolve().parents[1] / "examples/shallow_water.py"
    spec = importlib.util.spec_from_file_location("sw_example", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    out = tmp_path / "frames.gif"
    example.main(["--check", "--force-cpu", "--mesh", "2", "2", "--multistep", "5",
                  "--animate", str(out), "--coarsen", "2"])
    assert out.stat().st_size > 0
