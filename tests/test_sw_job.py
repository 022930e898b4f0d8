"""The solver as a job that writes output (``models.shallow_water``
``make_job``): snapshots against the benchmark's plain reference, the
same on every mesh and schedule, donation on with output, delivery in
order and at most ``lag`` late, and ``make_solver`` as a loop over it."""

import collections
import contextlib
import pathlib
import re
import time
from dataclasses import replace

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


def _through_the_kernel(monkeypatch):
    """The ``ghost`` 2 step as it runs on TPU devices, here: the kernel
    in Pallas's interpret mode, as ``tests/test_sw_kernels.py`` forces
    it."""
    import functools

    from mpi4jax_tpu.models import sw_kernels

    wide_step = sw_kernels.wide_step
    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *a, **kw: wide_step(*a, **dict(kw, interpret=True)))
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: cfg.ghost == 2)
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_where_the_step_is_the_kernel_a_snapshot_is_finished_from_its_row_sums(
        reference, seeded, on_one_device, mesh_shape, monkeypatch, tmp_path):
    """Where the step is the kernel and the blocks are made of a strip's
    rows, a call takes the room for the row sums of ``h``, ``u``, ``v``
    beside the state, donates both, and returns them written, and
    ``snap`` is handed those sums: the snapshots are the array
    code's to roundoff and the block means of the state they name, the
    state is a job without output's bit for bit, the counter and the
    span say what happened, and ``compile()`` and ``resume()`` lower
    ``snap`` with what ``advance`` hands it."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    _through_the_kernel(monkeypatch)
    got = []
    job = sw.make_job(
        cfg, comm, STEPS_A_CALL, sw.Snapshot(coarsen=COARSEN),
        lambda s, step: got.append((step, s)),
        sw.Checkpoint(tmp_path / "saves", every_calls=0))
    start = _state(cfg, comm, seeded)
    job.start(start)
    job.advance(2)
    lowered = job.multi.lower(job.state, job._sums)
    handed = lowered.out_info[1]
    assert [(a.shape, a.dtype) for a in handed] == [
        (a.shape, a.dtype) for a in job._sums] and len(handed) == 3
    assert [a.shape[1] for a in handed] == [a.shape[1] for a in job.state[:3]]
    assert all(a.shape[0] < job.state.h.shape[0] // 2 for a in handed)
    # a call allocates nothing: the state and the room are both donated
    assert all(a.donated for a in jax.tree.leaves(lowered.args_info))
    with pytest.raises(ValueError, match="from_sums=True.*row sums"):
        job.snap(*job._written())
    with pytest.raises(ValueError, match="from_sums=False.*padded field"):
        sw.make_snapshot(cfg, comm, job.snapshot)(*job._sums)
    job.save()
    job.compile()  # from here on the executables, lowered with the sums
    assert not hasattr(job._snap, "lower")
    job.advance(2)
    job.drain()
    assert [step for step, _ in got] == STEPS
    stats = job.stats()
    assert stats["snapshots_summed_in_step"] == stats["snapshots_produced"] == CALLS
    # a walk of the kernel is two steps, beside neighbours too: the
    # sums ride in the last of a call's five
    assert stats["steps_per_walk"] == 2
    assert len(_named(job, "job/enqueue", program="snap", handed="row_sums")) == CALLS
    for (_, snapshot), (_, array_code) in zip(got, on_one_device(2)):
        for k in FIELDS:
            assert snapshot[k].shape == (NY // COARSEN, NX // COARSEN)
            assert snapshot[k].dtype == np.float32
            assert np.abs(snapshot[k] - array_code[k]).max() <= 1e-4
    for k in FIELDS:
        whole = _interior(getattr(job.state, k), 2, mesh_shape)
        assert np.abs(got[-1][1][k] - reference.block_mean(whole, COARSEN)
                      ).max() <= 3e-5
    # the state: what a job without output returns
    plain = sw.make_job(cfg, comm, STEPS_A_CALL)
    plain.start(start)
    plain.advance(CALLS)
    for a, b in zip(job.state, plain.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # resumed from the save after two calls: the same output from there on
    again = []
    other = sw.make_job(
        cfg, comm, STEPS_A_CALL, sw.Snapshot(coarsen=COARSEN),
        lambda s, step: again.append((step, s)),
        sw.Checkpoint(tmp_path / "saves", every_calls=0))
    assert other.resume() == STEPS[1]
    assert not hasattr(other._snap, "lower")
    other.advance(2)
    other.drain()
    assert [step for step, _ in again] == STEPS[2:]
    for (_, a), (_, b) in zip(again, got[2:]):
        for k in FIELDS:
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("ghost,coarsen", [(1, 4), (4, 4), (2, 1), (2, 4)])
def test_the_array_code_hands_its_snapshot_program_the_fields(seeded, ghost, coarsen):
    """On every backend but TPU devices, and at ``coarsen`` 1, ``snap``
    reads ``h``, ``u``, ``v`` as it did: the call returns the state
    alone and the counter stays at zero."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    job, got = _run(cfg, _comm((1, 1)), seeded, sw.Snapshot(coarsen=coarsen), calls=2)
    assert isinstance(job.multi.lower(job.state).out_info, sw.SWState)
    assert job._sums == () and sw.make_sums_room(cfg, job.comm, job.snapshot) is None
    with pytest.raises(ValueError, match="nothing to finish"):
        sw.make_snapshot(cfg, job.comm, job.snapshot, from_sums=True)
    stats = job.stats()
    assert stats["snapshots_produced"] == 2 and stats["snapshots_summed_in_step"] == 0
    assert stats["steps_per_walk"] == 1
    assert len(_named(job, "job/enqueue", program="snap", handed="fields")) == 2
    assert not _named(job, "job/enqueue", program="snap", handed="row_sums")


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


def test_every_call_consumes_its_input_output_or_not_saved_or_not(
        seeded, tmp_path):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm((1, 1))
    job = sw.make_job(cfg, comm, STEPS_A_CALL, sw.Snapshot(coarsen=COARSEN),
                      checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=1))
    job.start(_state(cfg, comm, seeded))
    for _ in range(3):
        job.advance()  # a snapshot and a save of what it returns
        taken = job.state
        job.advance()
        # the call after them took every array of its input: what is
        # written and what is saved are copies made before it ran
        assert all(a.is_deleted() for a in taken)
    job.drain()
    assert job.stats()["saves_acknowledged"] == job.stats()["snapshots_delivered"] == 6


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

    def counting(self, cause=None):
        before = self._asked
        seconds = ask(self, cause)
        asked.extend(step for step, _ in list(self._pending)[before:self._asked])
        assert self._asked <= at_once and self._copies.held == self._asked * one
        return seconds

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
    assert job._asked == job._copies.held == 0


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


# -- the job saved, killed and resumed ------------------------------------


def _steps_of(got):
    return [step for step, _ in got]


@pytest.mark.parametrize("with_output", [False, True])
@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_a_resumed_job_continues_the_killed_one_bit_for_bit(
        seeded, tmp_path, ghost, with_output):
    """On ``comm2d``'s mesh: a job saved every two calls and dropped
    after its fourth, a new job resumed from the directory: the state
    two calls on is the uninterrupted job's in every bit of its six
    arrays, tendencies among them (the step after a resume is
    Adams-Bashforth, not forward Euler), and the two jobs' snapshots
    are the uninterrupted one's, each once."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=ghost)
    comm = _comm((2, 2))
    snapshot = sw.Snapshot(coarsen=COARSEN, lag=1) if with_output else None
    whole, whole_got = _run(cfg, comm, seeded, snapshot, calls=6)
    got = []
    ck = sw.Checkpoint(tmp_path / "run", every_calls=2)

    def job():
        return sw.make_job(
            cfg, comm, STEPS_A_CALL, snapshot,
            snapshot and (lambda s, step: got.append((step, s))), ck)

    killed = job()
    killed.start(_state(cfg, comm, seeded))
    killed.advance(4)
    killed.drain()
    assert [r["step"] for r in killed.saves] == [21, 41]
    del killed
    resumed = job()
    assert resumed.resume() == 41 and (resumed.step, resumed.calls) == (41, 4)
    resumed.advance(2)
    resumed.drain()
    assert resumed.step == whole.step == 61
    for name, a, b in zip(sw.SWState._fields, resumed.state, whole.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert [r["step"] for r in resumed.saves] == [61]
    assert _steps_of(got) == _steps_of(whole_got)
    for (_, mine), (_, theirs) in zip(got, whole_got):
        for k in mine:
            np.testing.assert_array_equal(mine[k], theirs[k])
    # a resume that drops the tendencies is another run
    dropped = job()
    dropped.resume()
    zeros = {k: jnp.zeros_like(getattr(dropped.state, k)) for k in ("dh", "du", "dv")}
    dropped.state = dropped.state._replace(**zeros)
    dropped.advance(2)
    assert np.abs(np.asarray(dropped.state.h) - np.asarray(whole.state.h)).max() > 1e-4


def test_a_save_holds_the_step_it_names_though_later_calls_run_first(
        seeded, tmp_path, monkeypatch):
    """No tearing under donation: the save's pieces are cut before the
    next call is enqueued, and that call and two more have consumed the
    state, each donating its input, before the first piece is fetched."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm((1, 1))
    job = sw.make_job(cfg, comm, STEPS_A_CALL,
                      checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=0))
    job.start(_state(cfg, comm, seeded))
    job.advance(2)
    saved_state = job.state
    want = [np.asarray(a).copy() for a in saved_state]
    go = __import__("threading").Event()
    to_host = sw.ckpt.to_host

    def late(pieces, ahead_bytes=None, **spans):
        go.wait(60)
        yield from to_host(pieces, ahead_bytes, **spans)

    monkeypatch.setattr(sw.ckpt, "to_host", late)
    save = job.save()
    job.advance(3)
    jax.block_until_ready(job.state)
    assert all(a.is_deleted() for a in saved_state) and not save.committed
    go.set()
    job.drain()
    assert save.committed and save.record["step"] == 21 and job.step == 51
    fresh = sw.make_job(cfg, comm, STEPS_A_CALL)
    assert fresh.resume(tmp_path / "run") == 21
    for name, a, b in zip(sw.SWState._fields, fresh.state, want):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)


def test_saves_are_acknowledged_in_order_and_the_newest_are_kept(
        seeded, tmp_path, monkeypatch):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm((2, 2))
    held = []
    commit, prune = sw.ckpt.Series.commit, sw.ckpt.Series.prune

    def watched(self, tmp, step):
        commit(self, tmp, step)
        held.append(self.steps())

    def pruned(self):
        prune(self)
        held.append(self.steps())

    monkeypatch.setattr(sw.ckpt.Series, "commit", watched)
    monkeypatch.setattr(sw.ckpt.Series, "prune", pruned)
    job = sw.make_job(
        cfg, comm, STEPS_A_CALL,
        checkpoint=sw.Checkpoint(tmp_path / "run", every_calls=1, keep=2))
    job.start(_state(cfg, comm, seeded))
    job.advance(5)
    assert job.stats()["saves_started"] == 5  # the fifth may be on its way
    job.drain()
    stats = job.stats()
    assert stats["saves_started"] == stats["saves_acknowledged"] == 5
    assert [r["step"] for r in job.saves] == [11, 21, 31, 41, 51]
    # an older save goes only once the new one is committed: from the
    # `keep`-th commit on the directory never holds fewer than `keep`
    assert held == [[11], [11], [11, 21], [11, 21], [11, 21, 31], [21, 31],
                    [21, 31, 41], [31, 41], [31, 41, 51], [41, 51]]
    assert not job.series.leftovers()  # drain() took the spare files away
    state_bytes = sum(a.nbytes for a in job.state)
    assert stats["save_bytes"] == 5 * state_bytes
    assert all(r["bytes"] == state_bytes and 0 < r["stage_s"] <= r["commit_s"]
               for r in job.saves)
    # waiting for the save before is the loop blocked; starting one is not
    assert stats["save_wait_s"] > 0 and stats["save_enqueue_s"] > 0
    assert stats["save_commit_s"] > 0
    # a job without a checkpoint saves nothing and says so
    bare = sw.make_job(cfg, comm, STEPS_A_CALL)
    with pytest.raises(ValueError, match="without a `checkpoint`"):
        bare.save()
    with pytest.raises(ValueError, match="no directory"):
        bare.resume()
    assert sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=sw.Checkpoint(
        tmp_path / "empty")).resume() is None


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("ahead", [None, 4096, 1500])
def test_a_saves_pieces_are_bounded_by_ahead_bytes(
        seeded, tmp_path, mesh_shape, ahead):
    """A piece, all devices' bands of it together, is at most half of
    ``ahead_bytes`` (a row of every block at least), so that one copy
    runs while the next waits; an array's pieces lie in one file; a
    restore reads them back under the same bound, bit for bit."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    ck = sw.Checkpoint(tmp_path / "run", every_calls=0, ahead_bytes=ahead)
    job = sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=ck)
    job.start(_state(cfg, comm, seeded))
    job.advance(1)
    job.save()
    job.drain()
    manifest = job.series.manifest(11)
    assert manifest["step"] == 11 and manifest["form"] == job.form()
    py, px = mesh_shape
    row = (NX // px + 4) * 4 * py * px  # one row of every device's block
    band = ck.piece_bytes(py * px)
    most = max(band * py * px, row)
    assert ck.ahead(py * px) == (ahead or sw.ckpt.AHEAD_BYTES * py * px)
    assert band == min(sw.ckpt.PIECE_BYTES, ck.ahead(py * px) // 2 // (py * px))
    for name, a, plan in zip(sw.SWState._fields, job.state, job._plan):
        held = manifest["arrays"][name]
        assert held["shape"] == list(a.shape) and held["file"] == f"{name}.npy"
        bands = [tuple(band) for band in held["bands"]]
        assert bands == plan and bands[0][0] == 0 and bands[-1][1] == a.shape[0] // py
        assert all(a[1] == b[0] for a, b in zip(bands, bands[1:]))
        width = a.shape[1] * a.dtype.itemsize
        assert all((hi - lo) * py * width <= most for lo, hi in bands)
        assert (len(bands) == 1) == (ahead is None)
        # one file an array, whole
        assert np.load(job.series.path(11) / held["file"]).shape == a.shape
    fresh = sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=ck)
    assert fresh.resume() == 11
    for a, b in zip(fresh.state, job.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fresh.stats()["restore_read_s"] > 0


def test_a_save_of_another_grid_mesh_or_schedule_is_refused_with_both_named(
        seeded, tmp_path):
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    ck = sw.Checkpoint(tmp_path / "run", every_calls=0)
    job = sw.make_job(cfg, _comm((2, 2)), STEPS_A_CALL, checkpoint=ck)
    job.start(_state(cfg, job.comm, seeded))
    job.save()
    job.drain()
    assert job.form() == {"grid": [NY, NX], "mesh": [2, 2], "ghost": 2,
                          "dtype": "float32", "tendencies": "interior"}
    for other_cfg, shape, what in (
            (cfg, (1, 1), r"mesh \[2, 2\].*runs mesh \[1, 1\]"),
            (sw.SWConfig(ny=NY, nx=NX, ghost=4), (2, 2), r"ghost 2.*runs ghost 4"),
            (sw.SWConfig(ny=NY, nx=2 * NX, ghost=2), (2, 2),
             r"grid \[32, 64\].*runs grid \[32, 128\]")):
        other = sw.make_job(other_cfg, _comm(shape), STEPS_A_CALL, checkpoint=ck)
        with pytest.raises(ValueError, match=what):
            other.resume()
        assert other.state is None
    # the schedules that carry padded tendencies say so
    assert sw.make_job(sw.SWConfig(ny=NY, nx=NX, ghost=1), _comm((1, 1)),
                       STEPS_A_CALL).form()["tendencies"] == "padded"


# -- the job's host spans (utils/spans.py) ----------------------------------


def _named(job, name, **counts):
    return [s for s in job.spans() if s.name == name
            and all(s.counts.get(k) == v for k, v in counts.items())]


def _total(spans):
    return sum(s.seconds for s in spans)


@pytest.mark.parametrize("ahead", [None, 1.5])
def test_every_time_of_a_job_with_output_is_the_sum_of_its_spans(seeded, ahead):
    """``output_wait_s`` is the fetches and the asks that a fetch made
    room for, ``callback_s`` the callbacks; a span's key is the step it
    is about, and a call's spans lie inside its ``job/advance``."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    one = 3 * (NY // COARSEN) * (NX // COARSEN) * 4
    job = sw.make_job(
        cfg, _comm((1, 1)), STEPS_A_CALL,
        sw.Snapshot(coarsen=COARSEN, lag=2, ahead_bytes=ahead and int(ahead * one)),
        lambda s, step: time.sleep(0.002))
    job.start(_state(cfg, job.comm, seeded))
    job.advance(3)
    job.advance(2)
    job.drain()
    stats, steps = job.stats(), [11, 21, 31, 41, 51]
    assert job.trace.dropped == 0 and job.trace.prefix == sw.SCOPE_PREFIX
    fetches, asks = _named(job, "job/fetch"), _named(job, "job/ask")
    after_a_fetch = [a for a in asks if a.cause in {f.id for f in fetches}]
    assert stats["output_wait_s"] == pytest.approx(
        _total(fetches) + _total(after_a_fetch), rel=1e-9)
    assert stats["callback_s"] == pytest.approx(
        _total(_named(job, "job/callback")), rel=1e-9) and stats["callback_s"] > 0.01
    # without a bound every copy is asked for as its snapshot is produced
    assert bool(after_a_fetch) == (ahead is not None)
    for name in ("job/fetch", "job/ask", "job/callback"):
        assert [s.key for s in _named(job, name)] == steps
    assert all(s.counts["bytes"] == one for s in fetches + asks)
    assert stats["bytes_to_host"] == sum(s.counts["bytes"] for s in fetches)
    for program in ("multi", "snap"):
        assert [s.key for s in _named(job, "job/enqueue", program=program)] == steps
    # a call of advance, its first step and its calls; the drain
    advances = _named(job, "job/advance")
    assert [(s.key, s.counts["calls"], s.cause) for s in advances] == [
        (11, 3, None), (41, 2, None)]
    drains = _named(job, "job/drain")
    assert [s.key for s in drains] == [0, 51]  # start() drains first
    roots = {s.id for s in advances + drains}
    by_id = {s.id: s for s in job.spans()}
    for s in job.spans():
        assert s.thread == advances[0].thread
        if s.id not in roots:  # caused by a span that was open, or by a fetch
            cause = by_id[s.cause]
            assert cause.id in roots or cause.name == "job/fetch"
            outer = cause if cause.id in roots else by_id[cause.cause]
            assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_every_time_of_a_saved_and_resumed_job_is_the_sum_of_its_spans(
        seeded, tmp_path, mesh_shape):
    """``save_wait_s``, ``save_enqueue_s``, a save's ``stage_s`` and
    ``commit_s``, ``restore_read_s`` and ``restore_to_device_s``; every
    span of a save's threads under the step it holds, every write caused
    by the ``checkpoint/save`` of its own save, that one by its
    ``job/save``."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    ck = sw.Checkpoint(tmp_path / "run", every_calls=1, keep=2, ahead_bytes=4096)
    job = sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=ck)
    job.start(_state(cfg, comm, seeded))
    job.advance(3)
    job.drain()
    stats = job.stats()
    assert stats["save_wait_s"] == pytest.approx(
        _total(_named(job, "job/save_wait")), rel=1e-9) and stats["save_wait_s"] > 0
    assert stats["save_enqueue_s"] == pytest.approx(
        _total(_named(job, "job/save_start")), rel=1e-9)
    saves = {s.key: s for s in _named(job, "job/save")}
    assert sorted(saves) == [11, 21, 31] == [r["step"] for r in job.saves]
    state_bytes = sum(a.nbytes for a in job.state)
    pieces = sum(len(plan) for plan in job._plan)
    main = saves[11].thread
    for record in job.saves:
        step = record["step"]
        mine = [s for s in job.spans() if s.key == step and s.name.startswith("checkpoint/")]
        (whole,) = [s for s in mine if s.name == "checkpoint/save"]
        assert whole.cause == saves[step].id and whole.thread == "checkpoint-save"
        assert whole.counts["bytes"] == saves[step].counts["bytes"] == state_bytes
        assert record["commit_s"] == whole.seconds
        fetches = [s for s in mine if s.name == "checkpoint/fetch"]
        writes = [s for s in mine if s.name == "checkpoint/write"]
        assert len(fetches) == len(writes) == pieces
        assert record["stage_s"] == (fetches[-1].end_ns - whole.start_ns) / 1e9
        assert sum(s.counts["bytes"] for s in fetches) == state_bytes
        assert sum(s.counts["bytes"] for s in writes) == state_bytes
        assert {s.cause for s in fetches} == {whole.id}
        assert {s.thread for s in fetches} == {"checkpoint-save"}
        # a write is caused by the save, whichever writer took it
        assert {s.cause for s in writes} == {whole.id}
        assert {s.thread for s in writes} <= {"checkpoint-write-0", "checkpoint-write-1"}
        (commit,) = [s for s in mine if s.name == "checkpoint/commit"]
        (prune,) = [s for s in mine if s.name == "checkpoint/prune"]
        assert commit.cause == prune.cause == whole.id
        assert whole.start_ns <= fetches[0].start_ns and commit.end_ns <= whole.end_ns
        assert whole.end_ns <= prune.start_ns  # commit_s ends at the rename
        # the staging program's enqueue lies in the loop's job/save_start
        (staged,) = [s for s in _named(job, "job/enqueue", program="stage")
                     if s.key == step]
        (started,) = [s for s in _named(job, "job/save_start") if s.key == step]
        assert staged.cause == started.id and started.cause == saves[step].id
        assert staged.thread == main
    assert stats["save_commit_s"] == sum(r["commit_s"] for r in job.saves)
    # a save waited for is named by the step it holds, not the next one's
    assert [s.key for s in _named(job, "job/save_wait")] == [
        s.key - STEPS_A_CALL for s in saves.values()
        if any(w.cause == s.id for w in _named(job, "job/save_wait"))]
    # resumed: every band read and handed to the device is a span
    fresh = sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=ck)
    assert fresh.resume() == 31
    stats = fresh.stats()
    (whole,) = _named(fresh, "job/resume")
    reads, sent = _named(fresh, "checkpoint/read"), _named(fresh, "checkpoint/to_device")
    assert 0 < stats["restore_read_s"] == pytest.approx(_total(reads), rel=1e-9)
    assert 0 < stats["restore_to_device_s"] == pytest.approx(_total(sent), rel=1e-9)
    assert len(reads) == len(sent) == pieces
    assert {s.cause for s in reads + sent} == {whole.id}
    assert {s.key for s in reads + sent} == {31} and whole.key == 31
    assert whole.counts["bytes"] == state_bytes == sum(s.counts["bytes"] for s in reads)
    (compiled,) = _named(fresh, "job/compile")
    assert compiled.cause == whole.id and compiled.key == 31
    assert whole.seconds >= _total(reads) + _total(sent) + compiled.seconds


def _without_callers(text):
    """A compiled text less what says where it was traced from (the
    job's programs are traced from ``advance``, a bare one from its
    caller): the tables of files, functions and frames, and each
    instruction's frame.  Names, scopes, shapes and schedule stay."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)+", "", text, flags=re.M)
    return re.sub(r" stack_frame_id=\d+", "", text)


def test_the_recorder_leaves_the_jobs_compiled_programs_as_they_are(seeded, tmp_path):
    """``multi``, ``snap`` and ``stage`` compiled inside an open span of
    the job's recorder, after calls made through it, are to the letter
    the programs made with no job and no recorder."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm((2, 2))
    snapshot = sw.Snapshot(coarsen=COARSEN)
    job = sw.make_job(cfg, comm, STEPS_A_CALL, snapshot, lambda s, step: None,
                      sw.Checkpoint(tmp_path / "run", every_calls=2, ahead_bytes=4096))
    job.start(_state(cfg, comm, seeded))
    job.advance(2)
    job.drain()
    written = job._written()
    programs = {
        True: [(job.multi, (job.state,)), (job.snap, written), (job.stage, (job.state,))],
        False: [(sw.make_multistep(cfg, comm, STEPS_A_CALL, donate=True), (job.state,)),
                (sw.make_snapshot(cfg, comm, snapshot), written),
                (sw.make_stage(comm, job._plan), (job.state,))]}
    texts = {}
    for mine in (True, False):
        with job.trace.span("job/enqueue") if mine else contextlib.nullcontext():
            texts[mine] = [_without_callers(program.lower(*args).compile().as_text())
                           for program, args in programs[mine]]
    assert texts[True] == texts[False]
    assert "job/" not in "".join(texts[True])
    assert sw.SCOPE_PREFIX + "checkpoint" in texts[True][2]
    assert sw.SCOPE_PREFIX + "snapshot" in texts[True][1]


# -- a job with both halves: one bound on what is on its way to the host ------


ONE = 3 * (NY // COARSEN) * (NX // COARSEN) * 4  # a snapshot of h, u, v


def _within(seconds, work):
    """``work()`` on a thread of its own, which is then the job's loop's:
    a deadlock fails the test instead of hanging the run."""
    import threading

    out = []

    def run():
        try:
            out.append((work(), None))
        except BaseException as error:  # handed to the test
            out.append((None, error))

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    thread.join(seconds)
    assert not thread.is_alive(), f"still waiting after {seconds} s"
    value, error = out[0]
    if error is not None:
        raise error
    return value


def _both(cfg, comm, directory, got, ahead, lag=3, every=2, keep=2):
    """A job with snapshots and saves whose host takes ``ahead`` bytes:
    given once, on either half."""
    return sw.make_job(
        cfg, comm, STEPS_A_CALL,
        sw.Snapshot(coarsen=COARSEN, lag=lag, ahead_bytes=ahead),
        lambda s, step: got.append((step, s)),
        sw.Checkpoint(directory, every_calls=every, keep=keep))


def _waits(job, name):
    return [s for s in job.spans() if s.name == name]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_with_both_halves_keeps_one_bound_that_bites(
        seeded, tmp_path, mesh_shape):
    """``host_bound``: snapshots' copies and a save's pieces together
    never over the job's one figure, which here holds two snapshots and
    not a piece beside them, so that the save's first piece has to wait
    for a snapshot to be fetched; and the job's output and saves are
    those of jobs that have one half each."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    bound = int(2.5 * ONE)
    got = []
    job = _both(cfg, comm, tmp_path / "run", got, bound, every=3)
    assert job.ahead_bytes == job.checkpoint.ahead_bytes == bound
    band = max((hi - lo) for plan in job._plan for lo, hi in plan)
    piece = band * mesh_shape[0] * (NX + 4 * mesh_shape[1]) * 4
    assert ONE < bound and piece <= bound // 2 and 2 * ONE + piece > bound

    def run():
        job.start(_state(cfg, comm, seeded))
        job.advance(3)  # two snapshots asked for, the third waiting; a save started
        deadline = time.monotonic() + 60
        while job._host._first != sw.ckpt.SAVE and time.monotonic() < deadline:
            time.sleep(0.001)
        assert job._host._first == sw.ckpt.SAVE  # its first piece waits for room
        job.advance(3)
        job.drain()

    _within(120, run)
    stats = job.stats()
    assert 2 * ONE <= stats["host_in_flight_max_bytes"] <= bound
    assert job._host.in_flight == 0
    held = _waits(job, "checkpoint/fetch_wait")
    assert held and all(s.counts["held_by"] == "snapshot" and s.thread == "checkpoint-save"
                        and s.key in (31, 61) for s in held)
    assert all(s.counts["held_by"] == "save" for s in _waits(job, "job/ask_wait"))
    assert stats["transfer_wait_s"] == pytest.approx(
        _total(held) + _total(_waits(job, "job/ask_wait")), rel=1e-9)
    assert stats["transfer_wait_s"] > 0
    assert sum(r["fetch_wait_s"] for r in job.saves) == pytest.approx(_total(held))
    # what it wrote and what it saved are a one-half job's
    assert _steps_of(got) == [11 + 10 * k for k in range(6)] and stats["max_lag"] <= 3
    assert [r["step"] for r in job.saves] == [31, 61]
    _, alone = _run(cfg, comm, seeded, sw.Snapshot(coarsen=COARSEN), calls=6)
    for (_, mine), (_, theirs) in zip(got, alone):
        for k in FIELDS:
            np.testing.assert_array_equal(mine[k], theirs[k])
    fresh = sw.make_job(cfg, comm, STEPS_A_CALL)
    assert fresh.resume(tmp_path / "run") == 61
    for a, b in zip(fresh.state, job.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_copy_that_alone_is_over_the_bound_goes_alone(seeded, tmp_path, mesh_shape):
    """Under a bound smaller than a snapshot every snapshot goes alone,
    with nothing of either kind in flight, the most in flight is that
    one copy, and the job still finishes: every snapshot, every save."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    bound = ONE // 2
    got = []
    job = _both(cfg, comm, tmp_path / "run", got, bound, lag=1, every=1)

    def run():
        job.start(_state(cfg, comm, seeded))
        job.advance(5)
        job.drain()

    _within(120, run)
    stats = job.stats()
    assert stats["host_in_flight_max_bytes"] == ONE  # over the bound by that copy alone
    assert _steps_of(got) == [11 + 10 * k for k in range(5)] and stats["max_lag"] <= 1
    assert stats["saves_started"] == stats["saves_acknowledged"] == 5
    # no piece was in flight beside a snapshot, nor a snapshot beside a piece
    flights = sorted(
        [(s.start_ns, +1, "snapshot") for s in _waits(job, "job/ask")]
        + [(s.end_ns, -1, "snapshot") for s in _waits(job, "job/fetch")],)
    pieces = [(s.start_ns, s.end_ns) for s in _waits(job, "checkpoint/fetch")]
    asked_at = {s.key: s.start_ns for s in _waits(job, "job/ask")}
    for fetch in _waits(job, "job/fetch"):
        a, b = asked_at[fetch.key], fetch.end_ns
        assert not any(a < end and start < b for start, end in pieces), fetch.key
    assert flights


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_output_across_a_kill_and_a_resume_is_the_uninterrupted_jobs(
        seeded, tmp_path, mesh_shape):
    """``seamless_output``: a job with both halves under a bound that
    bites, dropped after its fourth call with snapshots asked for and
    undelivered; a new job resumed from the directory starts with no
    snapshot pending, its first is of the resumed step plus one call,
    and from there on its snapshots are the uninterrupted job's bit for
    bit, as its state is."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    bound = int(2.5 * ONE)
    _, whole_got = _run(cfg, comm, seeded, sw.Snapshot(coarsen=COARSEN), calls=7)
    before, after = [], []

    def run():
        killed = _both(cfg, comm, tmp_path / "run", before, bound)
        killed.start(_state(cfg, comm, seeded))
        killed.advance(4)
        killed._settle()  # the save of step 41 acknowledged; output still pending
        # (the wait may have delivered some early: the save's pieces wanted their room)
        assert killed._pending and _steps_of(before) + [
            step for step, _ in killed._pending] == [11, 21, 31, 41]
        del killed
        resumed = _both(cfg, comm, tmp_path / "run", after, bound)
        assert resumed.resume() == 41
        assert not resumed._pending and resumed.snap is not None
        assert resumed.stats()["host_in_flight_max_bytes"] == 0
        resumed.advance(3)
        resumed.drain()
        return resumed

    resumed = _within(120, run)
    assert _steps_of(after) == [51, 61, 71] and resumed.step == 71
    assert resumed.stats()["host_in_flight_max_bytes"] <= bound
    for (step, mine), (at, theirs) in zip(after, whole_got[4:]):
        assert step == at
        for k in FIELDS:
            np.testing.assert_array_equal(mine[k], theirs[k], err_msg=f"{k} at {step}")
    # a snapshot one call off is another run's: the comparison sees it
    assert np.abs(after[1][1]["h"] - whole_got[4][1]["h"]).max() > 1e-4


def test_max_lag_holds_while_a_save_is_waited_for(seeded, tmp_path, monkeypatch):
    """A save every call into a slow file: the loop waits for the save
    before in every call, fetches no snapshot meanwhile, and still
    hands every snapshot over in order, none more than ``lag`` late."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm((1, 1))
    write_at = sw.ckpt.write_at

    def slow(fd, offset, array, bounce):
        time.sleep(0.002)
        write_at(fd, offset, array, bounce)

    monkeypatch.setattr(sw.ckpt, "write_at", slow)
    got = []
    job = _both(cfg, comm, tmp_path / "run", got, 4 * ONE, lag=2, every=1)

    def run():
        job.start(_state(cfg, comm, seeded))
        job.advance(6)
        assert len(job._pending) == 2
        job.drain()

    _within(120, run)
    stats = job.stats()
    assert stats["save_wait_s"] > 0.01 and len(_waits(job, "job/save_wait")) == 5
    assert stats["max_lag"] == 2 and _steps_of(got) == [11 + 10 * k for k in range(6)]
    assert stats["snapshots_delivered"] == stats["saves_acknowledged"] == 6
    assert stats["host_in_flight_max_bytes"] <= 4 * ONE
    # no snapshot was fetched inside a wait for a save
    for wait in _waits(job, "job/save_wait"):
        assert not any(wait.start_ns < s.start_ns < wait.end_ns
                       for s in _waits(job, "job/fetch"))


# what the loop's thread records over `advance(3); advance(2); drain()`
# from a fresh start, as the job before PR 45 recorded it (made there)
SNAPSHOT_ONLY = (
    "job/drain job/enqueue job/enqueue job/ask job/enqueue job/enqueue "
    "job/enqueue job/enqueue job/fetch job/ask job/callback job/advance "
    "job/enqueue job/enqueue job/fetch job/ask job/callback job/enqueue "
    "job/enqueue job/fetch job/ask job/callback job/advance job/fetch job/ask "
    "job/callback job/fetch job/callback job/drain").split()
CHECKPOINT_ONLY = (
    "job/drain job/enqueue job/enqueue job/enqueue job/save_start job/save "
    "job/enqueue job/advance job/enqueue job/save_wait job/enqueue "
    "job/save_start job/save job/enqueue job/advance job/drain").split()


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_job_with_one_half_is_what_it_was(seeded, tmp_path, mesh_shape):
    """A job with only a ``Snapshot`` or only a ``Checkpoint`` records
    the span names it recorded before the two halves shared a bound,
    never one of the two waits, and the three programs of a job with
    both halves are, to the letter, those of the jobs with one."""
    cfg = sw.SWConfig(ny=NY, nx=NX, ghost=2)
    comm = _comm(mesh_shape)
    snapshot = sw.Snapshot(coarsen=COARSEN, lag=2, ahead_bytes=2500)
    checkpoint = sw.Checkpoint(tmp_path / "run", every_calls=2, ahead_bytes=4096)
    halves = {
        "snapshot": sw.make_job(cfg, comm, STEPS_A_CALL, snapshot, lambda s, k: None),
        "checkpoint": sw.make_job(cfg, comm, STEPS_A_CALL, checkpoint=checkpoint)}
    for job in halves.values():
        job.start(_state(cfg, comm, seeded))
        job.advance(3)
        job.advance(2)
        job.drain()
        assert job.stats()["transfer_wait_s"] == 0
    names = {}
    for key, job in halves.items():
        main = _named(job, "job/advance")[0].thread
        names[key] = [s.name for s in job.spans() if s.thread == main]
        assert not _waits(job, "job/ask_wait") + _waits(job, "checkpoint/fetch_wait")
    assert names == {"snapshot": SNAPSHOT_ONLY, "checkpoint": CHECKPOINT_ONLY}
    others = collections.Counter(
        s.name for s in halves["checkpoint"].spans() if s.thread.startswith("checkpoint-"))
    pieces = sum(len(plan) for plan in halves["checkpoint"]._plan)
    assert others == {"checkpoint/save": 2, "checkpoint/commit": 2, "checkpoint/prune": 2,
                      "checkpoint/close": 2, "checkpoint/manifest": 2, "checkpoint/rename": 2,
                      "checkpoint/fetch": 2 * pieces, "checkpoint/write": 2 * pieces}
    assert halves["snapshot"].stats()["host_in_flight_max_bytes"] == ONE
    assert 0 < halves["checkpoint"].stats()["host_in_flight_max_bytes"] <= 4096
    # both halves, under the same figure: the same three programs
    both = sw.make_job(cfg, comm, STEPS_A_CALL,
                       replace(snapshot, ahead_bytes=4096), lambda s, k: None, checkpoint)
    assert both._plan == halves["checkpoint"]._plan
    state = halves["snapshot"].state
    written = halves["snapshot"]._written()
    for program, of, args in (("multi", "snapshot", (state,)), ("snap", "snapshot", written),
                              ("multi", "checkpoint", (state,)), ("stage", "checkpoint", (state,))):
        mine, theirs = (_without_callers(
            getattr(job, program).lower(*args).compile().as_text())
            for job in (both, halves[of]))
        assert mine == theirs, (program, of)


def test_make_solver_keeps_and_restarts_its_output(comm2d, tmp_path):
    """``make_solver(on_chunk=, checkpoint_dir=)`` under a snapshot's
    bound is the job with both halves: a run stopped half way and
    restarted hands out, between its two legs, the snapshots of the run
    that was never stopped."""
    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    n = 5
    t_half = cfg.dt * (1 + n) + cfg.dt * n * 2
    t_full = t_half + cfg.dt * n * 3
    seen = []
    one = 3 * 8 * 16 * 4

    def solver(**kw):
        return sw.make_solver(
            cfg, comm2d, num_multisteps=n,
            snapshot=sw.Snapshot(coarsen=2, lag=2, ahead_bytes=2 * one),
            on_chunk=lambda s, step: seen.append((step, {k: a.copy() for k, a in s.items()})),
            checkpoint_every=2, **kw)

    _within(120, lambda: solver(checkpoint_dir=tmp_path / "run")(t_half))
    _within(120, lambda: solver(checkpoint_dir=tmp_path / "run")(t_full))
    chained = list(seen)
    del seen[:]
    _within(120, lambda: solver()(t_full))
    # the first leg ended on a chunk that was not saved: the second
    # leg hands it out again, the same, and goes on
    assert [s for s, _ in seen] == [1 + n * k for k in range(1, 7)]
    assert [s for s, _ in chained] == [6, 11, 16, 16, 21, 26, 31]
    whole = dict(seen)
    for step, mine in chained:
        for k in FIELDS:
            np.testing.assert_array_equal(mine[k], whole[step][k])


def test_the_example_animates_a_run_it_restarts(tmp_path, capsys):
    """``--animate`` with ``--checkpoint-dir``: a short run writes its
    frames and its saves; a longer rerun in the same directory resumes
    from the newest save and goes on writing frames from there."""
    pytest.importorskip("matplotlib")
    import importlib.util

    path = pathlib.Path(__file__).resolve().parents[1] / "examples/shallow_water.py"
    spec = importlib.util.spec_from_file_location("sw_example_restart", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    common = ["--check", "--force-cpu", "--mesh", "2", "2", "--multistep", "5",
              "--coarsen", "2", "--checkpoint-dir", str(tmp_path / "run"),
              "--checkpoint-every", "2"]

    def frames(days, name):
        example.main(common + ["--days", str(days), "--animate", str(tmp_path / name)])
        said = capsys.readouterr().err
        assert (tmp_path / name).stat().st_size > 0
        return int(re.search(r"\((\d+) frames\)", said).group(1))

    first = frames(0.01, "first.gif")
    saved = sw.ckpt.Series(tmp_path / "run").latest()
    assert first >= 3 and saved is not None
    # twice as long, from the newest save on: the chunks after it
    whole = 2 * first
    again = frames(0.02, "again.gif")
    assert again == whole - (saved - 1) // 5
    assert sw.ckpt.Series(tmp_path / "run").latest() > saved
