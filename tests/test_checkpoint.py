"""Checkpoint/resume (utils/checkpoint.py — SURVEY §5.4; absent in the
reference, first-class here): sharded round-trips, stepped manager with
retention, and bit-identical solver resume."""

import dataclasses
import functools
import os
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.utils import checkpoint as ckpt


def test_roundtrip_plain_pytree(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "step": np.int64(7)}
    ckpt.save(tmp_path / "c1", tree)
    out = ckpt.restore(tmp_path / "c1", like=tree)
    assert np.array_equal(np.asarray(out["a"]), np.arange(6.0).reshape(2, 3))
    assert int(out["step"]) == 7


def test_roundtrip_sharded(comm1d, tmp_path):
    mesh = comm1d.mesh
    sharding = jax.NamedSharding(mesh, jax.P("i"))
    x = jax.device_put(jnp.arange(16.0).reshape(8, 2), sharding)
    ckpt.save(tmp_path / "c2", {"x": x})
    out = ckpt.restore(tmp_path / "c2", like={"x": x})
    assert out["x"].sharding.is_equivalent_to(sharding, 2)
    assert np.array_equal(np.asarray(out["x"]), np.asarray(x))


def test_manager_retention_and_latest(tmp_path):
    with ckpt.Manager(tmp_path / "series", max_to_keep=2) as mgr:
        assert mgr.latest_step() is None
        for step in (1, 2, 3):
            mgr.save(step, {"v": jnp.float32(step)})
        assert mgr.latest_step() == 3
        out = mgr.restore(3, like={"v": jnp.float32(0)})
        assert float(out["v"]) == 3.0
    assert ckpt.latest_step(tmp_path / "series") == 3
    # retention: step 1 evicted
    with ckpt.Manager(tmp_path / "series", max_to_keep=2) as mgr:
        with pytest.raises(Exception):
            mgr.restore(1, like={"v": jnp.float32(0)})


def test_manager_wait_until_finished_commits(tmp_path):
    # the durability barrier: after wait_until_finished() the step dir
    # is COMMITTED on disk (no .orbax-checkpoint-tmp left) — what a
    # fault-tolerant loop relies on before telling peers the step is
    # safe (tests/proc/test_failure_recovery.py exercises the
    # composition; this pins the contract in isolation)
    with ckpt.Manager(tmp_path / "d", max_to_keep=2) as mgr:
        mgr.save(5, {"v": jnp.float32(5)})
        mgr.wait_until_finished()
        names = [p.name for p in (tmp_path / "d").iterdir()]
        assert "5" in names, names
        assert not any("tmp" in n for n in names), names


def test_solver_resume_bit_identical(comm2d, tmp_path):
    """Stop/checkpoint/restore mid-run must reproduce the uninterrupted
    trajectory exactly (the resumability guarantee)."""
    from mpi4jax_tpu.models import shallow_water as sw

    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    comm = comm2d
    init = sw.make_init(cfg, comm)
    first = sw.make_first_step(cfg, comm)
    multi = sw.make_multistep(cfg, comm, 5)

    s = first(init())
    s_mid = multi(s)
    s_full = multi(s_mid)  # 10 steps, uninterrupted

    ckpt.save(tmp_path / "mid", {"state": s_mid})
    restored = ckpt.restore(tmp_path / "mid", like={"state": s_mid})
    s_resumed = multi(sw.SWState(*restored["state"]))

    for a, b in zip(s_full, s_resumed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_solver_resume(comm2d, tmp_path):
    """A solver with checkpoint_dir resumes from the latest checkpoint:
    an interrupted run continued in a second solve() matches one
    uninterrupted trajectory chunk-for-chunk."""
    from mpi4jax_tpu.models import shallow_water as sw

    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    n = 5
    t_half = cfg.dt * (1 + n) + cfg.dt * n * 2  # warmup + 2 timed chunks
    t_full = t_half + cfg.dt * n * 2  # + 2 more

    ck = tmp_path / "run"
    solve_a = sw.make_solver(cfg, comm2d, num_multisteps=n, checkpoint_dir=ck)
    state_a, _, _ = solve_a(t_half)

    assert ckpt.latest_step(ck) is not None  # something was saved

    # "crash" and resume: fresh solver, same dir, longer horizon
    solve_b = sw.make_solver(cfg, comm2d, num_multisteps=n, checkpoint_dir=ck)
    state_b, _, steps_b = solve_b(t_full)

    # oracle: uninterrupted run to the same horizon, no checkpointing
    solve_c = sw.make_solver(cfg, comm2d, num_multisteps=n)
    state_c, _, _ = solve_c(t_full)

    for b, c in zip(state_b, state_c):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_rerun_completed_run_does_not_advance(comm2d, tmp_path):
    """Re-solving an already-completed run in the same checkpoint dir
    must return the restored state untouched, not push the trajectory
    past the requested horizon (and must not write new checkpoints)."""
    from mpi4jax_tpu.models import shallow_water as sw

    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    n = 5
    t1 = cfg.dt * (1 + n) + cfg.dt * n * 2

    ck = tmp_path / "run"
    state_a, _, steps_a = sw.make_solver(
        cfg, comm2d, num_multisteps=n, checkpoint_dir=ck
    )(t1)
    assert steps_a > 0
    last = ckpt.latest_step(ck)

    state_b, _, steps_b = sw.make_solver(
        cfg, comm2d, num_multisteps=n, checkpoint_dir=ck
    )(t1)
    assert steps_b == 0  # nothing left to do
    assert ckpt.latest_step(ck) == last  # no new checkpoint written
    for a, b in zip(state_a, state_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_transformer_resume_bit_identical(tmp_path):
    """Checkpoint/restore mid-training of the newest model family (MoE
    transformer, topk routing + aux router losses) reproduces the
    uninterrupted run bit for bit — restore is exact and the sharded
    train step is deterministic, so resumed training is
    indistinguishable from never having stopped."""
    from mpi4jax_tpu.models import moe_transformer as moe

    mesh = jax.make_mesh(
        (2, 2, 2), ("dp", "tp", "sp"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
    )
    world = m.MeshComm.from_mesh(mesh)
    cfg = moe.MoEConfig(
        vocab=32, d_model=16, layers=2, heads=4, kv_heads=2, head_dim=8,
        experts=4, d_ff=32, routing="topk", aux_weight=0.02, z_weight=1e-3,
    )
    step = moe.make_global_train_step(
        mesh, world.sub("dp"), world.sub("tp"), world.sub("sp"), cfg, lr=0.1
    )
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    batch = (tokens, jnp.roll(tokens, -1, axis=1))

    for _ in range(2):
        params, _ = step(params, batch)

    ckpt.save(tmp_path / "moe_mid", {"params": params})
    restored = ckpt.restore(tmp_path / "moe_mid", like={"params": params})[
        "params"
    ]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    cont, resumed = params, restored
    for _ in range(2):
        cont, loss_c = step(cont, batch)
        resumed, loss_r = step(resumed, batch)
    np.testing.assert_array_equal(np.asarray(loss_c), np.asarray(loss_r))
    for a, b in zip(jax.tree.leaves(cont), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- streamed saves: the directory, the bound on the copies, a real kill ----


def test_a_series_commits_by_one_rename_and_keeps_the_newest(tmp_path):
    series = ckpt.Series(tmp_path / "run", keep=2)
    assert series.steps() == [] and series.latest() is None
    assert ckpt.latest_step(tmp_path / "run") is None
    for step in (11, 21, 31):
        tmp = series.begin(step)
        np.save(tmp / "x.0.npy", np.full(3, step))
        # a save on its way is no save: not listed, not the latest
        assert step not in series.steps() and series.leftovers() == [tmp]
        (tmp / ckpt.MANIFEST).write_text('{"step": %d}' % step)
        series.commit(tmp, step)
        # an older save goes only after the commit: `keep` stand meanwhile
        assert series.steps() == [s for s in (11, 21, 31) if s <= step]
        series.prune()
        assert series.latest() == step and series.steps() == [
            s for s in (11, 21, 31) if step - 10 <= s <= step]
    assert series.steps() == [21, 31] and ckpt.latest_step(tmp_path / "run") == 31
    assert series.manifest(31) == {"step": 31}
    # the save that went is the next one's directory, its files for the
    # new save to write over and its manifest gone; it is no save
    spare, = series.leftovers()
    assert (spare / "x.0.npy").exists() and (spare / ckpt.MANIFEST).exists()
    tmp = series.begin(41)
    assert series.steps() == [21, 31] and series.leftovers() == [tmp]
    assert [p.name for p in tmp.iterdir()] == ["x.0.npy"]
    (tmp / ckpt.MANIFEST).write_text('{"step": 41}')
    series.commit(tmp, 41)
    series.prune()
    assert series.steps() == [31, 41] and len(series.leftovers()) == 1
    # committing a step again replaces it
    tmp = series.begin(41)
    assert series.steps() == [31, 41]
    (tmp / ckpt.MANIFEST).write_text('{"step": 41, "again": true}')
    series.commit(tmp, 41)
    assert series.steps() == [31, 41] and series.manifest(41)["again"]
    os.rename(series.path(31), series.path(21))
    # what an interrupted save left, and the spare files, are cleaned,
    # never read
    (series.directory / "41.partial-99").mkdir()
    assert series.latest() == 41 and series.clean() == 2
    assert sorted(p.name for p in series.directory.iterdir()) == ["21", "41"]
    # a directory without a manifest is somebody else's
    (series.directory / "51").mkdir()
    with pytest.raises(ValueError, match="not a streamed save"):
        series.manifest(51)


def test_latest_step_reads_a_managers_directory_too(tmp_path):
    with ckpt.Manager(tmp_path / "m", max_to_keep=3) as mgr:
        for step in (2, 4):
            mgr.save(step, {"v": jnp.float32(step)})
    assert ckpt.latest_step(tmp_path / "m") == 4
    assert ckpt.Series(tmp_path / "m").steps() == [2, 4]


@pytest.mark.parametrize("rows, row_bytes, piece_bytes, want", [
    (7204, 14404 * 4, 80e6, [1201, 1200, 1201, 1201, 1200, 1201]),
    (10, 100, 64 << 20, [10]),
    (10, 100, 250, [2, 2, 2, 2, 2]),
    (3, 100, 10, [1, 1, 1]),  # a row wider than a piece is a piece
])
def test_piece_rows_cuts_even_bands_under_the_bound(rows, row_bytes, piece_bytes, want):
    bands = ckpt.piece_rows(rows, row_bytes, piece_bytes)
    assert [hi - lo for lo, hi in bands] == want
    assert bands[0][0] == 0 and bands[-1][1] == rows
    assert all(a[1] == b[0] for a, b in zip(bands, bands[1:]))


class _FakePiece:
    """A device array that counts what is asked for and not fetched."""

    flying = 0
    most = 0
    log = []

    def __init__(self, k, nbytes):
        self.k, self.nbytes, self.asked = k, nbytes, False

    def copy_to_host_async(self):
        assert not self.asked
        self.asked = True
        cls = _FakePiece
        cls.flying += self.nbytes
        cls.most = max(cls.most, cls.flying)
        cls.log.append(("ask", self.k))

    def __array__(self, dtype=None, copy=None):
        assert self.asked, "fetched before its copy was asked for"
        _FakePiece.flying -= self.nbytes
        _FakePiece.log.append(("fetch", self.k))
        return np.full(2, self.k)


@pytest.mark.parametrize("ahead, most", [
    (None, 600), (250, 200), (200, 200), (199, 100), (50, 100)])
def test_copies_to_the_host_stay_under_ahead_bytes(ahead, most):
    """At most ``ahead_bytes`` asked for and not yet fetched, the
    oldest piece's always; asked and fetched oldest first; every piece
    arrives, in order, and the list handed over is taken apart."""
    _FakePiece.flying = _FakePiece.most = 0
    _FakePiece.log = []
    pieces = [(f"p{k}", _FakePiece(k, 100)) for k in range(6)]
    got = [(name, int(host[0])) for name, host in ckpt.to_host(pieces, ahead)]
    assert got == [(f"p{k}", k) for k in range(6)] and pieces == []
    assert _FakePiece.most == most and _FakePiece.flying == 0
    for kind in ("ask", "fetch"):
        assert [k for what, k in _FakePiece.log if what == kind] == list(range(6))


@pytest.mark.parametrize("ahead, most", [(None, 5), (2000, 2), (900, 1)])
def test_a_restores_copies_to_the_device_stay_under_ahead_bytes(
        tmp_path, monkeypatch, ahead, most):
    whole = np.arange(5 * 200, dtype=np.float32).reshape(50, 20)
    np.save(tmp_path / "a.npy", whole)
    bands = [(10 * k, 10 * k + 10) for k in range(5)]  # 800 bytes each
    flying, seen = [], []
    put, done = jax.device_put, jax.block_until_ready

    def counting_put(host, sharding):
        flying.append(host.nbytes)
        seen.append(len(flying))
        return put(host, sharding)

    def counting_done(x):
        flying.pop(0)
        return done(x)

    monkeypatch.setattr(ckpt.jax, "device_put", counting_put)
    monkeypatch.setattr(ckpt.jax, "block_until_ready", counting_done)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    arrays, read_s, to_device_s = ckpt.read_pieces(
        tmp_path / "a.npy", bands, sharding, ahead)
    for (lo, hi), a in zip(bands, arrays):
        np.testing.assert_array_equal(np.asarray(a), whole[lo:hi])
    assert max(seen) == most and read_s > 0 and to_device_s > 0


def test_a_saves_pieces_land_in_one_file_an_array_whatever_their_order(tmp_path):
    """Two writers share a file by position: bands written out of
    order, in calls smaller than a band, make the array ``numpy.load``
    reads; and a save of host arrays goes through the same path."""
    whole = np.arange(7 * 5, dtype=np.float32).reshape(7, 5)
    fd, start = ckpt.begin_npy(tmp_path / "a.npy", whole.shape, whole.dtype)
    bounce = np.empty(16, np.uint8)
    for lo, hi in ((4, 7), (0, 2), (2, 4)):
        ckpt.write_at(fd, start + lo * 5 * 4, whole[lo:hi], bounce)
    __import__("os").close(fd)
    np.testing.assert_array_equal(np.load(tmp_path / "a.npy"), whole)

    series = ckpt.Series(tmp_path / "run", keep=1)
    for step in (3, 4):
        pieces = [(("a.npy", lo), _Host(whole[lo:hi] + step))
                  for lo, hi in ((0, 3), (3, 7))]
        ckpt.Save(series, step, {"step": step},
                  {"a.npy": (whole.shape, whole.dtype)}, pieces, ahead_bytes=64).wait()
    # the save that went stands by as the next one's files, until cleaned
    assert series.steps() == [4] and len(series.leftovers()) == 1
    assert series.clean() == 1 and series.steps() == [4]
    np.testing.assert_array_equal(np.load(series.path(4) / "a.npy"), whole + 4)
    assert series.manifest(4) == {"step": 4}


class _Host:
    """A piece that is on the host already."""

    def __init__(self, array):
        self.array, self.nbytes = array, array.nbytes

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return self.array


def test_a_failed_save_is_raised_to_whoever_waits_and_commits_nothing(tmp_path):
    series = ckpt.Series(tmp_path / "run")

    class Broken(_FakePiece):
        def __array__(self, dtype=None, copy=None):
            raise OSError("the copy failed")

    save = ckpt.Save(series, 7, {"step": 7}, {"a.npy": ((2,), np.float32)},
                     [(("a.npy", 0), Broken(0, 8))])
    with pytest.raises(RuntimeError, match="save of step 7 failed") as info:
        save.wait()
    assert isinstance(info.value.__cause__, OSError) and not save.committed
    assert series.steps() == [] and series.clean() == 1


def test_a_save_and_a_restore_keep_their_spans_in_the_recorder_they_are_given(tmp_path):
    """A save on its own (no job): its record's times are its spans',
    its writes are caused by its ``checkpoint/save``, that by whoever
    handed it over; a failed save commits no span of a commit; and a
    restore's seconds are the sums of its bands' spans."""
    from mpi4jax_tpu.utils.spans import Recorder

    whole = np.arange(8 * 5, dtype=np.float32).reshape(8, 5)
    series, trace = ckpt.Series(tmp_path / "run", keep=1), Recorder("t.")
    pieces = [(("a.npy", lo), _Host(whole[lo:lo + 2])) for lo in (0, 2, 4, 6)]
    with trace.span("caller") as caller:
        save = ckpt.Save(series, 9, {"step": 9}, {"a.npy": (whole.shape, whole.dtype)},
                         pieces, ahead_bytes=64, trace=trace, cause=caller.id)
    record = save.wait()
    by_name = {}
    for s in trace.spans():
        by_name.setdefault(s.name, []).append(s)
    assert sorted(by_name) == [
        "caller", "checkpoint/close", "checkpoint/commit", "checkpoint/fetch",
        "checkpoint/manifest", "checkpoint/prune", "checkpoint/rename",
        "checkpoint/save", "checkpoint/write"]
    (saved,) = by_name["checkpoint/save"]
    assert saved.cause == caller.id and saved.key == 9 and saved.counts == {"bytes": 160}
    assert record["commit_s"] == saved.seconds
    fetches, writes = by_name["checkpoint/fetch"], by_name["checkpoint/write"]
    assert record["stage_s"] == (fetches[-1].end_ns - saved.start_ns) / 1e9
    assert len(fetches) == len(writes) == 4
    assert all(s.key == 9 and s.cause == saved.id and s.counts == {"bytes": 40}
               for s in fetches + writes)
    assert {s.thread for s in fetches} == {"checkpoint-save"} == {saved.thread}
    assert {s.thread for s in writes} <= {"checkpoint-write-0", "checkpoint-write-1"}
    assert all(saved.start_ns <= s.start_ns <= s.end_ns <= saved.end_ns
               for s in fetches + writes + by_name["checkpoint/commit"])
    # which of a commit's calls stands, the day one does: the files'
    # descriptors closed, then the manifest and the rename, each a span
    (commit,), (closed,) = by_name["checkpoint/commit"], by_name["checkpoint/close"]
    (wrote,), (renamed,) = by_name["checkpoint/manifest"], by_name["checkpoint/rename"]
    assert closed.cause == saved.id and wrote.cause == renamed.cause == commit.id
    assert {s.key for s in (closed, wrote, renamed)} == {9}
    assert (writes[-1].end_ns <= closed.start_ns <= closed.end_ns <= commit.start_ns
            <= wrote.start_ns <= wrote.end_ns <= renamed.start_ns
            <= renamed.end_ns <= commit.end_ns)
    # a save that is given no recorder keeps one of its own
    ckpt.Save(series, 10, {"step": 10}, {"a.npy": (whole.shape, whole.dtype)},
              [(("a.npy", 0), _Host(whole))]).wait()
    assert len(trace.spans()) == 15

    class Broken(_FakePiece):
        def __array__(self, dtype=None, copy=None):
            raise OSError("the copy failed")

    failed = Recorder()
    with pytest.raises(RuntimeError):
        ckpt.Save(series, 11, {"step": 11}, {"a.npy": ((2,), np.float32)},
                  [(("a.npy", 0), Broken(0, 8))], trace=failed).wait()
    assert [s.name for s in failed.spans()] == [
        "checkpoint/fetch", "checkpoint/close", "checkpoint/save"]

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    arrays, read_s, to_device_s = ckpt.read_pieces(
        series.path(10) / "a.npy", [(0, 3), (3, 8)], sharding, 64, trace=trace, key=10)
    np.testing.assert_array_equal(np.concatenate([np.asarray(a) for a in arrays]), whole)
    reads = [s for s in trace.spans() if s.name == "checkpoint/read"]
    sent = [s for s in trace.spans() if s.name == "checkpoint/to_device"]
    assert [s.counts["bytes"] for s in reads] == [60, 100] == [
        s.counts["bytes"] for s in sent]
    assert read_s == sum(s.seconds for s in reads) > 0
    assert to_device_s == sum(s.seconds for s in sent) > 0
    assert {s.key for s in reads + sent} == {10}


def test_to_host_enters_the_callers_span_round_each_fetch():
    _FakePiece.flying = _FakePiece.most = 0
    _FakePiece.log = []
    pieces = [(f"p{k}", _FakePiece(k, 100)) for k in range(3)]
    seen = []

    class Round:
        def __init__(self, piece):
            self.k = piece.k

        def __enter__(self):
            seen.append(("in", self.k, len(_FakePiece.log)))

        def __exit__(self, *exc):
            seen.append(("out", self.k, _FakePiece.log[-1]))

    assert [name for name, _ in ckpt.to_host(pieces, 100, span=Round)] == ["p0", "p1", "p2"]
    # entered after the asks, left once the piece is fetched
    assert [(what, k) for what, k, _ in seen] == [
        (what, k) for k in range(3) for what in ("in", "out")]
    assert all(last == ("fetch", k) for what, k, last in seen if what == "out")


_KILLED_CHILD = '''
import sys, time
import numpy as np
import jax
import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.utils import checkpoint as ckpt

directory, out, t1 = sys.argv[1], sys.argv[2], float(sys.argv[3])
slow = float(sys.argv[4])
write = ckpt.write_at
ckpt.write_at = lambda *a: (time.sleep(slow), write(*a))  # a slow disk
mesh = jax.make_mesh((2, 2), ("y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
solve = sw.make_solver(cfg, m.MeshComm.from_mesh(mesh), num_multisteps=5,
                       checkpoint_dir=directory)
state, _, steps = solve(t1)
np.savez(out, steps=steps, **{k: np.asarray(a) for k, a in state._asdict().items()})
'''


def test_a_killed_run_resumes_from_its_last_acknowledged_save(tmp_path):
    """A real kill: a child process running ``make_solver(checkpoint_dir=
    ...)`` is sent SIGKILL after its second acknowledged save and while
    a third is being written; a second child resumes in the directory;
    the end state is the uninterrupted run's bit for bit and the
    directory holds no temporary."""
    import os
    import signal
    import subprocess
    import sys
    import time

    from mpi4jax_tpu.models import shallow_water as sw

    cfg = sw.SWConfig(ny=16, nx=32, ghost=2)
    t1 = cfg.dt * (1 + 5) + cfg.dt * 5 * 7  # the warm-up chunk and seven more
    ck, script = tmp_path / "run", tmp_path / "child.py"
    script.write_text(_KILLED_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(pathlib.Path(__file__).resolve().parents[1])]
                   + sys.path))

    def child(out, slow):
        return subprocess.Popen(
            [sys.executable, str(script), str(ck), str(out), repr(t1), str(slow)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    series = ckpt.Series(ck)
    first = child(tmp_path / "killed.npz", 0.05)
    deadline = time.time() + 120
    seen = set()  # the saves acknowledged so far (the oldest goes as a new one starts)
    try:
        while True:
            committed, left = series.steps(), series.leftovers()
            seen.update(committed)
            if len(seen) >= 2 and committed and left:
                first.send_signal(signal.SIGKILL)
                break
            assert first.poll() is None, first.stdout.read()
            assert time.time() < deadline, "no third save within 120 s"
            time.sleep(0.002)
    finally:
        first.kill()
    first.wait()
    assert first.returncode == -signal.SIGKILL
    assert not (tmp_path / "killed.npz").exists()
    # what the kill left: an acknowledged save or two and half a save
    committed = series.steps()
    assert committed and series.leftovers()
    assert series.latest() < 1 + 5 * 8

    second = child(tmp_path / "resumed.npz", 0)
    assert second.wait(timeout=120) == 0, second.stdout.read()
    resumed = np.load(tmp_path / "resumed.npz")
    assert int(resumed["steps"]) == 1 + 5 * 8 - committed[-1]
    assert not series.leftovers() and series.latest() == 1 + 5 * 8

    mesh = jax.make_mesh((2, 2), ("y", "x"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    whole, _, _ = sw.make_solver(cfg, m.MeshComm.from_mesh(mesh), num_multisteps=5)(t1)
    for name, want in whole._asdict().items():
        np.testing.assert_array_equal(resumed[name], np.asarray(want), err_msg=name)


# -- one bound on every kind of copy to the host ------------------------------


def test_a_host_bound_counts_every_kind_and_lets_an_oversized_copy_go_alone():
    bound = ckpt.HostBound(1000)
    assert bound.take("snapshot", 600) and not bound.take("save", 500)
    assert bound.take("save", 400) and bound.in_flight == bound.peak == 1000
    assert not bound.take("snapshot", 1) and bound.holder("snapshot") == "save"
    bound.give("snapshot", 600)
    bound.give("save", 400)
    # alone over the bound: only with nothing of either kind in flight
    assert bound.take("save", 10) and not bound.take("snapshot", 5000)
    bound.give("save", 10)
    assert bound.take("snapshot", 5000) and not bound.take("save", 1)
    assert bound.peak == 5000 and bound.in_flight == 5000
    bound.give("snapshot", 5000)
    # no bound: whatever is asked
    free = ckpt.HostBound()
    assert free.take("save", 1 << 40) and free.take("snapshot", 1 << 40)


def test_a_wait_for_room_is_served_before_later_copies_and_is_a_span():
    """The save's thread, none of its pieces in flight, waits for room
    that snapshots hold: a snapshot asked for meanwhile is refused, the
    owner that waits in ``until`` hears of it, and the wait is recorded
    with what held the copy back."""
    from mpi4jax_tpu.utils.spans import Recorder

    trace = Recorder()
    bound = ckpt.HostBound(1000)
    assert bound.take("snapshot", 800)
    side = ckpt.Side(bound, "save", functools.partial(trace.span, "checkpoint/fetch_wait", key=7))
    thread = threading.Thread(target=side.take, args=(400, True), daemon=True)
    thread.start()
    assert bound.until(lambda: False, "snapshot") is False  # the save waits for a snapshot's room
    assert thread.is_alive() and not bound.take("snapshot", 100)
    bound.give("snapshot", 800)
    thread.join(30)
    assert not thread.is_alive() and side.held == 400 and bound.in_flight == 400
    (waited,) = trace.spans()
    assert waited.name == "checkpoint/fetch_wait" and waited.key == 7
    assert waited.counts == {"held_by": "snapshot", "bytes": 400}
    assert side.waited_s == waited.seconds > 0
    assert bound.take("snapshot", 100)  # nobody waits any more
    side.close()
    assert side.held == 0 and bound.in_flight == 100
    # `until` returns once its caller's own condition holds, after a wake
    done = threading.Event()
    threading.Timer(0.05, lambda: (done.set(), bound.wake())).start()
    assert bound.until(done.is_set, "snapshot") is True


@pytest.mark.parametrize("most, at_once", [(None, 3), (1000, 3), (250, 2), (150, 1)])
def test_to_host_keeps_its_pieces_under_the_bound_it_shares(most, at_once):
    """``to_host(side=)``: beside another kind's 50 bytes the pieces go
    as far as both bounds let them, the oldest always."""
    _FakePiece.flying = _FakePiece.most = 0
    _FakePiece.log = []
    bound = ckpt.HostBound(most)
    assert bound.take("snapshot", 50)
    side = ckpt.Side(bound, "save", None)
    pieces = [(f"p{k}", _FakePiece(k, 100)) for k in range(6)]
    got = [name for name, _ in ckpt.to_host(pieces, 300, side=side)]
    assert got == [f"p{k}" for k in range(6)]
    assert _FakePiece.most == 100 * at_once and bound.peak == 50 + 100 * at_once
    assert bound.in_flight == 50 and side.held == 0
