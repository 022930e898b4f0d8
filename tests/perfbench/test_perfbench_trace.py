"""The trace reduction, on a small trace recorded on the chip (see
``perfbench/testdata/README.md``) and on made-up events."""

import pytest

from perfbench.harness import files, trace
from perfbench.harness.trace import Event, Trace

RECORDED = files.BENCH_DIR / "testdata" / "solver-1chip.xplane.pb"
CHIP = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    return trace.read_xplane(str(RECORDED), ("enqueue", "sync"))


def test_recorded_planes_and_spans(recorded):
    assert list(recorded.device_ops) == [CHIP]
    assert len(recorded.modules[CHIP]) == 8  # 3 + 5 calls
    assert {k: len(v) for k, v in recorded.host.items()} == {
        "traced_window": 1, "enqueue": 2, "sync": 2}


def test_recorded_busy_window_idle(recorded):
    # read by hand from the trace: the eight programs took 81.543333 ms,
    # the host's span round them 83.915878 ms
    programs = sum(e.duration_ns for e in recorded.modules[CHIP]) / 1e9
    assert programs == pytest.approx(0.081543333)
    assert trace.window_s(recorded) == pytest.approx(0.083915878)
    busy = trace.busy_s(recorded)
    assert busy == pytest.approx(0.081230706)
    # operations run inside programs, and fill nearly all of them
    assert 0.99 * programs < busy <= programs
    assert trace.idle_share(recorded) == pytest.approx(
        100 * (1 - 0.081230706 / 0.083915878))


def test_recorded_ops_per_step_and_top_ops(recorded):
    # the while loops that span their bodies are no operations of their own
    assert trace.op_count(recorded) == 5585
    assert not any(trace.short_name(e.name).startswith("while")
                   for e in recorded.device_ops[CHIP])
    top = trace.op_seconds(recorded)
    assert top[0][0] == "slice_add_fusion.10"
    assert top[0][1] == pytest.approx(0.020165434)
    assert sum(v for _, v in top) == pytest.approx(trace.busy_s(recorded), rel=1e-3)


def test_recorded_breakdown_names_the_gaps(recorded):
    b = trace.breakdown(recorded, ("enqueue", "sync"))
    assert len(b["device_ops"]) == 10 and 1 <= len(b["idle_gaps"]) <= 10
    name, seconds = b["idle_gaps"][0]
    # the longest gap: the host syncs on the large calls, then enqueues the small
    assert name in ("enqueue", "sync") and seconds == pytest.approx(0.001239282)


def _ev(name, start, dur):
    return Event(name, float(start), float(dur))


def test_leaves_drop_the_spanning_events():
    events = [_ev("%while.1 = () while()", 0, 100), _ev("%a = f32[] fusion()", 0, 40),
              _ev("%call.2 = () call()", 50, 50), _ev("%b = f32[] fusion()", 50, 20),
              _ev("%c = f32[] copy()", 70, 30), _ev("%d = f32[] fusion()", 120, 10)]
    assert sorted(trace.short_name(e.name) for e in trace.leaves(events)) == [
        "a", "b", "c", "d"]


def test_union_gaps_and_idle_on_made_up_events():
    made = Trace(
        device_ops={"/device:TPU:0": [_ev("%a = x()", 0, 40), _ev("%b = x()", 30, 30),
                                      _ev("%c = x()", 80, 20)],
                    "/device:TPU:1": [_ev("%a = x()", 0, 50)]},
        host={"traced_window": [_ev("traced_window", 0, 100)],
              "enqueue": [_ev("enqueue", 0, 5)], "sync": [_ev("sync", 55, 30)]})
    assert trace.union_ns(made.device_ops["/device:TPU:0"]) == 80
    assert trace.gaps(made.device_ops["/device:TPU:0"]) == [(60, 80)]
    assert trace.busy_s(made) == pytest.approx(65e-9)  # mean of 80 and 50
    assert trace.idle_share(made) == pytest.approx(35.0)
    # the clocks meet where the first operation and the first span start
    assert trace.idle_gaps(made, ("enqueue", "sync")) == [
        ["sync", pytest.approx(20e-9)]]


def test_scope_seconds_reads_op_name_from_the_program_text():
    hlo = '''
  %all-reduce.3 = f32[8]{0} all-reduce(%p), metadata={op_name="jit(f)/shard_map/mpi4jax_tpu.allreduce/psum" stack_frame_id=5}
  ROOT %fusion.1 = f32[8]{0} fusion(%all-reduce.3), kind=kLoop, metadata={op_name="jit(f)/shard_map/mul"}
  %collective-permute-done.2 = f32[2]{0} collective-permute-done(%s), metadata={op_name="jit(f)/mpi4jax_tpu.halo_exchange_2d/mpi4jax_tpu.sendrecv/ppermute"}
'''
    made = Trace(device_ops={CHIP: [
        _ev("%all-reduce.3 = f32[8]{0} all-reduce(%p)", 0, 3000),
        _ev("%fusion.1 = f32[8]{0} fusion(%all-reduce.3)", 3000, 1000),
        _ev("%collective-permute-done.2 = f32[2]{0} collective-permute-done(%s)", 4000, 500)]})
    assert trace.scope_seconds(made, [hlo]) == {
        "mpi4jax_tpu.allreduce": pytest.approx(3e-6),
        "mpi4jax_tpu.halo_exchange_2d": pytest.approx(0.5e-6)}


def test_a_trace_without_the_window_span_or_a_device_is_an_error():
    with pytest.raises(ValueError):
        trace.window_s(Trace())
    with pytest.raises(ValueError):
        trace.busy_s(Trace())
