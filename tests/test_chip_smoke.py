"""chip_smoke.py's check functions at a tiny size on the CPU devices.

The script has no rehearsal option: what it runs on the chip at the real
size is steered here, so a wrong path, argument or comparison is found
without chip time.  The chip run itself is `python chip_smoke.py`
(through the chip tool); nothing here is a device result.
"""

import ast
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
try:
    import chip_smoke
finally:
    sys.path.pop(0)

from mpi4jax_tpu.models import shallow_water as sw  # noqa: E402
from mpi4jax_tpu.models import transformer as tfm  # noqa: E402

# tests/test_bench_smoke.py's TINY, as build() keywords
TINY = dict(batch=1, seq=64, layers=2, d_model=64, heads=4, kv_heads=4,
            d_ff=128)
SW_TINY = sw.SWConfig(ny=24, nx=48)


def test_solver_check_tiny():
    cpu = jax.devices("cpu")
    out = chip_smoke.solver_check(SW_TINY, cpu[:1], cpu[1], steps_per_call=5)
    assert out["schedules_max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC
    # the same backend on another device: the reference path itself
    assert out["reference_max_diff"] == 0.0


def test_solver_four_device_checks_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    weak = chip_smoke.solver_weak_check(cfg, cpu, steps_per_call=5)
    assert "48x96 on 2x2" in weak["compared"]
    inv = chip_smoke.solver_invariance_check(cfg, cpu, steps_per_call=5)
    assert inv["max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC


def test_solver_job_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_job_check(
        cfg, cpu, mesh_shapes=((2, 2), (1, 1)), steps_per_call=5, calls=3)
    assert out["last_snapshot_max_diff"] <= chip_smoke.TOL_BLOCK_MEAN
    assert 0 < out["meshes_max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC
    assert "on 2x2 and 1x1" in out["compared"]


def test_solver_restart_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_restart_check(
        cfg, cpu, mesh_shapes=((2, 2), (1, 1)), steps_per_call=5, calls=3)
    assert 0 < out["meshes_max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC
    assert out["save_bytes"] == 3 * (28 * 52 + 24 * 48) * 4
    assert "dropped after 3 and resumed" in out["compared"]


def test_solver_monitor_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_monitor_check(
        cfg, cpu, mesh_shapes=((2, 2), (1, 1)), steps_per_call=5, calls=3)
    # the NaN is in the state from the fourth call on (step 21), and the
    # job stops by that call's line with `lag` more calls enqueued
    assert out["stops"] == {
        shape: {"step": 21, "calls_enqueued": 3, "nonfinite": out["stops"]["2x2"]["nonfinite"]}
        for shape in ("2x2", "1x1")}
    assert out["stops"]["2x2"]["nonfinite"] > 0
    assert 0 < out["meshes_max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC
    assert "solver.monitor" in chip_smoke.GROUPS[1]["solver"][1]
    assert "solver4.monitor" in chip_smoke.GROUPS[4]["solver4"][1]


def test_solver_adjoint_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_adjoint_check(cfg, cpu, calls=2, steps_per_call=3)
    assert set(out["rel_l2"]) == {"h", "u", "v"}
    assert 0 <= max(out["rel_l2"].values()) <= 1e-4
    assert out["cost"][0] == pytest.approx(out["cost"][1], rel=1e-5)
    assert "window of 7 steps" in out["compared"]
    assert "solver4.adjoint" in chip_smoke.GROUPS[4]["solver4.adjoint"][1]
    # the chip's run is at the domain refined once: 1800x3600 cells a chip
    fine = chip_smoke._refined(sw.SWConfig().bench_size(), 2)
    assert (fine.ny, fine.nx, fine.dx, fine.dy) == (3600, 7200, 2500.0, 2500.0)
    assert fine.dt == pytest.approx(sw.SWConfig().bench_size().dt / 2)
    # a decomposition that changed the gradient is refused
    with pytest.raises(AssertionError, match="changes the gradient"):
        chip_smoke.solver_adjoint_check(
            cfg, cpu, calls=2, steps_per_call=3, tol=0.0)


def test_solver_tangent_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_tangent_check(cfg, cpu, cpu, calls=2, steps_per_call=3)
    assert set(out["rel_l2"]) == {"tangent", "h", "u", "v"}
    assert 0 < max(out["rel_l2"].values()) <= 2e-4
    assert out["adjoint_test"][2] < 1e-5
    assert "window of 7 steps" in out["compared"] and "2x2 on cpu" in out["compared"]
    one = chip_smoke.solver_tangent_check(
        cfg, cpu[:1], cpu, calls=1, steps_per_call=2)
    assert "1x1 on cpu" in one["compared"] and max(one["rel_l2"].values()) == 0
    assert "solver.tangent" in chip_smoke.GROUPS[1]["solver.tangent"][1]
    assert "solver4.tangent" in chip_smoke.GROUPS[4]["solver4.tangent"][1]
    small = chip_smoke._small_cfg()
    assert (small.ny, small.nx, small.ghost) == (256, 512, 2)
    # a product that is not the reference's is refused
    with pytest.raises(AssertionError, match="not its reference's"):
        chip_smoke.solver_tangent_check(
            cfg, cpu, cpu, calls=2, steps_per_call=3, tol=0.0)


def test_solver_observed_check_tiny(monkeypatch):
    cpu = jax.devices("cpu")
    out = chip_smoke.solver_observed_check([(24, 48), (16, 20)], cpu)
    assert out["wrong_cells"] == {"24x48": 0, "16x20": 0}
    assert "solver.observed" in chip_smoke.GROUPS[1]["solver.observed"][1]
    # a transpose that is not numpy's is refused
    monkeypatch.setattr(
        sw, "_spread",
        lambda coarse, ghost, c: jax.numpy.zeros(
            (c * coarse.shape[0] + 2 * ghost, c * coarse.shape[1] + 2 * ghost),
            coarse.dtype))
    with pytest.raises(AssertionError, match="not numpy's"):
        chip_smoke.solver_observed_check([(24, 48)], cpu)


def test_solver_output_restart_check_tiny():
    cpu = jax.devices("cpu")
    cfg = sw.SWConfig(ny=24, nx=48, ghost=2)
    out = chip_smoke.solver_output_restart_check(
        cfg, cpu, mesh_shapes=((2, 2), (1, 1)), coarsen=2, steps_per_call=5, calls=3)
    one = 3 * 12 * 24 * 4
    assert out["host_bound_bytes"] == 2 * one + one // 2
    assert 2 * one <= out["host_in_flight_max_bytes"] <= out["host_bound_bytes"]
    assert 0 < out["meshes_max_diff"] <= chip_smoke.TOL_SAME_ARITHMETIC
    assert "dropped after 3 and resumed" in out["compared"]
    assert "solver4.output_restart" in chip_smoke.GROUPS[4]["solver4"][1]


@pytest.mark.parametrize("n", [1, 4, 8])
def test_ops_check(n):
    assert chip_smoke.ops_check(jax.devices()[:n])["max_diff"] == 0.0


def test_ops_check_catches_a_wrong_value(monkeypatch):
    import mpi4jax_tpu as m

    real = m.bcast
    monkeypatch.setattr(
        m, "bcast", lambda x, root, **kw: real(x + 1, root, **kw)
    )
    with pytest.raises(AssertionError, match="bcast"):
        chip_smoke.ops_check(jax.devices()[:4])


def test_cpu_child_check_on_the_cpu_backend():
    # here the parent holds the CPU backend; on the chip it holds the TPU
    out = chip_smoke.cpu_child_check()
    assert out["max_diff"] == 0.0
    assert "8 virtual CPU devices" in out["compared"]


def test_cpu_child_check_catches_a_wrong_value(monkeypatch):
    wrong = chip_smoke.CPU_CHILD.replace(
        "m.allreduce(v, m.SUM", "m.allreduce(v + 1, m.SUM"
    )
    assert wrong != chip_smoke.CPU_CHILD
    monkeypatch.setattr(chip_smoke, "CPU_CHILD", wrong)
    with pytest.raises(AssertionError, match="max_diff=8.0"):
        chip_smoke.cpu_child_check()


def test_no_phase_record_carries_a_rate():
    # every key of every phase's record is a string in the source; a
    # rate printed here would be a number under no harness
    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    strings = [
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Constant) and isinstance(node.value, str)
    ]
    assert len(strings) > 100  # f-string parts included
    assert [x for x in strings if x.startswith("smoke_")] == []
    assert [x for x in strings if x.endswith(("_per_s", "_per_sec"))] == []


def test_grad_and_selfcomm_checks():
    assert chip_smoke.grad_check(jax.devices())["max_diff"] == 0.0
    assert chip_smoke.selfcomm_check()["max_diff"] == 0.0


@pytest.mark.parametrize("n", [1, 4])
def test_rendezvous_check(n):
    assert chip_smoke.rendezvous_check(jax.devices()[:n])["max_diff"] == 0.0


def test_train_check_tiny_falls_and_halves_a_refused_batch(monkeypatch):
    out = chip_smoke.train_falls_check(
        TINY, jax.devices()[:1], vocab=256, attn_impl="xla",
        expect_kernel=False,
    )
    assert out["batch"] == 1 and out["mesh"] == [1, 1, 1]
    assert out["losses"][-1] < out["losses"][0]

    # a compiler that refuses batch 4 sends the check to batch 2
    from benchmarks import transformer as tb

    real_build = tb.build

    def build(**kw):
        built = real_build(**kw)
        if kw["batch"] == 4:
            def refuse(*a, **k):
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: Used 22.07G of 15.75G hbm"
                )
            built.step = type("S", (), {"lower": staticmethod(refuse)})
        return built

    monkeypatch.setattr(tb, "build", build)
    out = chip_smoke.train_check(
        dict(TINY, batch=4), jax.devices()[:1], vocab=256,
        attn_impl="xla", expect_kernel=False, steps=1,
    )
    assert out["batch"] == 2
    assert out["refused"][0]["batch"] == 4


def test_train_check_reports_a_quiet_dense_path():
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.train_check(
            TINY, jax.devices()[:1], vocab=256, attn_impl="xla", steps=1
        )


def test_train_sharded_check_tiny():
    out = chip_smoke.train_sharded_check(
        TINY, jax.devices()[:4], vocab=256, attn_impl="xla",
        expect_kernel=False,
    )
    assert out["four_chips"]["mesh"] == [1, 2, 2]
    assert out["one_chip"]["global_tokens"] == [1, 128]
    assert out["max_diff"] <= chip_smoke.TOL_SHARDED_LOSS


def test_flash_check_interpret():
    out = chip_smoke.flash_check((1, 128, 2, 64), interpret=True, blocks=64)
    assert out["max_diff"] <= 3e-2 and out["max_rel_diff_grad"] <= 3e-2
    # interpret mode keeps float32 dots exact; the chip does not
    assert out["max_diff_f32_inputs"] <= 2e-5


def test_decode_check_tiny():
    cfg = tfm.TransformerConfig(
        vocab=32, d_model=16, layers=2, heads=4, kv_heads=2, head_dim=8,
        d_ff=32,
    )
    out = chip_smoke.decode_check(
        cfg, jax.devices(), batch=4, prompt=5, max_len=14,
        prefill_impl="xla",
    )
    assert out["max_diff"] == 0


def test_staged_check_on_the_cpu_staging_path(monkeypatch):
    # the launcher worker's io_callback tier, forced on the CPU; the
    # child pins its own platform whatever this process uses
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = chip_smoke.staged_check(
        "cpu", env={"MPI4JAX_TPU_FORCE_STAGED": "1"}
    )
    assert out["max_diff"] == 0.0
    with pytest.raises(AssertionError, match="staged ok platform=cpu"):
        chip_smoke.staged_check("tpu")


def test_run_phase_line(capsys):
    assert chip_smoke.run_phase("demo", lambda: {"max_diff": 0.0}) is True
    assert chip_smoke.run_phase("bad", lambda: 1 / 0) is False
    good, bad = map(json.loads, capsys.readouterr().out.splitlines())
    assert good["phase"] == "demo" and good["ok"] is True
    assert {"wall_s", "compile_s", "cache"} <= set(good)
    assert bad["ok"] is False and "ZeroDivisionError" in bad["error"]


def test_a_phase_line_counts_what_the_phase_compiled_from_the_librarys_record(
        capsys, monkeypatch):
    """``compile_s`` and ``cache`` keep their names and come from the
    process's build spans (``utils/spans.py``): the file registers no
    listener of its own, and a phase is charged what it built, not what
    the phase before it did."""
    import inspect

    import jax
    import jax.numpy as jnp

    from mpi4jax_tpu.utils import spans

    # a test worker's recorder may be full and letting its oldest spans go
    monkeypatch.setattr(spans, "builds", spans.Recorder("mpi4jax_tpu."))

    def builds_one():
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(3.0)).block_until_ready()
        return {"built": 1}

    assert chip_smoke.run_phase("builds", builds_one) is True
    assert chip_smoke.run_phase("idle", lambda: {}) is True
    built, idle = map(json.loads, capsys.readouterr().out.splitlines())
    assert built["compile_s"] > 0 and set(built["cache"]) == {"requests", "hits", "writes"}
    assert all(isinstance(n, int) for n in built["cache"].values())
    assert built["cache"]["hits"] <= built["cache"]["requests"]
    assert idle["compile_s"] == 0 and idle["cache"] == {"requests": 0, "hits": 0, "writes": 0}
    assert list(built)[-3:] == ["wall_s", "compile_s", "cache"]
    # the sum the line prints is the spans' own
    after = spans.builds.spans()[-1].end_ns + 1
    assert chip_smoke._compiled_since(after) == (0, {"requests": 0, "hits": 0, "writes": 0})
    assert "register_event" not in inspect.getsource(chip_smoke)


def test_a_phase_line_says_when_the_record_of_builds_let_spans_go(monkeypatch):
    from mpi4jax_tpu.utils import spans

    monkeypatch.setattr(spans, "builds", spans.Recorder("mpi4jax_tpu."))
    spans.builds.dropped = 3
    _, cache = chip_smoke._compiled_since(0)
    assert cache == {"requests": 0, "hits": 0, "writes": 0, "dropped": 3}


def test_main_without_a_tpu_exits_nonzero_and_runs_no_phase(
    monkeypatch, capsys
):
    ran = []
    real = chip_smoke._run_child

    def run_child(group, deadline):
        ran.append(group)
        return real(group, deadline)

    monkeypatch.setattr(chip_smoke, "_run_child", run_child)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chip_smoke.main([]) == 2
    assert ran == ["probe"]
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err and "'cpu'" in captured.err


def test_main_reports_a_missing_phase_record(monkeypatch, capsys):
    def run_child(group, deadline):
        if group == "probe":
            return 0, json.dumps({"phase": "probe", "device": {
                "platform": "tpu", "kind": "TPU v5 lite", "count": 1}})
        if group == "rendezvous":
            return None, ""  # killed at its deadline
        return 0, "\n".join(
            json.dumps({"phase": name, "ok": True})
            for name in chip_smoke.GROUPS[1][group][1]
        )

    monkeypatch.setattr(chip_smoke, "_run_child", run_child)
    assert chip_smoke.main([]) == 1
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": False, "failed": ["rendezvous"]}
    assert any(
        x.get("phase") == "rendezvous" and "deadline" in x["error"]
        for x in lines
    )


def test_main_ok_line_is_last_and_exact(monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}

    def run_child(group, deadline):
        if group == "probe":
            return 0, json.dumps({"phase": "probe", "device": device})
        return 0, "\n".join(
            json.dumps({"phase": name, "ok": True})
            for name in chip_smoke.GROUPS[4][group][1]
        )

    monkeypatch.setattr(chip_smoke, "_run_child", run_child)
    assert chip_smoke.main(["--chips", "4"]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}
    assert np.all([k in last for k in ('"ok": true', '"count": 4')])
