"""A temporary copy of the benchmark with small cells added to it.

The cells, a configuration and a per-layer metric are added as new files
beside the copied ones, and as entries appended to ``BENCHMARK.json``:
no file the benchmark has is edited.  That is how a later PR adds its
own, and these tests show that the harness finds them.
"""

import argparse
import json
import pathlib
import re
import shutil
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]

TOY_METRIC = '''
"""A per-layer metric added as a new file: batches in the window."""


def read(view):
    return float(len(view.samples))
'''


def _coll_rows():
    rows = json.loads((ROOT / "perfbench/workloads/coll-2x2.json").read_text())["rows"]
    for row in rows:
        row["reps"] = 3
        if row["op"] == "halo":
            row["shape"] = [16, 28]
        elif row.get("sample_blocks"):
            row.update(bytes=4 * (1 << 18) * 4, sample_blocks=2)
        elif row["bytes"] > 8:
            row["bytes"] = 65536
    return rows


def make_copy(tmp_path):
    """``(root, bench_dir)`` of a copy with the cells ``sw-toy-1x1``,
    ``sw-toy-2x2`` (24x48 cells, the configuration ``shallow-water-toy``)
    and ``coll-toy`` (64 KiB rows on 2x2) added."""
    root = pathlib.Path(tmp_path) / "checkout"
    bench = root / "perfbench"
    shutil.copytree(ROOT / "perfbench", bench)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = json.loads((bench / "configs/shallow-water.json").read_text())
    config["name"] = "shallow-water-toy"
    config["check"].update(calls=2, row_blocks=1)
    (bench / "configs/shallow-water-toy.json").write_text(json.dumps(config))
    benchmark["configs"].append(dict(
        benchmark["configs"][0], name="shallow-water-toy",
        file="perfbench/configs/shallow-water-toy.json"))

    cells = {}
    for name, mesh in (("sw-toy-1x1", [1, 1]), ("sw-toy-2x2", [2, 2])):
        cells[name] = {
            "config": "shallow-water-toy", "traffic": name, "chips": len(mesh) ** 2 // 1 if mesh == [2, 2] else 1,
            "why": "a test cell", "mesh": mesh,
            "grid": {"ny": 24, "nx": 48, "refine": 1},
            "rows": [{"name": "multistep", "slots": 1, "reps": 2}],
        }
    coll = json.loads((bench / "workloads/coll-2x2.json").read_text())
    coll.update(traffic="coll-toy", why="a test cell", rows=_coll_rows())
    cells["coll-toy"] = coll
    for name, cell in cells.items():
        (bench / f"workloads/{name}.json").write_text(json.dumps(cell))
        benchmark["workloads"].append({
            k: cell[k] for k in ("config", "traffic", "chips", "why")
        } | {"name": name})

    (bench / "layer_metrics/toy_batches.py").write_text(TOY_METRIC)
    benchmark["per_layer"].append({
        "name": "toy_batches", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "setup_s",
        "workloads": ["sw-toy-1x1"]})
    for section in ("end_to_end", "per_layer"):
        for metric in benchmark[section]:
            listed = metric.get("workloads", [])
            if "sw-bench-1chip" in listed:
                listed += ["sw-toy-1x1", "sw-toy-2x2"]
            if "coll-2x2" in listed:
                listed += ["coll-toy"]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return root, bench


def cell_args(workload, seed=2**31 + 77, seconds=0.3, trace=0):
    return argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace)


# -- compiled texts made by hand ---------------------------------------
#
# The CPU's devices run the solver's array code: a text with a kernel
# call in it is made here, line for line as the TPU backend prints one
# (compiled for a described v5e, the kernel's serialized body cut short).

TABLES = f'''
FileNames
1 "{ROOT}/mpi4jax_tpu/models/shallow_water.py"
2 "{ROOT}/mpi4jax_tpu/models/sw_kernels.py"
3 "{ROOT}/mpi4jax_tpu/parallel/halo.py"

FunctionNames
1 "make_multistep.<locals>.local_fn"
2 "wide_step"
3 "_pack"

FileLocations
1 {{file_name_id=1 function_name_id=1 line=837 end_line=837 column=15 end_column=55}}
2 {{file_name_id=2 function_name_id=2 line=350 end_line=350 column=11 end_column=30}}
3 {{file_name_id=3 function_name_id=3 line=175 end_line=175 column=8 end_column=40}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}
2 {{file_location_id=2 parent_frame_id=2}}
3 {{file_location_id=3 parent_frame_id=2}}

'''
PACK = 'metadata={op_name="jit(local_fn)/while/body/closed_call/mpi4jax_tpu.halo_slabs_2d/pack/slice" stack_frame_id=3}'
KERNEL = ('custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, '
          'metadata={op_name="jit(local_fn)/while/body/closed_call/jit(wide_step)/pallas_call" '
          'stack_frame_id=2}, backend_config={"flag_configs":[],"custom_call_config":'
          '{"body":"TUzvUgFNTElS","needs_layout_passes":true},"aliasing_operands":{"lists":[]}}')
STATE = ("h", "u", "v", "dh", "du", "dv")


def multistep_text(rows=36, cols=68, coarse=None):
    """A multistep as the TPU backend compiles the solver's since PR 31:
    in its loop's body a fusion of two ``slice``s a written field (the
    sent slabs) and one kernel call, ``%wide_step.3``, that is handed two
    small operands, six fields and six slabs and hands six fields back.
    With ``coarse`` (a shape) the program has a second kernel call after
    the loop, ``%wide_step_out.4``, that hands three arrays of that shape
    back as well, and three fusions ``%finish.N`` that scale them."""
    F, S = f"f32[{rows},{cols}]{{1,0:T(8,128)}}", f"f32[{rows},2]{{1,0:T(8,128)S(1)}}"
    carried = ", ".join([F] * 6)
    body = [f"  %arg = (s32[]{{:T(128)}}, {carried}) parameter(0)",
            "  %one = s32[]{:T(128)} constant(1)",
            "  %coef = f32[3]{0:T(128)} constant({1, 2, 3})"]
    body += [f"  %{k} = {F} get-tuple-element(%arg), index={i + 1}"
             for i, k in enumerate(STATE)]
    for n, k in zip((6, 7, 8), "huv"):
        body.append(f"  %fusion.{n} = ({S}, {S}) fusion(%{k}), kind=kLoop, "
                    f"calls=%fused_computation, {PACK}")
        body += [f"  %{k}_{side} = {S} get-tuple-element(%fusion.{n}), index={i}, {PACK}"
                 for i, side in enumerate("we")]
    body.append("  %flags = s32[2]{0:T(128)S(1)} broadcast(%one), dimensions={}")
    handed = ("%flags, %coef, %h, %u, %v, /*index=5*/%h_w, %h_e, %u_w, %u_e, %v_w, "
              "/*index=10*/%v_e, %dh, %du, %dv")
    body.append(f"  %wide_step.3 = ({carried}) custom-call({handed}), {KERNEL}")
    body.append(f"  ROOT %tuple.9 = (s32[]{{:T(128)}}, {carried}) tuple(%one, "
                + ", ".join(f"%{k}" for k in STATE) + ")")
    entry = [f"  %state_{k}.1 = {F} parameter({i})" for i, k in enumerate(STATE)]
    results = [F] * 6
    if coarse:
        C = f"f32[{coarse[0]},{coarse[1]}]{{1,0:T(8,128)}}"
        entry += ["  %flags.1 = s32[2]{0:T(128)S(1)} constant({1, 1})",
                  "  %coef.1 = f32[3]{0:T(128)} constant({1, 2, 3})"]
        entry += [f"  %slab.{i} = {S} slice(%state_{k}.1), slice={{[0:{rows}], [2:4]}}, {PACK}"
                  for i, k in enumerate("hhuuvv")]
        entry.append(
            f"  %wide_step_out.4 = ({carried}, {C}, {C}, {C}) custom-call(%flags.1, "
            "%coef.1, %state_h.1, %state_u.1, %state_v.1, /*index=5*/%slab.0, %slab.1, "
            "%slab.2, %slab.3, %slab.4, /*index=10*/%slab.5, %state_dh.1, %state_du.1, "
            f"%state_dv.1), {KERNEL}")
        entry += [f"  %sum.{i} = {C} get-tuple-element(%wide_step_out.4), index={6 + i}"
                  for i in range(3)]
        entry += [f"  %finish.{i} = {C} fusion(%sum.{i}), kind=kLoop, calls=%scale, "
                  'metadata={op_name="jit(local_fn)/mpi4jax_tpu.snapshot/coarsen/mul" '
                  "stack_frame_id=1}" for i in range(3)]
        results += [C] * 3
    signature = ", ".join(f"state_{k}.1: f32[{rows},{cols}]" for k in STATE)
    returned = ", ".join(r.partition("{")[0] for r in results)
    return ("HloModule jit_local_fn, is_scheduled=true\n" + TABLES + f'''
%fused_computation (param_0: f32[{rows},{cols}]) -> (f32[{rows},2], f32[{rows},2]) {{
  %param_0 = {F} parameter(0)
  %slice.1 = {S} slice(%param_0), slice={{[0:{rows}], [2:4]}}, {PACK}
  %slice.2 = {S} slice(%param_0), slice={{[0:{rows}], [{cols - 4}:{cols - 2}]}}, {PACK}
  ROOT %tuple.1 = ({S}, {S}) tuple(%slice.1, %slice.2)
}}

%body (arg: (s32[], {carried})) -> (s32[], {carried}) {{
''' + "\n".join(body) + f'''
}}

ENTRY %main.5 ({signature}) -> ({returned}) {{
''' + "\n".join(entry) + f'''
  ROOT %tuple.2 = ({", ".join(results)}) tuple(%state_h.1)
}}
''')


def program_text(taken, handed_back, scope="mpi4jax_tpu.snapshot/coarsen", arrays=3):
    """A program of ``arrays`` fusions ``%out.N`` under ``scope``, each
    handed one parameter of shape ``taken`` and handing back one array
    of shape ``handed_back``: a snapshot program (three fields in, three
    coarse fields out) or, with six, a save's staging program."""
    T = f"f32[{taken[0]},{taken[1]}]"
    B = f"f32[{handed_back[0]},{handed_back[1]}]"
    where = f'metadata={{op_name="jit(local_fn)/{scope}/slice" stack_frame_id=1}}'
    lines = [f"  %fields_{i}_.1 = {T}{{1,0:T(8,128)}} parameter({i})"
             for i in range(arrays)]
    lines += [f"  %out.{i} = {B}{{1,0:T(8,128)}} fusion(%fields_{i}_.1), kind=kLoop, "
              f"calls=%pool, {where}" for i in range(arrays)]
    params = ", ".join(f"fields_{i}_.1: {T}" for i in range(arrays))
    results = ", ".join([B] * arrays)
    return ("HloModule jit_local_fn, is_scheduled=true\n" + TABLES + f'''
ENTRY %main.4 ({params}) -> ({results}) {{
''' + "\n".join(lines) + f'''
  ROOT %tuple.1 = ({results}) tuple({", ".join(f"%out.{i}" for i in range(arrays))})
}}
''')


def event_lines(text):
    """``{instruction: its line}``: what the chip's trace names an event by."""
    return {name: line.strip() for line, name in
            re.findall(r"^\s*(?:ROOT\s+)?(%([\w.\-]+) = .*)$", text, re.M)}


def made_job_session(texts, reps=3):
    """What the job cells' readers ask of a session, round texts made
    by hand: the driver's own ``programs`` and ``traced_programs`` over
    a job that holds a snapshot program only where ``texts`` has one;
    a call is ten steps."""
    from perfbench.harness import files

    driver = files.load_module("drivers", "shallow_water_job")
    job = types.SimpleNamespace(
        multi=object(), snap=object() if "snapshot" in texts else None)
    session = types.SimpleNamespace(
        job=job, rows={"multistep": {"reps": reps}},
        ctx=types.SimpleNamespace(bench_dir=files.BENCH_DIR),
        compiled_text=texts.__getitem__)
    session.programs = lambda: driver.Session.programs(session)
    session.traced_programs = (
        lambda *a: driver.Session.traced_programs(session, *a))
    session.units = lambda row: session.rows[row]["reps"] * 10
    session.facts = lambda: {"steps_per_call": 10}
    return session


def a_step(lines, kernel_ns=700):
    """One step's events as the solver's loop runs them since PR 31,
    named by ``event_lines(multistep_text())``: the three fusions of the
    sent slabs, 10 ns each, a scalar's broadcast, the kernel call."""
    return [(lines[f"fusion.{n}"], 10) for n in (8, 7, 6)] + [
        (lines["flags"], 1), (lines["wide_step.3"], kernel_ns)]


def made_trace(executions, chip="/device:TPU:0"):
    """A trace of ``executions``, each a list of ``(event name, ns)``,
    run one after the other on one chip, 7 ns idle between programs."""
    from perfbench.harness.trace import Event, Trace

    made = Trace()
    t = 0.0
    made.device_ops[chip], made.modules[chip] = [], []
    for events in executions:
        start = t
        for name, ns in events:
            made.device_ops[chip].append(Event(name, t, float(ns)))
            t += ns
        made.modules[chip].append(Event("jit_local(1)", start, t - start))
        t += 7.0
    return made
