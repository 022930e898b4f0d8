"""A temporary copy of the benchmark with small cells added to it.

The cells, a configuration and a per-layer metric are added as new files
beside the copied ones, and as entries appended to ``BENCHMARK.json``:
no file the benchmark has is edited.  That is how a later PR adds its
own, and these tests show that the harness finds them.
"""

import argparse
import json
import pathlib
import shutil

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
