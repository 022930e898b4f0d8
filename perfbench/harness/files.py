"""Where the benchmark's files are, and how one is loaded by its name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it.  Python files are loaded by path, so a name may hold ``-`` and ``.``.
"""

import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")


class BenchmarkFileError(Exception):
    """A name that ``BENCHMARK.json`` gives has no file, or a bad one."""


def _path(kind, name, suffix, bench_dir):
    if not _NAME.match(name):
        raise BenchmarkFileError(f"{kind} name {name!r} is not a valid name")
    path = pathlib.Path(bench_dir) / kind / f"{name}{suffix}"
    if not path.is_file():
        raise BenchmarkFileError(f"no file {path} for {kind} {name!r}")
    return path


def load_json(kind, name, bench_dir=BENCH_DIR):
    """``perfbench/<kind>/<name>.json`` as a dict."""
    with open(_path(kind, name, ".json", bench_dir)) as f:
        return json.load(f)


def load_module(kind, name, bench_dir=BENCH_DIR):
    """``perfbench/<kind>/<name>.py`` as a module, loaded by path."""
    path = _path(kind, name, ".py", bench_dir)
    ident = re.sub(r"\W", "_", f"perfbench_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(ident, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(root=ROOT):
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(benchmark, name):
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchmarkFileError(f"BENCHMARK.json has no workload {name!r}")


def metrics_of(benchmark, section, cell_name):
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it, and those that list no cells."""
    return [
        m for m in benchmark[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
