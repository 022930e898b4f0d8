"""The benchmark's arithmetic and its look-up of files by name (CPU, no chip)."""

import json
import statistics

import numpy as np
import pytest

from perfbench.harness import files, stats
from perfbench.harness.peaks import peaks_for


@pytest.mark.parametrize("p", [0, 5, 50, 95, 100])
def test_percentile_is_numpys(p):
    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    assert stats.percentile(values, p) == pytest.approx(np.percentile(values, p))


def test_percentile_of_one_sample_and_of_none():
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_the_contracts():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / statistics.median(values))


@pytest.mark.parametrize("op,factor", [
    # an allgather's payload is the shard a rank sends: nccl-tests' 3/4
    # of the four shards it holds after
    ("allreduce", 1.5), ("allgather", 3.0), ("alltoall", 0.75),
    ("bcast", 1.0), ("sendrecv", 1.0), ("halo", 1.0)])
def test_busbw_factors_on_four_ranks(op, factor):
    # 1 GiB a rank in one second, in GB/s of 1e9 bytes
    assert stats.busbw_gbps(op, 1 << 30, 4, 1.0) == pytest.approx(
        factor * (1 << 30) / 1e9)


def test_schedule_same_work_for_every_seed_other_order():
    rows = [{"name": "a", "slots": 9}, {"name": "b", "slots": 9},
            {"name": "c", "slots": 2}]
    one, two = stats.schedule(rows, 1), stats.schedule(rows, 2**31 + 5)
    assert sorted(one) == sorted(two) == ["a"] * 9 + ["b"] * 9 + ["c"] * 2
    assert one != two
    assert one == stats.schedule(rows, 1)


def test_schedule_needs_a_slot():
    with pytest.raises(ValueError):
        stats.schedule([{"name": "a", "slots": 0}], 1)


def test_peaks_unknown_device_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("cpu")


@pytest.mark.parametrize("bad", ["../run", "a/b", "", "a b", "x" * 65])
def test_a_name_that_is_no_name_is_refused(bad):
    with pytest.raises(files.BenchmarkFileError):
        files.load_json("workloads", bad)


def test_every_name_in_benchmark_json_has_its_file():
    benchmark = files.load_benchmark()
    for cell in benchmark["workloads"]:
        workload = files.load_json("workloads", cell["name"])
        config = files.load_json("configs", workload["config"])
        assert {k: workload[k] for k in ("config", "chips", "traffic", "why")} == {
            k: cell[k] for k in ("config", "chips", "traffic", "why")}
        assert (files.BENCH_DIR / "drivers" / f"{config['driver']}.py").is_file()
        assert (files.BENCH_DIR / "references" / f"{config['reference']}.py").is_file()
    for entry in benchmark["configs"]:
        on_disk = json.loads((files.ROOT / entry["file"]).read_text())
        assert on_disk["source"] == entry["source"]
        assert on_disk["reduced"] == entry["reduced"]
    for metric in benchmark["per_layer"]:
        assert hasattr(files.load_module("layer_metrics", metric["name"]), "read")


def test_metrics_of_a_cell():
    benchmark = files.load_benchmark()
    names = {m["name"] for m in files.metrics_of(benchmark, "end_to_end", "coll-2x2")}
    assert names == {"coll_busbw", "coll_lat_p95_us", "coll_table_geomean_us", "setup_s"}
    layer = {m["name"] for m in files.metrics_of(benchmark, "per_layer", "sw-bench-1chip")}
    assert "compile_s" in layer and "allreduce_tax_large" not in layer
