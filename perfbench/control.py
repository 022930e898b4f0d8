"""The control of a cell's comparison, at the cell's own size, on the chip.

    python3 -m perfbench.control --workload <cell> --seeds <n> [<n> ...]

For every seed, in one process: the cell's set-up, one batch of every
row, the comparison of the timed programs with the plain reference
(sound, has to pass) and the same comparison with the control in the
program's place, the reference carried in the nearest lower precision
(has to fail).  Prints every number beside its limit.  The benchmark's
own runs never run this; PERF.md's limits were set from its readings.
"""

import argparse
import json

from perfbench import run
from perfbench.harness import files


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    benchmark = files.load_benchmark()
    cell = files.find_cell(benchmark, args.workload)
    run.enable_bytecode_cache(files.ROOT)
    run.runtime_environment()
    run.enable_compile_cache(files.ROOT)
    devices = run.require_chips(cell["chips"])
    workload = files.load_json("workloads", cell["name"])
    config = files.load_json("configs", cell["config"])
    driver = files.load_module("drivers", config["driver"])
    for seed in args.seeds:
        session = driver.setup(run.Context(config, workload, seed, list(devices)))
        for row in workload["rows"]:
            session.batch(row["name"])
        for kind, numbers in (("sound", session.check), ("control", session.control)):
            for c in numbers():
                print(json.dumps({"seed": seed, "kind": kind, **c,
                                  "within": c["value"] <= c["limit"]}), flush=True)
        del session


if __name__ == "__main__":
    main()
