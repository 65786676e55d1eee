"""The control of the comparison that decides ``correct``.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed, makes the cell's data and a check sample of its traffic's
rows (as many as a run checks), answers them with the reference at the
configuration's precision and again with every input and distance rounded
to bfloat16, the nearest precision below, and prints the compared numbers
of the bfloat16 answers: the upper readings the limits are set below. The
benchmark's own runs do not run it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell_parts: tuple, seed: int, seconds: float) -> dict:
    from chipbench import compare, data, reference, traffic

    cell, config, mix = cell_parts
    points, pool = data.dataset(seed, config["data"]["n_points"], mix["pool_rows"],
                                config["data"]["d"])
    rows, n = [], 0
    for r in traffic.requests(mix, seed, seconds, pool):
        if n >= mix["check_rows"]:
            break
        rows.append(r.rows)
        n += r.rows.shape[0]
    import numpy as np

    queries = np.concatenate(rows)
    dep = config["deployment"]
    want = reference.Reference(points, seed, config["slsh"], dep["nu"], dep["p"]).query(queries)
    low = reference.Reference(points, seed, config["slsh"], dep["nu"], dep["p"],
                              "bfloat16").query(queries)
    values = compare.numbers(low, want)
    values["rows"] = int(queries.shape[0])
    return values


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from chipbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = harness.cell_parts(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(parts, seed, args.seconds)
        out.update(seed=seed, seconds=round(time.perf_counter() - t, 3))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
