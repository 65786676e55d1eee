"""Run one benchmark cell on the accelerator this machine holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the cell's data from ``--seed``, builds the index, warms up the
cell's micro-batch rungs, serves the cell's traffic for ``--seconds``,
checks a sample of the answers against the plain reference, and prints one
JSON object as the last line of standard output. ``--trace 1`` also
records a profiler trace of the window and reports the per-layer metrics
instead of the end-to-end ones. Without a TPU, or with fewer chips than
the cell needs, it prints no result and exits with code 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".jax_cache")  # fixed: the path is part of the key


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
