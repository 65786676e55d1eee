"""Find a cell's knee: serve its open-loop mix at a rising series of rates.

    python3 chipbench/sweep.py --workload <cell> --rates 100,200,300 --seconds 6
        [--seed n] [--dump-trace DIR]

Builds the cell's index once and, for each rate, serves the mix's schedule
at that rate through the same front end. For each rate it prints the
requests, p50 and p99 latency, the median latency of the first and of the
last fifth of the requests, and how long the queue took to drain after the
last request was due. The knee is the
highest rate at which the backlog does not grow through the window: the
latency of the last fifth of the requests stays near that of the first
fifth and the queue drains within a few micro-batches. ``--dump-trace``
also records a profiler trace of one second at the first rate into DIR.
Not run by the benchmark itself: its result is written into the traffic
file by hand, with the sweep in PERF.md.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dump-trace", default=None)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench import data, harness, traffic
    from repro.serve.frontend import FrontendConfig

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = harness.cell_parts(bench, args.workload)
    devices = harness.devices_for(cell, require_tpu=True)
    points, pool = data.dataset(args.seed, config["data"]["n_points"],
                                mix["pool_rows"], config["data"]["d"])
    index = harness.build(config, points, args.seed, devices)
    jax.block_until_ready(index.pipeline_index)
    rec = harness.Recorder(index)
    fe = index.frontend(FrontendConfig(ladder=tuple(mix["ladder"])))
    fe.warmup()
    rec.on = True
    print(f"setup_s={time.perf_counter() - T_START:.3f} device={devices[0].device_kind}"
          f" x{len(devices)}", flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        reqs = traffic.requests(m, args.seed + k, args.seconds, pool)
        run = harness.Run(cell, config, m, args.seed, args.seconds, cell["chips"],
                          devices[0].device_kind)
        last_due = reqs[-1].due_s
        t0 = time.perf_counter()
        harness.window_open(fe, rec, run, reqs, float("inf"))
        drain_s = run.window_s - harness.LEAD_S - last_due
        rec.results.clear()
        lat = np.asarray([1e3 * t.latency_s for t in run.tickets])
        fifth = max(len(lat) // 5, 1)
        row = {
            "rate_per_s": rate, "requests": len(lat),
            "rows_per_s": sum(t.n_queries for t in run.tickets) / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "drain_s": drain_s,
            "batches": len(run.batches),
            "rows_per_batch": sum(b.rows for b in run.batches) / max(len(run.batches), 1),
            "wall_s": time.perf_counter() - t0,
        }
        print(json.dumps(row), flush=True)
        if drain_s > 5.0:  # far past the knee: higher rates only take longer
            break
    if args.dump_trace:
        m = dict(mix, rate_per_s=float(args.rates.split(",")[0]))
        reqs = traffic.requests(m, args.seed, 2.0, pool)
        run = harness.Run(cell, config, m, args.seed, 2.0, cell["chips"],
                          devices[0].device_kind)
        harness.window_open(fe, rec, run, reqs, float("inf"),
                            harness.Tracing(args.dump_trace, 1.0))
        print(f"trace of {len(run.batches)} micro-batches in {args.dump_trace}")


if __name__ == "__main__":
    main()
