"""Benchmark runner. One module per paper table/figure (+ roofline/kernels).

Prints ``name,us_per_call,derived`` CSV rows. Set REPRO_BENCH_FULL=1 for
paper-scale datasets (minutes-to-hours on CPU); default is a scaled-down
run that preserves every qualitative claim.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.runtime import compile_cache

    compile_cache.enable()
    from benchmarks import (
        elastic_bench,
        fig3_tradeoff,
        fig4_slsh,
        kernels_bench,
        pipeline_bench,
        roofline,
        routing_bench,
        scale_bench,
        serve_bench,
        stream_bench,
        table2_scaling,
        table3_scaling,
    )

    modules = {
        "fig3": fig3_tradeoff,
        "fig4": fig4_slsh,
        "table2": table2_scaling,
        "table3": table3_scaling,
        "kernels": kernels_bench,
        "pipeline": pipeline_bench,
        "roofline": roofline,
        "stream": stream_bench,
        "routing": routing_bench,
        "scale": scale_bench,
        "elastic": elastic_bench,
        "serve": serve_bench,
    }
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = False
    for name, mod in modules.items():
        if only and name != only:
            continue
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            failed = True
            print(f"{name},-1,ERROR", flush=True)
            traceback.print_exc()
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
