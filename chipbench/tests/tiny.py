"""A tiny copy of the benchmark's cells for runs on the CPU."""
import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

TINY_SLSH = dict(
    m_out=16, L_out=4, m_in=8, L_in=2, alpha=0.02, k=4, c_max=32, c_in=8,
    h_max=4, p_max=64, c_comp=48, build_chunk=1024, query_chunk=8,
    backend="reference",
)


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def parts(name: str, *, n_points: int = 4096, rate: float = 40.0,
          nu: int | None = None, p: int | None = None, **slsh) -> tuple:
    """The cell ``name`` of BENCHMARK.json with its configuration cut to a
    size the CPU answers in seconds (same code paths, smaller numbers)."""
    from chipbench import harness

    cell, config, mix = copy.deepcopy(harness.cell_parts(bench(), name))
    cell["chips"] = 1
    config["data"]["n_points"] = n_points
    config["slsh"].update(TINY_SLSH, **slsh)
    dep = config["deployment"]
    dep["nu"], dep["p"] = nu or 2, p or dep["p"]
    if mix["loop"] == "open":
        mix["rate_per_s"] = rate
        mix["ladder"] = [4, 8]
    else:
        mix["rows"] = [[16, 16, 1.0]]
        mix["ladder"] = [16]
        mix["sequence"] = 512
    mix["pool_rows"] = 1024
    mix["check_rows"] = 64
    return cell, config, mix
