"""Paper-scale out-of-core build harness (DESIGN.md §13).

Streams a seedable ABP-like window dataset (``data/windows.py`` chunked
synthesis — the full array is assembled once, chunk by chunk) through the
``repro.dslsh`` Deployment API onto the paper's 40-cell routed grid, and
emits ``BENCH_scale.json`` with four sections:

* **build** — wall time + points/s for the grid build, the resolved
  per-cell build mode, and the memory accountant's per-cell byte split;
* **rss_probe** — subprocess peak-RSS of a single-shard build at the full
  dataset size, chunked vs monolithic (the CI gate: chunked peak build
  bytes <= 0.6x monolithic at smoke size);
* **eval** — MCC on a labeled query subset for DSLSH and exhaustive kNN
  (chunked running-top-k, never a full distance matrix), plus the paper's
  comparisons speedup vs exhaustive;
* **payload** — single-shard query latency + modeled tail HBM bytes per
  format (f32/f16/i8), with the §13 exactness certificate (rerank misses
  counted; knn_idx bit-identical to f32 at zero misses).

Tiers: smoke n=131072 (default; CI) and the paper-scale FULL tier
n=1,370,000 (``REPRO_BENCH_FULL=1``). As a child process
(``--probe MODE N``) it prints one JSON line of RSS accounting instead.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common

SCALE_JSON = os.environ.get(
    "REPRO_BENCH_SCALE_JSON",
    os.path.join(os.path.dirname(__file__), "artifacts", "BENCH_scale.json"),
)

NU, P = 10, 4  # the paper's 40-cell grid (L_out=16 divides across p=4)
SEED = 0
GEN_CHUNK = 16_384  # windows streamed per generator step
KNN_CHUNK = 8_192  # data rows per exhaustive running-top-k step
PAYLOAD_FORMATS = ("f32", "f16", "i8")
PAYLOAD_C_RERANK = 32  # keeps the f16 tail-byte model well under f32


def _tier():
    if common.FULL:
        return dict(tier="full", n=1_370_000, nq=2_000, q_lat=512)
    return dict(tier="smoke", n=131_072, nq=500, q_lat=128)


def _cfg(**kw):
    return common.slsh_cfg(**kw)


def stream_dataset(n: int, nq: int):
    """Assemble (points, labels, qx, qy) from the chunked window stream.

    The stream is consumed chunk-by-chunk into preallocated arrays — the
    generator itself never materializes more than one GEN_BLOCK — and the
    ``nq`` rows *after* the first ``n`` become the out-of-sample labeled
    query set (same stream, disjoint rows).
    """
    from repro.data import windows

    spec = windows.SyntheticWindowSpec(n=n + nq, seed=SEED)
    pts = np.empty((n, spec.d), np.float32)
    labs = np.empty((n,), np.int8)
    lo = 0
    for p, y in windows.synth_window_chunks(
        windows.SyntheticWindowSpec(n=n, seed=SEED), GEN_CHUNK
    ):
        pts[lo : lo + p.shape[0]] = p
        labs[lo : lo + p.shape[0]] = y
        lo += p.shape[0]
    qx, qy = windows.synth_window_slice(spec, n, n + nq)
    return pts, labs, qx, qy


def _probe_rss(mode: str, n: int) -> dict:
    """One subprocess single-shard build; returns its RSS accounting.

    The child measures host RSS, so it runs on the CPU backend: this
    process already holds the accelerator, and a chip serves one process.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.scale_bench", "--probe", mode, str(n)],
        capture_output=True, text=True, check=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _probe_child(mode: str, n: int) -> None:
    """Child body: build once at ``n`` single-shard, print RSS JSON."""
    import resource

    from repro.core import pipeline

    def cur_rss_kb() -> int:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    cfg = _cfg(build_mode=mode)
    pts, _, _, _ = stream_dataset(n, 0)
    data = jnp.asarray(pts)
    del pts
    jax.block_until_ready(data)
    outer, inner = pipeline.make_family(jax.random.PRNGKey(SEED), data.shape[1], cfg)
    # warmup at tiny n pays jax init + compile before the watermark
    warm = data[:1024]
    jax.block_until_ready(pipeline.build_from_params(warm, outer, inner, cfg))
    del warm
    pre = cur_rss_kb()
    t0 = time.perf_counter()
    idx = pipeline.build_from_params(data, outer, inner, cfg)
    jax.block_until_ready(idx)
    wall = time.perf_counter() - t0
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(json.dumps({
        "mode": mode, "platform": jax.default_backend(), "n": n,
        "pre_kb": pre, "peak_kb": peak,
        "build_delta_kb": max(peak - pre, 0), "wall_s": wall,
    }))


def _exhaustive_knn(pts_j, qx_j, k: int):
    """Chunked exhaustive kNN: running top-k over KNN_CHUNK-row slabs —
    peak memory O(nq * KNN_CHUNK), never a full (nq, n) matrix."""

    @jax.jit
    def step(kd, ki, chunk, off):
        dist = jnp.sum(jnp.abs(qx_j[:, None, :] - chunk[None]), axis=-1)
        idx = jnp.broadcast_to(
            off + jnp.arange(chunk.shape[0], dtype=jnp.int32), dist.shape
        )
        alld = jnp.concatenate([kd, dist], axis=1)
        alli = jnp.concatenate([ki, idx], axis=1)
        neg, p = jax.lax.top_k(-alld, k)
        return -neg, jnp.take_along_axis(alli, p, axis=1)

    nq = qx_j.shape[0]
    kd = jnp.full((nq, k), jnp.inf, jnp.float32)
    ki = jnp.full((nq, k), -1, jnp.int32)
    n = pts_j.shape[0]
    for lo in range(0, n - n % KNN_CHUNK, KNN_CHUNK):
        kd, ki = step(kd, ki, jax.lax.dynamic_slice_in_dim(pts_j, lo, KNN_CHUNK), lo)
    if n % KNN_CHUNK:  # ragged tail: one extra trace at most
        kd, ki = step(kd, ki, pts_j[n - n % KNN_CHUNK :], n - n % KNN_CHUNK)
    return kd, ki


def run():
    from repro import dslsh
    from repro.core import predict
    from repro.runtime import payload as payload_mod

    tier = _tier()
    n, nq = tier["n"], tier["nq"]
    cfg = _cfg()
    report = {
        "tier": tier["tier"], "n": n, "nq": nq, "seed": SEED,
        "grid": {"nu": NU, "p": P, "cells": NU * P},
        "config": {
            k: getattr(cfg, k)
            for k in ("m_out", "L_out", "m_in", "L_in", "c_max", "k",
                      "build_chunk")
        },
    }

    # ---- dataset (streamed assembly)
    t0 = time.perf_counter()
    pts, labs, qx, qy = stream_dataset(n, nq)
    gen_s = time.perf_counter() - t0
    pts, labs, n_real = dslsh.pad_to_multiple(pts, labs, NU * P)
    n_pad = pts.shape[0]
    report["n_pad"] = n_pad
    report["gen"] = {
        "wall_s": gen_s, "pts_per_s": n / max(gen_s, 1e-9),
        "pos_frac": float((labs[:n_real] == 1).mean()),
    }
    yield ("scale/generate", gen_s * 1e6, f"pts_per_s={n / max(gen_s, 1e-9):.0f}")

    # ---- peak-RSS probes: chunked vs monolithic single-shard build
    probes = {m: _probe_rss(m, n) for m in ("chunked", "monolithic")}
    ratio = probes["chunked"]["build_delta_kb"] / max(
        probes["monolithic"]["build_delta_kb"], 1
    )
    report["rss_probe"] = {**probes, "chunked_over_monolithic": ratio}
    yield (
        "scale/build_rss_chunked", probes["chunked"]["wall_s"] * 1e6,
        f"delta_kb={probes['chunked']['build_delta_kb']}",
    )
    yield (
        "scale/build_rss_monolithic", probes["monolithic"]["wall_s"] * 1e6,
        f"delta_kb={probes['monolithic']['build_delta_kb']}",
    )
    yield ("scale/build_rss_ratio", 0.0, f"chunked_over_monolithic={ratio:.2f}")

    # ---- 40-cell routed grid build through the Deployment API
    pts_j, labs_j = jnp.asarray(pts), jnp.asarray(labs)
    qx_j, qy_j = jnp.asarray(qx), jnp.asarray(qy)
    del pts, labs
    deploy = dslsh.grid(nu=NU, p=P, routed=True)
    t0 = time.perf_counter()
    index = dslsh.build(jax.random.PRNGKey(7), pts_j, cfg, deploy)
    jax.block_until_ready(index.pipeline_index)
    build_s = time.perf_counter() - t0
    n_cell = n_pad // NU
    from repro.core import pipeline as _pl

    report["build"] = {
        "wall_s": build_s,
        "pts_per_s": n_pad / max(build_s, 1e-9),
        "per_cell_n": n_cell,
        "per_cell_mode": _pl._pick_build_mode(cfg, n_cell),
        "memory": index.memory_report().to_dict(),
    }
    yield (
        "scale/grid_build", build_s * 1e6,
        f"pts_per_s={n_pad / max(build_s, 1e-9):.0f}",
    )

    # ---- labeled-subset accuracy + comparisons speedup vs exhaustive
    t0 = time.perf_counter()
    res = index.query(qx_j)
    jax.block_until_ready((res.knn_dist, res.knn_idx))
    query_s = time.perf_counter() - t0
    mcc_slsh = float(predict.mcc(
        predict.predict_batch(labs_j, res.knn_idx, res.knn_dist), qy_j
    ))
    ekd, eki = _exhaustive_knn(pts_j, qx_j, cfg.k)
    mcc_pknn = float(predict.mcc(predict.predict_batch(labs_j, eki, ekd), qy_j))
    max_comps = np.asarray(res.max_comparisons_per_cell).astype(np.float64)
    med = float(np.median(max_comps))
    pknn_comps = n_pad // NU  # each node scans its full slice per query
    speedup = pknn_comps / max(med, 1.0)
    report["eval"] = {
        "query_wall_s": query_s,
        "us_per_query": query_s / nq * 1e6,
        "mcc_slsh": mcc_slsh,
        "mcc_pknn": mcc_pknn,
        "mcc_loss": mcc_pknn - mcc_slsh,
        "median_comps": med,
        "pknn_comps": pknn_comps,
        "speedup_vs_exhaustive": speedup,
        "overflow_cells": res.overflow_cells,
        "routed_frac": res.routed_frac,
    }
    yield (
        "scale/eval", query_s / nq * 1e6,
        f"speedup={speedup:.1f}x mcc_slsh={mcc_slsh:.3f} mcc_pknn={mcc_pknn:.3f}",
    )

    # ---- compressed-payload formats on one cell's single-shard tail
    pcfg0 = _cfg(backend="pallas", c_rerank=PAYLOAD_C_RERANK)
    cell_pts = pts_j[: n_pad // (NU * P)]
    qp = qx_j[: tier["q_lat"]]
    base_idx = None
    fmts = {}
    for fmt in PAYLOAD_FORMATS:
        pcfg = pcfg0.replace(payload=fmt)
        h = dslsh.build(jax.random.PRNGKey(7), cell_pts, pcfg, dslsh.single())
        r, us = common.timer(lambda h=h: h.query(qp), repeats=2)
        tail_bytes = payload_mod.tail_gather_bytes(
            pcfg.c_comp, pcfg.c_rerank, cell_pts.shape[1], fmt
        )
        entry = {
            "us_per_query": us / qp.shape[0],
            "tail_gather_bytes_per_query": tail_bytes,
            "rerank_misses": (
                0 if r.rerank_misses is None else int(np.asarray(r.rerank_misses).sum())
            ),
        }
        if fmt == "f32":
            base_idx = r
            entry["bytes_reduction_vs_f32"] = 1.0
            entry["knn_idx_identical_to_f32"] = True
        else:
            entry["bytes_reduction_vs_f32"] = (
                payload_mod.tail_gather_bytes(
                    pcfg.c_comp, pcfg.c_rerank, cell_pts.shape[1], "f32"
                ) / tail_bytes
            )
            entry["knn_idx_identical_to_f32"] = bool(
                jnp.array_equal(base_idx.knn_idx, r.knn_idx)
            )
        fmts[fmt] = entry
        yield (
            f"scale/payload_{fmt}", us / qp.shape[0],
            f"bytes={tail_bytes} misses={entry['rerank_misses']}"
            f" x{entry['bytes_reduction_vs_f32']:.2f}",
        )
    report["payload"] = {
        "n_cell": int(cell_pts.shape[0]), "nq": int(qp.shape[0]),
        "c_comp": pcfg0.c_comp, "c_rerank": pcfg0.c_rerank,
        "formats": fmts,
    }

    os.makedirs(os.path.dirname(SCALE_JSON), exist_ok=True)
    with open(SCALE_JSON, "w") as f:
        json.dump(report, f, indent=2)
    yield ("scale/json_report", 0.0, SCALE_JSON)


def main() -> None:
    if len(sys.argv) >= 2 and sys.argv[1] == "--probe":
        _probe_child(sys.argv[2], int(sys.argv[3]))
        return
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()
