"""Drive the served DSLSH path once on a TPU and check its answers.

    python chip_smoke.py                # one chip: the served path
    python chip_smoke.py --four-chips   # four chips: the 2x2 device mesh

One chip: build the paper's deployment — 1,370,000 synthetic AHE windows
(d=30, seed 0) on the 40-cell routed grid (nu=10, p=4) with the
``benchmarks.common.slsh_cfg`` configuration and ``backend="pallas"`` —
warm up the serving front end, serve requests from two tenants, and check
every undegraded answer against ``backend="reference"`` on the same index
(k-NN indices equal, distances allclose, comparison and overflow counters
equal). MCC against exhaustive PKNN is printed for the served queries.

``--four-chips``: the same points on ``dslsh.mesh`` over a 2x2 mesh of four
chips, queried with both reducers, against the single-device simulation of
the same 2x2 grid; k-NN, comparisons and overflow must be equal, and the
index and data shards must sit on four distinct devices.

The script exits non-zero, and prints no result line, when JAX finds no
TPU, when the repository's ``src/`` is not beside it, or when any phase
fails. On success the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N_POINTS = 1_370_000
N_REQUESTS = 48  # two tenants, alternating
REQ_ROWS = (1, 2, 3, 4, 5, 6, 7, 8)  # rows per request, cycled
LADDER = (8, 32)  # front-end micro-batch buckets
HASH_CHECK_ROWS = 65_536


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def tpu_devices():
    """The TPU devices JAX sees; fails when there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    return devices


def dataset(n: int, nq: int):
    """(points, labels, queries, query labels) of the synthetic AHE window
    stream, the points padded to a multiple of the 40-cell grid."""
    from benchmarks import scale_bench

    from repro import dslsh

    pts, labs, qx, qy = scale_bench.stream_dataset(n, nq)
    pts, labs, _ = dslsh.pad_to_multiple(pts, labs, 40)
    return pts, labs, qx, qy


def config():
    from benchmarks import common

    from repro.kernels import blocking

    cfg = common.slsh_cfg(backend="pallas", interpret=None)
    if cfg.backend != "pallas" or blocking.resolve_interpret(cfg.interpret):
        fail(f"not the compiled pallas path: backend={cfg.backend},"
             f" interpret={blocking.resolve_interpret(cfg.interpret)}")
    return cfg


def check_hash_words(cfg, pts) -> None:
    """The compiled hash_pack kernel's words equal the reference hashing,
    for both families, on a slab of real points."""
    import jax
    import numpy as np

    from repro.core import pipeline

    outer, inner = pipeline.make_family(jax.random.PRNGKey(0), pts.shape[1], cfg)
    x = jax.numpy.asarray(pts[:HASH_CHECK_ROWS])
    pallas = pipeline.get_backend("pallas", cfg)
    ref = pipeline.get_backend("reference", cfg)
    for name, params in (("outer", outer), ("inner", inner)):
        got = np.asarray(pallas.signature_words(params, x))
        want = np.asarray(ref.signature_words(params, x))
        if not np.array_equal(got, want):
            fail(f"hash_pack {name} words differ from the reference on"
                 f" {int((got != want).any(axis=(1, 2)).sum())} rows")
    log(f"hash_pack words == reference on {x.shape[0]} points (outer + inner)")


def check_same(name, got, want, *, exact: bool) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if exact:
        ok = np.array_equal(got, want)
    else:
        ok = got.shape == want.shape and np.allclose(got, want, rtol=1e-5, atol=1e-5)
    if not ok:
        fail(f"{name} differs: {got.ravel()[:8]} vs {want.ravel()[:8]}")


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro import api, dslsh
    from repro.core import predict
    from repro.kernels.query_fused import query_fused
    from repro.serve import frontend as frontend_mod

    cfg = config()
    log("backend=pallas interpret=False (compiled Mosaic kernels)")
    for note in query_fused.XLA_STAGES:
        log(f"xla-stage: {note}")
    nq = sum(REQ_ROWS[i % len(REQ_ROWS)] for i in range(N_REQUESTS))
    t0 = time.perf_counter()
    pts, labs, qx, qy = dataset(N_POINTS, nq)
    log(f"data: n={N_POINTS} (padded {pts.shape[0]}) d={pts.shape[1]}"
        f" queries={nq} seed={seed} gen_s={time.perf_counter() - t0:.3f}")
    check_hash_words(cfg, pts)

    deploy = dslsh.grid(nu=10, p=4, routed=True)
    t0 = time.perf_counter()
    index = dslsh.build(jax.random.PRNGKey(seed), pts, cfg, deploy)
    jax.block_until_ready(index.pipeline_index)
    log(f"build: grid={deploy.nu}x{deploy.p} cells={deploy.cells} routed=True"
        f" build_s={time.perf_counter() - t0:.3f} (compile included)")

    fe = index.frontend(frontend_mod.FrontendConfig(ladder=LADDER))
    t0 = time.perf_counter()
    programs = fe.warmup()
    log(f"warmup: {programs} query programs in {time.perf_counter() - t0:.3f}s")
    reqs, lo = [], 0
    for i in range(N_REQUESTS):
        rows = REQ_ROWS[i % len(REQ_ROWS)]
        reqs.append(fe.submit(qx[lo:lo + rows], tenant=f"icu-{'ab'[i % 2]}"))
        lo += rows
    fe.drain()
    stats = fe.assert_conserved()
    done = [r for r in reqs if r.status == "done"]
    if len(done) != N_REQUESTS:
        fail(f"served {len(done)} of {N_REQUESTS} requests: {stats}")
    lat = np.asarray([r.latency_s for r in done]) * 1e3
    log(f"served: {len(done)} requests, {nq} queries, tenants=2,"
        f" degraded={stats.degraded_responses}, shed={stats.shed},"
        f" latency_ms p50={np.percentile(lat, 50):.3f}"
        f" p99={np.percentile(lat, 99):.3f} (host clock, queueing included)")

    # the reference backend over the same built index is the oracle
    ref = api.wrap_grid(
        index.pipeline_index, pts, cfg.replace(backend="reference"),
        dslsh.Grid(nu=deploy.nu, p=deploy.p), plan=index.plan,
    )
    want = ref.query(qx)
    direct = index.query(qx)
    check_same("comparisons", direct.comparisons, want.comparisons, exact=True)
    check_same("compaction_overflow", direct.compaction_overflow,
               want.compaction_overflow, exact=True)
    ki = np.concatenate([r.knn_idx for r in done])
    kd = np.concatenate([r.knn_dist for r in done])
    checked = 0
    lo = 0
    for r in done:
        hi = lo + r.n_queries
        if not r.degraded:
            check_same(f"request {r.rid} knn_idx", r.knn_idx, want.knn_idx[lo:hi], exact=True)
            check_same(f"request {r.rid} knn_dist", r.knn_dist, want.knn_dist[lo:hi], exact=False)
            checked += 1
        lo = hi
    log(f"reference: {checked} undegraded answers checked, k-NN identical to"
        f" backend=reference; comparisons and overflow equal on {nq} queries;"
        f" overflow_cells={direct.overflow_cells}"
        f" routed_frac={direct.routed_frac:.4f}")

    labs_j = jax.numpy.asarray(labs)
    pkd, pki, _ = dslsh.pknn_query(jax.numpy.asarray(pts), qx, cfg.k,
                                   dslsh.Grid(nu=deploy.nu, p=deploy.p))
    mcc_slsh = float(predict.mcc(predict.predict_batch(labs_j, ki, kd), qy))
    mcc_pknn = float(predict.mcc(predict.predict_batch(labs_j, pki, pkd), qy))
    log(f"mcc: dslsh={mcc_slsh:.4f} pknn={mcc_pknn:.4f} on {nq} queries"
        f" ({int(qy.sum())} positive)")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import dslsh
    from repro.launch.mesh import make_local_mesh

    cfg = config()
    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--four-chips needs four TPU devices, JAX sees {len(devices)}")
    pts, _, qx, _ = dataset(N_POINTS, 64)
    log(f"data: n={N_POINTS} (padded {pts.shape[0]}) d={pts.shape[1]}"
        f" queries={qx.shape[0]} seed={seed}")
    mesh = make_local_mesh(2, 2)
    data = jax.device_put(pts, NamedSharding(mesh, P("data", None)))
    t0 = time.perf_counter()
    index = dslsh.build(jax.random.PRNGKey(seed), data, cfg, dslsh.mesh(mesh))
    jax.block_until_ready(index.pipeline_index)
    log(f"mesh build: 2x2 on {len(mesh.devices.flat)} chips"
        f" build_s={time.perf_counter() - t0:.3f} (compile included)")
    for name, arr in [("data", data)] + [
        (f"index leaf {i}", a) for i, a in enumerate(jax.tree.leaves(index.pipeline_index))
    ]:
        on = {s.device for s in arr.addressable_shards}
        if len(arr.sharding.device_set) != 4 or len(on) != 4:
            fail(f"{name} sits on {len(on)} devices, not 4")
    log("placement: data and every index leaf have shards on 4 distinct devices")

    t0 = time.perf_counter()
    sim = dslsh.build(jax.random.PRNGKey(seed), pts, cfg, dslsh.grid(nu=2, p=2))
    want = sim.query(qx)
    jax.block_until_ready(want.knn_idx)
    log(f"simulation: 2x2 grid on one device, build + query"
        f" {time.perf_counter() - t0:.3f}s (compile included)")
    for reducer in ("allgather", "tree"):
        handle = dslsh.Index(
            dataclasses.replace(index.deploy, reducer=reducer), cfg,
            {"index": index.pipeline_index, "data": data},
        )
        t0 = time.perf_counter()
        got = handle.query(qx)
        jax.block_until_ready(got.knn_idx)
        q_s = time.perf_counter() - t0
        check_same(f"{reducer} knn_idx", got.knn_idx, want.knn_idx, exact=True)
        check_same(f"{reducer} knn_dist", got.knn_dist, want.knn_dist, exact=False)
        check_same(f"{reducer} comparisons", got.comparisons, want.comparisons, exact=True)
        check_same(f"{reducer} overflow", got.compaction_overflow,
                   want.compaction_overflow, exact=True)
        log(f"mesh reducer={reducer}: k-NN, comparisons, overflow equal to the"
            f" 2x2 simulation on {qx.shape[0]} queries"
            f" (first query_s={q_s:.3f}, compile included;"
            f" max comparisons {int(np.asarray(got.comparisons).max())})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the repository's src/ is not beside this script")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    devices = tpu_devices()
    from repro.runtime import compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}"
        f" compile_cache={compile_cache.enable()}")
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
