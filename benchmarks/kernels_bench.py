"""Kernel microbenchmarks (interpret mode on CPU — correctness-scale only;
the BlockSpec tiling targets TPU v5e), plus the fused query-tail megakernel
vs the staged dedup/compact/top-k chain *in isolation* — same synthetic
candidate tensor, no hash/gather head, so the row isolates exactly what the
fusion buys (DESIGN.md §4). The end-to-end pipeline benchmark lives in
benchmarks/pipeline_bench.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common

FUSED_ROUNDS = 9


def _synth_candidates(key, q_n, c_total, run, n):
    """Gather-shaped candidates: ascending runs of random indices, each run
    padded with -1 past a random fill count (what _stage_gather emits)."""
    kv, kc = jax.random.split(key)
    windows = c_total // run
    vals = jax.random.randint(kv, (q_n, windows, run), 0, n, dtype=jnp.int32)
    vals = jnp.sort(vals, axis=-1)
    count = jax.random.randint(kc, (q_n, windows, 1), 0, run + 1)
    pos = jnp.arange(run)[None, None, :]
    return jnp.where(pos < count, vals, -1).reshape(q_n, c_total)


def run():
    from repro.kernels.l1_topk import ops as l1
    from repro.kernels.hash_pack import ops as hp
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.query_fused import ops as qf

    key = jax.random.PRNGKey(0)
    q = jax.random.uniform(key, (8, 30))
    cands = jax.random.uniform(key, (8, 2048, 30))
    mask = jnp.ones((8, 2048), bool)
    _, us = common.timer(lambda: l1.l1_topk(q, cands, mask, k=10), repeats=3)
    yield ("kernel/l1_topk_8x2048", us, "interpret=platform")

    x = jax.random.normal(key, (512, 30))
    proj = jax.random.normal(key, (30, 128))
    _, us = common.timer(lambda: hp.signrp_pack(x, proj), repeats=3)
    yield ("kernel/hash_pack_512x128", us, "interpret=platform")

    qkv = jax.random.normal(key, (1, 4, 256, 64))
    _, us = common.timer(
        lambda: fa.flash_attention(qkv, qkv[:, :2], qkv[:, :2], causal=True), repeats=3
    )
    yield ("kernel/flash_attn_256", us, "interpret=platform")

    # --- fused megakernel vs staged chain, head excluded (DESIGN.md §4).
    # Shapes match pipeline_bench's chunk: Q=64 queries x C=2048 gathered
    # candidates (run=64 ascending windows) against n=131072 points.
    from repro.core import pipeline
    from repro.kernels import blocking

    if not blocking.resolve_interpret(None):
        # compiled, there is no fused f32 tail to time: Mosaic refuses its
        # body (query_fused.XLA_STAGES) and the pallas backend runs the chain
        yield ("kernel/query_tail_fused_over_staged", 0.0, "fused=interpret-only")
        return

    n, d, q_n, c_total, run_len, cc, k = 131072, 64, 64, 2048, 64, 256, 10
    data = jax.random.uniform(jax.random.PRNGKey(1), (n, d))
    qs = jax.random.uniform(jax.random.PRNGKey(2), (q_n, d))
    cand = _synth_candidates(jax.random.PRNGKey(3), q_n, c_total, run_len, n)

    def staged(cand_, qs_):
        cs, uq, comps = pipeline._stage_dedup(cand_)
        comp_cand, comp_valid, _ = pipeline._stage_compact(cs, uq, comps, cc)
        pts = data[jnp.clip(comp_cand, 0, n - 1)]
        return l1.l1_topk(qs_, pts, comp_valid, k=k)

    staged_jit = jax.jit(staged)

    def fused(cand_, qs_):
        return qf.query_tail(data, qs_, cand_, run=run_len, c_comp=cc, k=k)

    jax.block_until_ready(staged_jit(cand, qs))  # compile
    jax.block_until_ready(fused(cand, qs))
    t_staged, t_fused = [], []
    for _ in range(FUSED_ROUNDS):  # interleaved: load drift hits both
        _, us_s = common.timer(lambda: staged_jit(cand, qs))
        _, us_f = common.timer(lambda: fused(cand, qs))
        t_staged.append(us_s)
        t_fused.append(us_f)
    us_s, us_f = float(np.median(t_staged)), float(np.median(t_fused))
    yield (f"kernel/query_tail_staged_{q_n}x{c_total}", us_s, "chain=dedup+compact+l1")
    yield (f"kernel/query_tail_fused_{q_n}x{c_total}", us_f, "chain=megakernel")
    yield (
        "kernel/query_tail_fused_over_staged", 0.0,
        f"ratio={us_f / max(us_s, 1e-9):.3f}",
    )
