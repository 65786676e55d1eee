"""Roofline methodology tests.

XLA's cost_analysis counts while-bodies once, so the roofline terms are
analytic (benchmarks/roofline.py); these tests close the loop by checking
the analytic FLOPs against a LOOP-FREE single-layer HLO lowering.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import roofline
from repro import configs
from repro.launch.dryrun import collective_bytes
from repro.models import api, dense

jax.config.update("jax_platform_name", "cpu")


def _single_layer_flops_hlo(cfg, batch, seq):
    """cost_analysis of one unscanned layer forward (no inner loops)."""
    cfg = dataclasses.replace(cfg, q_chunk=seq)  # single attention chunk
    model = api.build_model(cfg)
    ldefs = dense.layer_defs(cfg)
    from repro.models import params as PM

    lp = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
        ldefs,
        is_leaf=lambda x: hasattr(x, "logical"),
    )
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)

    def f(lp, x):
        return dense.block_train(cfg, lp, x, jnp.arange(seq))

    compiled = jax.jit(f).lower(lp, x).compile()
    return float(compiled.cost_analysis()["flops"])


@pytest.mark.parametrize("arch_id", ["granite-8b", "qwen3-32b"])
def test_analytic_layer_flops_vs_hlo(arch_id):
    cfg = configs.get(arch_id)
    batch, seq = 1, 512
    tokens = batch * seq
    hlo = _single_layer_flops_hlo(cfg, batch, seq)
    analytic = roofline._layer_matmul_flops(cfg, tokens) + batch * roofline._attn_flops(
        cfg, seq, seq, causal=True
    )
    ratio = hlo / analytic
    assert 0.85 < ratio < 1.15, (hlo, analytic, ratio)


def test_roofline_terms_all_cells():
    for arch_id in configs.ARCH_IDS:
        cfg = configs.get(arch_id)
        for cell in api.SHAPE_CELLS:
            if api.cell_skip_reason(cfg, cell):
                continue
            t = roofline.analytic_terms(cfg, cell, (16, 16))
            s = roofline.terms_seconds(t)
            assert t["flops"] > 0 and t["bytes_hbm"] > 0, (arch_id, cell)
            assert all(v >= 0 for v in s.values())
            mf = roofline.model_flops_6nd(cfg, cell)
            # compiled compute within sane factor of the 6ND yardstick
            if cell == "train_4k" and cfg.family in ("dense",):
                assert 0.3 < mf / t["flops"] <= 1.25, (arch_id, mf / t["flops"])


def test_train_dominated_by_compute_decode_by_memory():
    cfg = configs.get("granite-8b")
    t_train = roofline.terms_seconds(roofline.analytic_terms(cfg, "train_4k", (16, 16)))
    t_dec = roofline.terms_seconds(roofline.analytic_terms(cfg, "decode_32k", (16, 16)))
    assert max(t_train, key=t_train.get) == "compute_s"
    assert max(t_dec, key=t_dec.get) == "memory_s"


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[8,128] all-gather(bf16[1,128] %x), replica_groups={}
  %ar.1 = f32[256] all-reduce(f32[256] %y), to_apply=%add
  %rs = f32[2,64] reduce-scatter(f32[2,512] %z), dimensions={1}
  %cp = u32[16] collective-permute(u32[16] %w)
  %agstart = bf16[4,4] all-gather-start(bf16[1,4] %v)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2 + 4 * 4 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 2 * 64 * 4
    assert out["collective-permute"] == 16 * 4


def test_artifacts_cover_all_cells():
    """The shipped dry-run artifacts enumerate all 40 cells x 2 meshes."""
    import glob, json, os

    arts = glob.glob(os.path.join(roofline.ARTIFACT_DIR, "*.json"))
    if len(arts) < 80:
        pytest.skip("dry-run artifacts not generated in this checkout")
    by_key = {}
    for p in arts:
        r = json.load(open(p))
        by_key[(r["arch"], r["cell"], r["mesh"])] = r["status"]
    assert len(by_key) == 80
    assert all(v in ("ok", "skip") for v in by_key.values()), by_key
    assert sum(v == "ok" for v in by_key.values()) == 62
