"""Quickstart: build a distributed SLSH index over synthetic ABP windows and
predict Acute Hypotensive Episodes — the paper's pipeline in ~40 lines.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import dslsh
from repro.core import predict
from repro.data import abp, windows
from repro.runtime import compile_cache

compile_cache.enable()

# 1. Synthesize ABP (MAP) waveforms and build the rolling-window dataset.
cfg_abp = abp.ABPConfig(n_beats=60_000, episode_rate=1.0 / 2500.0)
mapv, valid = abp.synth_dataset_beats(jax.random.PRNGKey(0), 8, cfg_abp)
ds = windows.build_dataset(np.asarray(mapv), np.asarray(valid), windows.AHE_51_5C)
train, qx, qy = windows.train_test_split(ds, n_test=200)
print(f"dataset: {ds['name']}  n={train['points'].shape[0]}  "
      f"%no-AHE={ds['pct_no_ahe']:.1f}")

# 2. Configure DSLSH: a composed config (hash family + static budgets) and a
#    deployment descriptor — nu=2 nodes x p=8 cores, stratified (l1 outer +
#    cosine inner on heavy buckets).
deploy = dslsh.grid(nu=2, p=8)
cfg = dslsh.make_config(
    dslsh.FamilyConfig(m_out=24, L_out=16, m_in=12, L_in=4, alpha=0.01,
                       val_lo=20.0, val_hi=180.0),
    dslsh.BudgetConfig(k=10, c_max=128, c_in=32, h_max=8, p_max=256),
)
pts, labs, _ = dslsh.pad_to_multiple(train["points"], train["labels"], deploy.cells)
pts, labs = jnp.asarray(pts), jnp.asarray(labs)

# 3. Build (the Root broadcasts one hash family; each cell owns L/p tables).
index = dslsh.build(jax.random.PRNGKey(1), pts, cfg, deploy)

# 4. Query -> one typed DistributedQueryResult (Reducer merge + counters),
#    then the weighted K-NN vote.
res = index.query(jnp.asarray(qx))
pred = predict.predict_batch(labs, res.knn_idx, res.knn_dist)
mcc = float(predict.mcc(pred, jnp.asarray(qy)))

# 5. Compare against the exhaustive PKNN baseline.
pkd, pki, pcomps = dslsh.pknn_query(pts, jnp.asarray(qx), 10, deploy.grid)
pred_p = predict.predict_batch(labs, pki, pkd)
mcc_p = float(predict.mcc(pred_p, jnp.asarray(qy)))

max_comps = float(np.median(np.asarray(res.max_comparisons_per_cell)))
print(f"DSLSH:  MCC={mcc:.3f}  median max-comparisons/processor={max_comps:.0f}")
print(f"PKNN:   MCC={mcc_p:.3f}  comparisons/processor={int(pcomps[0,0,0])}")
print(f"speedup in comparisons: {float(pcomps[0,0,0])/max(max_comps,1):.1f}x  "
      f"MCC loss: {mcc_p - mcc:+.3f}")
