"""ICU end-to-end scenario: streaming AHE prediction with fault tolerance.

Simulates the paper's deployment: a DSLSH cluster answers latency-critical
AHE queries; one node goes down mid-stream (heartbeat missed); the Reducer
first proceeds without it (straggler deadline), then the cluster restores
the lost node's cells in place — surviving cells reused untouched — and
keeps serving. Every phase answers through the same typed ``repro.dslsh``
handle.

Run:  PYTHONPATH=src python examples/icu_pipeline.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import dslsh
from repro.core import predict
from repro.data import abp, windows
from repro.runtime import compile_cache, ft

compile_cache.enable()

# dataset
cfg_abp = abp.ABPConfig(n_beats=60_000, episode_rate=1.0 / 2500.0)
mapv, valid = abp.synth_dataset_beats(jax.random.PRNGKey(0), 8, cfg_abp)
ds = windows.build_dataset(np.asarray(mapv), np.asarray(valid), windows.AHE_51_5C)
train, qx, qy = windows.train_test_split(ds, n_test=300)

deploy = dslsh.grid(nu=4, p=4)
cfg = dslsh.make_config(
    dslsh.FamilyConfig(m_out=24, L_out=16, m_in=12, L_in=4, alpha=0.01,
                       val_lo=20.0, val_hi=180.0),
    dslsh.BudgetConfig(k=10, c_max=128, c_in=32, h_max=8, p_max=256),
)
pts, labs, _ = dslsh.pad_to_multiple(train["points"], train["labels"], deploy.cells)
pts, labs = jnp.asarray(pts), jnp.asarray(labs)
index = dslsh.build(jax.random.PRNGKey(1), pts, cfg, deploy)
print(f"cluster up: nu={deploy.nu} nodes x p={deploy.p} cores, n={pts.shape[0]}")

monitor = ft.HeartbeatMonitor(n_nodes=deploy.nu, deadline_s=0.5)
now = time.time()
for n in range(deploy.nu):
    monitor.beat(n, t=now)


def mcc_of(res, labs_, qy_):
    pred = predict.predict_batch(labs_, res.knn_idx, res.knn_dist)
    return float(predict.mcc(pred, jnp.asarray(qy_)))


# phase 1: healthy cluster
res = index.query(jnp.asarray(qx[:100]))
print(f"phase 1 (healthy):     MCC={mcc_of(res, labs, qy[:100]):.3f}")

# phase 2: node 2 misses its heartbeat -> Reducer proceeds without it
monitor.beat(2, t=now - 10.0)
drop = jnp.asarray(monitor.drop_mask(now=now))
res = index.query(jnp.asarray(qx[100:200]), drop_mask=drop)
print(f"phase 2 (node 2 down, deadline reducer): MCC={mcc_of(res, labs, qy[100:200]):.3f}"
      f"  (answers stay available, recall degrades gracefully)")

# phase 3: permanent failure -> restore node 2's cells in place on the
# same grid (pass the live handle: surviving cells' tables are reused
# untouched, and answers come back bit-identical to the healthy index)
index2, labs2, _ = ft.elastic_reshard_index(
    jax.random.PRNGKey(1), train["points"], train["labels"], cfg, index, [2]
)
res = index2.query(jnp.asarray(qx[200:]))
comps = np.asarray(res.max_comparisons_per_cell)
print(f"phase 3 (node 2 restored on nu={index2.deploy.nu}): MCC="
      f"{mcc_of(res, labs2, qy[200:]):.3f}  "
      f"median comps/proc={float(np.median(comps)):.0f}")
print("pipeline complete: detection -> degraded service -> elastic recovery")
