"""Streaming DSLSH quickstart: live ICU monitoring over an ABP stream.

A StreamingMonitor warms up on seven historical patient records, then
replays an eighth record as a live timestamped stream
(``windows.stream_windows_from_record``): each arriving batch of lag
windows is first classified (rolling AHE prediction with per-event
latency), then ingested into the sharded streaming index — queryable
immediately, no rebuild. Nodes compact automatically when their delta
segments fill.

Run:  PYTHONPATH=src python examples/stream_quickstart.py
"""
import jax
import numpy as np

from repro import dslsh, stream
from repro.data import abp, windows
from repro.runtime import compile_cache

compile_cache.enable()

# --- dataset: 8 synthetic ABP records; 7 historical + 1 live (paper §4)
cfg_abp = abp.ABPConfig(n_beats=60_000, episode_rate=1.0 / 2500.0)
mapv, valid = abp.synth_dataset_beats(jax.random.PRNGKey(0), 8, cfg_abp)
mapv, valid = np.asarray(mapv), np.asarray(valid)
hist = windows.build_dataset(mapv[:7], valid[:7], windows.AHE_51_5C)
live_pts, live_lab, live_ts = windows.stream_windows_from_record(
    mapv[7], valid[7], windows.AHE_51_5C
)
print(f"history={hist['points'].shape[0]} windows "
      f"(pct_no_ahe={hist['pct_no_ahe']:.1f}%)  "
      f"live={live_pts.shape[0]} windows ({int(live_lab.sum())} AHE)")

# --- warm the sharded streaming index on the historical windows
grid = dslsh.Grid(nu=2, p=2)
cfg = dslsh.make_config(
    dslsh.FamilyConfig(m_out=24, L_out=8, m_in=12, L_in=4, alpha=0.01,
                       val_lo=20.0, val_hi=180.0),
    dslsh.BudgetConfig(k=10, c_max=128, c_in=32, h_max=8, p_max=256),
    dslsh.RuntimeConfig(query_chunk=16),
)
n_warm = hist["points"].shape[0] // grid.nu * grid.nu
monitor = stream.StreamingMonitor(
    jax.random.PRNGKey(1), hist["points"][:n_warm], hist["labels"][:n_warm],
    cfg, grid,
    node_capacity=n_warm // grid.nu + 1024, delta_cap=64, t0=0.0,
    # a live window's label is only observable once its condition window
    # closes — no look-ahead leaks into the rolling MCC
    label_delay_s=float(windows.AHE_51_5C.cond_beats),
)
print(f"warm: nu={grid.nu} x p={grid.p} cells, n_index={monitor.n_index()}")

# --- live phase: predict-then-ingest, timestamped in beats (~seconds)
events = monitor.replay(live_pts, live_lab, live_ts, batch_size=16)

lat = np.asarray([e.latency_s for e in events if e.preds])
print(f"streamed {live_pts.shape[0]} windows over "
      f"{live_ts[-1] - live_ts[0]:.0f} beats in {len(events)} events; "
      f"n_index={monitor.n_index()}  compactions={sum(e.compacted for e in events)}")
print(f"prediction latency: median={np.median(lat)*1e3:.1f} ms  "
      f"p95={np.percentile(lat, 95)*1e3:.1f} ms")
print(f"rolling MCC={monitor.mcc():.3f}  "
      f"(median per-cell comparisons="
      f"{np.median([e.comparisons for e in events if e.preds]):.0f})")
print(f"routing: median fraction of cells visited per batch="
      f"{np.median([e.routed_frac for e in events if e.preds]):.2f} "
      f"(DESIGN.md §10 — 1.00 would mean the Forwarder broadcast)")
