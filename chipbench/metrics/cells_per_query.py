"""Cells the router sent each real query row to, averaged over the
window's rows: the program's own route mask (``routed``, (nu, p, Q)) of
each micro-batch, read over its real rows."""
import numpy as np


def read(run):
    cells = rows = 0
    for b in run.batches:
        if b.rows:
            cells += int(np.asarray(b.result.routed)[:, :, : b.rows].sum())
            rows += b.rows
    return cells / rows if rows else None
