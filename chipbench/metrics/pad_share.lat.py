"""Ladder padding rows over all rows computed, in %."""


def read(run):
    total = sum(b.bucket for b in run.batches)
    if total == 0:
        return None
    return 100.0 * sum(b.bucket - b.rows for b in run.batches) / total
