"""Real query rows per micro-batch the window executed."""


def read(run):
    if not run.batches:
        return None
    return sum(b.rows for b in run.batches) / len(run.batches)
