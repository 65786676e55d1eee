"""Median time an answered request of the window waited in the front
end's queue, in ms: from when it was due (``submitted_at``) to the start
of the pump whose micro-batch carried it (the program's ``started_at``
stamp). None where the program stamps no ``started_at``."""
from chipbench import traffic


def read(run):
    waits = [1e3 * (t.started_at - t.submitted_at) for t in run.tickets
             if t.status == "done" and getattr(t, "started_at", None) is not None]
    return traffic.percentile(waits, 50) if waits else None
