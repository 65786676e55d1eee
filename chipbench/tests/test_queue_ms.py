"""The front end's queue time, read from the program's request stamps (CPU,
tiny sizes)."""
import types

import pytest

from chipbench import harness
from chipbench.tests import tiny


def _ticket(submitted_at, started_at=None, status="done"):
    t = types.SimpleNamespace(submitted_at=submitted_at, status=status)
    if started_at is not None:
        t.started_at = started_at
    return t


def test_queue_ms_reads_the_median_wait_of_answered_requests():
    run = harness.Run({}, {}, {}, 0, 1.0, 1, "cpu")
    run.tickets = [_ticket(1.0, 1.010), _ticket(2.0, 2.030), _ticket(3.0, 3.020),
                   _ticket(4.0, 9.0, status="timed_out")]
    assert harness.load_reader("queue_ms.lat").read(run) == pytest.approx(20.0)


def test_queue_ms_is_silent_where_the_program_stamps_nothing():
    run = harness.Run({}, {}, {}, 0, 1.0, 1, "cpu")
    run.tickets = [_ticket(1.0), _ticket(2.0)]  # a program without started_at
    assert harness.load_reader("queue_ms.lat").read(run) is None
    run.tickets = []
    assert harness.load_reader("queue_ms.lat").read(run) is None


def test_a_traced_run_reports_queue_ms(monkeypatch):
    """A traced run of the open-loop cell reports the wait, which is part
    of every request's latency."""
    name = "ahe-grid10x1.icu-steady"
    parts = tiny.parts(name, n_points=2048, rate=200.0)
    runs = []
    window = harness.window_open

    def keep(fe, rec, run, *a, **k):
        runs.append(run)
        return window(fe, rec, run, *a, **k)

    monkeypatch.setattr(harness, "window_open", keep)
    out = harness.run_cell(tiny.bench(), name, 2**31 + 31, 0.5, True, t_start=0.0,
                           require_tpu=False, parts=parts)
    assert out["correct"], out["compared"]
    value = out["metrics"]["queue_ms.lat"]["value"]
    done = [t for t in runs[0].tickets if t.status == "done"]
    assert done and all(t.submitted_at <= t.started_at <= t.submitted_at + t.latency_s
                        for t in done)
    assert 0.0 <= value <= max(1e3 * t.latency_s for t in done)
