"""The benchmark's run: data, build, warm-up, the measured window, the
check against the reference, and the result line.

Everything a cell needs is found by name: the configuration file
(``configs/<config>.json``), the traffic mix (``traffic/<mix>.json``) and
one reader per metric (``metrics/<metric>.py``, a ``read(run)`` that
returns a number or None). The program is driven only through
``repro.dslsh`` and its serving front end (``Index.frontend``,
``submit``, ``pump``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from chipbench import compare, data, readers, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LEAD_S = 0.05  # the schedule starts this long after the loop is ready
TRACE_S = 1.5  # the traced run profiles this much of the window's end


class Refused(Exception):
    """The run cannot be made here (no accelerator, too few chips)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, root: str = ROOT):
    """The reader module of metric ``name`` (``metrics/<name>.py``)."""
    path = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_parts(bench: dict, name: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """(cell entry, configuration file, traffic file) of workload ``name``,
    found by the names in ``bench`` under checkout ``root``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(root, "chipbench", "traffic", f"{cell['traffic']}.json"))
    return cell, config, mix


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell ``cell`` reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Batch:
    rows: int  # real query rows
    bucket: int  # rows computed, padding included
    spans: list  # (ticket, lo, hi) of each request served
    result: object  # the program's DistributedQueryResult
    traced: bool = False  # ran inside the traced part of the window
    pump_s: float = 0.0  # host clock around the pump() that ran it
    query_s: float = 0.0  # host clock around Index.query inside that pump


@dataclasses.dataclass
class Run:
    """What the readers of metrics get."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    chips: int
    device_kind: str
    setup_s: float = 0.0
    build_s: float = 0.0
    window_s: float = 0.0
    tickets: list = dataclasses.field(default_factory=list)
    batches: list = dataclasses.field(default_factory=list)
    rows_done_in_window: int = 0
    trace: object = None  # chipbench.trace.Trace of the traced window


class Recorder:
    """Wraps ``Index.query`` so that the run keeps each micro-batch's
    result (counters included), which the front end does not hand back."""

    def __init__(self, index, patch=None):
        self.results: list = []
        self.seconds: list = []
        self.on = False
        inner = index.query if patch is None else patch(index, index.query)

        def query(q, **kw):
            t = time.perf_counter()
            res = inner(q, **kw)
            if self.on:
                self.results.append(res)
                self.seconds.append(time.perf_counter() - t)
            return res

        index.query = query


def devices_for(cell: dict, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise Refused(f"the cell needs {cell['chips']} chips, JAX sees {len(devs)}")
    return devs[: cell["chips"]]


def build(config: dict, points: np.ndarray, seed: int, devices):
    """The deployment the configuration states, built over ``points``."""
    import jax

    from repro import dslsh

    cfg = dslsh.make_config(**config["slsh"])
    dep = config["deployment"]
    key = jax.random.PRNGKey(seed)
    if dep["kind"] == "grid":
        deploy = dslsh.grid(nu=dep["nu"], p=dep["p"], routed=dep["routed"])
        return dslsh.build(key, points, cfg, deploy)
    if dep["kind"] == "mesh":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        grid = np.asarray(devices).reshape(dep["nu"], dep["p"])
        mesh = Mesh(grid, ("data", "model"))
        pts = jax.device_put(points, NamedSharding(mesh, PartitionSpec("data", None)))
        deploy = dslsh.mesh(mesh, reducer=dep["reducer"], routed=dep["routed"])
        return dslsh.build(key, pts, cfg, deploy)
    raise ValueError(f"unknown deployment kind {dep['kind']!r}")


class Tracing:
    """The profiler over the last ``seconds`` of the window. A trace of a
    whole window of this program is millions of device operations, more
    than a run can read back in its time; and stopping the profiler stalls
    the host for seconds, so it stops only once the window has closed.
    ``poll(t_end)`` starts it when the window's end is near."""

    def __init__(self, logdir: str | None, seconds: float):
        self.logdir, self.seconds = logdir, seconds
        self.active = self.done = False

    def poll(self, t_end: float) -> None:
        if (self.logdir is None or self.active or self.done
                or time.perf_counter() < t_end - self.seconds):
            return
        import jax

        start_trace(self.logdir)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        if self.active:
            import jax

            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def span(self, name: str):
        if self.active:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


NO_TRACE = Tracing(None, 0.0)


class GcPauses:
    """Times the interpreter's garbage collections inside a block."""

    def __enter__(self):
        self.pauses: list[tuple[int, float]] = []
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def summary(self) -> str:
        out = []
        for g in (0, 1, 2):
            p = [s for gen, s in self.pauses if gen == g]
            if p:
                out.append(f"gen{g} n={len(p)} total_ms={1e3 * sum(p):.1f}"
                           f" max_ms={1e3 * max(p):.1f}")
        return " ".join(out) or "none"


def start_trace(logdir: str) -> None:
    """Start the profiler with the Python tracer off: it would record every
    call the host makes (the wait loop's clock reads among them)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def _pump(fe, rec: Recorder, run: Run, tr: Tracing) -> list:
    n0 = len(rec.results)
    traced = tr.active
    t0 = time.perf_counter()
    with tr.span("bench.pump"):
        done = fe.pump()
    took = time.perf_counter() - t0
    if len(rec.results) > n0:
        spans, lo = [], 0
        for t in done:
            if t.status == "done":
                spans.append((t, lo, lo + t.n_queries))
                lo += t.n_queries
        res = rec.results[-1]
        run.batches.append(Batch(lo, int(res.knn_idx.shape[0]), spans, res, traced, took,
                                 rec.seconds[-1]))
    return done


def window_open(fe, rec, run: Run, reqs, deadline_s: float, tr: Tracing = NO_TRACE) -> dict:
    """Open loop: each request is submitted when due (``submit(now=due)``,
    so its latency runs from then), and the front end pumps whenever rows
    are queued. Returns the generator's lateness."""
    clock = time.perf_counter
    n = len(reqs)
    lag = np.zeros(n)
    i = 0
    start = clock() + LEAD_S
    t0 = clock()
    while i < n or fe.queue_depth:
        tr.poll(start + run.seconds)
        now = clock()
        if i < n and start + reqs[i].due_s <= now:
            with tr.span("bench.submit"):
                while i < n and start + reqs[i].due_s <= now:
                    due = start + reqs[i].due_s
                    run.tickets.append(fe.submit(
                        reqs[i].rows, tenant=reqs[i].tenant,
                        deadline_s=deadline_s, now=due,
                    ))
                    lag[i] = now - due
                    i += 1
        if fe.queue_depth:
            _pump(fe, rec, run, tr)
        elif i < n:
            with tr.span("bench.wait"):
                due = start + reqs[i].due_s
                while clock() < due - 2e-3:
                    time.sleep(1e-3)
                while clock() < due:
                    pass
    tr.stop()
    run.window_s = clock() - t0
    run.rows_done_in_window = sum(t.n_queries for t in run.tickets if t.status == "done")
    return {"lag_p50_ms": 1e3 * float(np.median(lag)),
            "lag_max_ms": 1e3 * float(lag.max()) if n else 0.0}


def window_closed(fe, rec, run: Run, reqs, deadline_s: float, tr: Tracing = NO_TRACE) -> dict:
    """Closed loop: ``clients`` callers each keep one request outstanding
    and send the next as soon as the last is answered. The window closes
    with the first micro-batch that ends after ``seconds``, so that it holds
    whole micro-batches only; requests still open then are answered, and
    checked, but their rows are not counted in the window."""
    clock = time.perf_counter
    n_clients = int(run.mix["clients"])
    nxt = 0

    def send():
        nonlocal nxt
        if nxt >= len(reqs):
            raise RuntimeError("the closed-loop sequence ran out: raise 'sequence'")
        r = reqs[nxt]
        nxt += 1
        with tr.span("bench.submit"):
            run.tickets.append(fe.submit(r.rows, tenant=r.tenant, deadline_s=deadline_s))

    rows = 0
    t0 = clock()
    end = t0 + run.seconds
    for _ in range(n_clients):
        send()
    while True:
        tr.poll(end)
        done = _pump(fe, rec, run, tr)
        now = clock()
        rows += sum(t.n_queries for t in done if t.status == "done")
        if now > end:
            break
        for _ in done:
            send()
    tr.stop()
    run.window_s = now - t0
    while fe.queue_depth:
        _pump(fe, rec, run, tr)
    run.rows_done_in_window = rows
    return {}


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, parts=None,
             patch=None) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``parts`` gives the cell's (entry, configuration, mix) in place of the
    files, and ``patch(index, query) -> query`` replaces the timed path
    underneath the front end; with ``require_tpu=False`` the run goes on
    without an accelerator. Tests and rehearsals use these three."""
    import jax

    from repro import obs

    cell, config, mix = parts or cell_parts(bench, name)
    devices = devices_for(cell, require_tpu)
    run = Run(cell, config, mix, seed, seconds, cell["chips"], devices[0].device_kind)
    d = config["data"]["d"]

    t = time.perf_counter()
    points, pool = data.dataset(seed, config["data"]["n_points"], mix["pool_rows"], d)
    reqs = traffic.requests(mix, seed, seconds, pool)
    data_s = time.perf_counter() - t

    t = time.perf_counter()
    index = build(config, points, seed, devices)
    jax.block_until_ready(index.pipeline_index)
    run.build_s = time.perf_counter() - t

    from repro.serve.frontend import FrontendConfig

    rec = Recorder(index, patch)
    fe = index.frontend(FrontendConfig(ladder=tuple(mix["ladder"])))
    t = time.perf_counter()
    fe.warmup()
    warm_s = time.perf_counter() - t
    retraces = obs.query_retraces()
    deadline_s = math.inf if mix["deadline_s"] is None else float(mix["deadline_s"])
    run.setup_s = time.perf_counter() - t_start
    log(f"setup: data_s={data_s:.3f} build_s={run.build_s:.3f} warmup_s={warm_s:.3f}"
        f" setup_s={run.setup_s:.3f} (rungs {tuple(mix['ladder'])})")

    trace_dir = os.path.join(HERE, ".trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tr = Tracing(trace_dir, TRACE_S) if trace else NO_TRACE
    rec.on = True
    window = window_open if mix["loop"] == "open" else window_closed
    with GcPauses() as gc_pauses:
        gen = window(fe, rec, run, reqs, deadline_s, tr)
    rec.on = False
    compiles = obs.query_retraces() - retraces
    stats = fe.stats()
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0) for dv in devices)
    slow = sorted(run.batches, key=lambda b: -b.pump_s)
    log(f"pumps: p50_ms={1e3 * np.median([b.pump_s for b in slow]):.3f}"
        " longest_ms(rung, ms in Index.query)="
        + ",".join(f"{1e3 * b.pump_s:.1f}({b.bucket},{1e3 * b.query_s:.1f})"
                   for b in slow[:8])
        + f"; gc: {gc_pauses.summary()}")
    log(f"window: {len(run.tickets)} requests, {len(run.batches)} micro-batches,"
        f" window_s={run.window_s:.3f} compiles_in_window={compiles}"
        f" shed={stats.shed} timed_out={stats.timed_out} latency_ms p50/p95/p99="
        + "/".join(f"{readers.latency_percentile(run, q) or 0:.1f}" for q in (50, 95, 99))
        + " "
        + " ".join(f"{k}={v:.3f}" for k, v in gen.items()))
    if trace:
        from chipbench import trace as trace_mod

        t = time.perf_counter()
        run.trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {sum(len(o.names) for o in run.trace.chips)} device operations"
            f" on {len(run.trace.chips)} chips read in {time.perf_counter() - t:.3f}s")

    if trace:
        from chipbench import roofline

        spans, traced = readers.pump_spans(run), readers.traced_batches(run)
        for k, calls in roofline.CALLS.items():
            seen = sum(trace_mod.op_ns(ops, spans, k)[1] for ops in run.trace.chips)
            model = run.chips * sum(len(calls(config, b.bucket, run.chips)) for b in traced)
            log(f"trace: {k} calls seen {seen}, modelled {model},"
                f" over {len(spans)} traced micro-batches ({len(traced)} recorded)")

    kinds = ["per_layer"] if trace else ["end_to_end"]
    metrics = {}
    for kind in kinds:
        for m in metrics_of(bench, name, kind):
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the answers: pull the sample off the device, free the program's
    # state, then run the reference
    got = compare.sample(run)
    failed = sum(t.status != "done" for t in run.tickets)
    del fe, index, rec
    run.batches = []
    t = time.perf_counter()
    want = compare.answers(run, points, got.queries)
    values = compare.numbers(dataclasses.asdict(got), want)
    values["unanswered"] = failed
    compared = compare.judge(config, values)
    correct = compare.passed(compared)
    log(f"reference: {got.requests} requests, {got.queries.shape[0]} rows checked"
        f" in {time.perf_counter() - t:.3f}s; served rows with a repeated neighbour:"
        f" {compare.repeated_rows(got.knn_idx)}")
    for k, v in compared.items():
        log(f"compared {k}: {v['value']} limit {v['limit']}")
    out = {
        "correct": bool(correct),
        "attempted": len(run.tickets),
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(peak),
        },
    }
    if trace:
        from chipbench import trace as trace_mod

        w0, w1 = run.trace.window
        chips = run.trace.chips
        busy = [trace_mod.busy_ns(ops, [(w0, w1)]) for ops in chips]
        out["device"]["busy_s"] = 1e-9 * sum(busy) / max(len(busy), 1)
        out["device"]["window_s"] = 1e-9 * (w1 - w0)
        out["breakdown"] = {
            "device_ops": trace_mod.top_ops(run.trace, [(w0, w1)]),
            "idle_gaps": trace_mod.idle_gaps(run.trace),
        }
    out["compared"] = compared
    return out
