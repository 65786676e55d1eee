"""Tests of the chip benchmark's yardstick, on the CPU and at tiny sizes.

Nothing here describes a TPU: the harness's runs go on without one
(``require_tpu=False``) at sizes the CPU answers in seconds.
"""
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import compare, data, harness, readers, reference, roofline, traffic
from chipbench import trace as tr
from chipbench.tests import tiny

ROOT = tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _mix(name):
    return harness.load_json(os.path.join(ROOT, "chipbench", "traffic", f"{name}.json"))


# ------------------------------------------------------------- data, traffic


def test_rows_depend_only_on_seed_and_row():
    seed = 2**31 + 5
    whole = data.rows(seed, 0, 9000, 30)
    pieces = [data.rows(seed, lo, min(lo + 1234, 9000), 30) for lo in range(0, 9000, 1234)]
    np.testing.assert_array_equal(np.concatenate(pieces), whole)
    np.testing.assert_array_equal(data.rows(seed, 4000, 4100, 30), whole[4000:4100])
    assert not np.array_equal(data.rows(seed + 1, 0, 100, 30), whole[:100])


def test_rows_equal_the_program_generator_they_copy():
    from repro.data import windows

    spec = windows.SyntheticWindowSpec(n=9000, seed=123)
    want, _ = windows.synth_window_slice(spec, 100, 8200)
    np.testing.assert_array_equal(data.rows(123, 100, 8200, 30), want)


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_open_schedule_count_and_mean_rate(seed):
    mix = dict(_mix("icu-steady"), rate_per_s=150.0)
    pool = np.arange(1000 * 30, dtype=np.float32).reshape(1000, 30)
    reqs = traffic.requests(mix, seed, 10.0, pool)
    assert len(reqs) == 1500
    due = np.asarray([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 10.0
    assert len(reqs) / 10.0 == pytest.approx(mix["rate_per_s"])
    assert all(r.rows.shape[0] == 1 for r in reqs)  # one bed's window each
    assert len({r.tenant for r in reqs}) == mix["tenants"]


def test_seeds_permute_the_same_work():
    mix = dict(_mix("icu-steady"), rate_per_s=100.0)
    pool = np.zeros((512, 30), np.float32)
    a = traffic.requests(mix, 7, 5.0, pool)
    b = traffic.requests(mix, 8, 5.0, pool)
    for key in (lambda r: r.rows.shape[0], lambda r: r.tenant):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = lambda rs: sorted(np.round(np.diff([r.due_s for r in rs] + [5.0]), 9))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b))
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_closed_sequence_sizes():
    mix = _mix("backfill")
    reqs = traffic.requests(mix, 3, 10.0, np.zeros((4096, 30), np.float32))
    sizes = np.asarray([r.rows.shape[0] for r in reqs])
    assert len(reqs) == mix["sequence"]
    assert np.all(sizes == 128)


def test_percentiles_are_taken_over_all_requests():
    rng = np.random.default_rng(0)
    lat = rng.exponential(50.0, 1001)
    for q in (50, 99):
        assert traffic.percentile(lat, q) == pytest.approx(np.percentile(lat, q))

    class Ticket:
        def __init__(self, s, status="done"):
            self.latency_s, self.status = s, status

    run = harness.Run({}, {}, {}, 0, 1.0, 1, "cpu")
    run.tickets = [Ticket(v / 1e3) for v in lat] + [Ticket(9.0, "shed")]
    assert readers.latency_percentile(run, 99) == pytest.approx(np.percentile(lat, 99))


# ---------------------------------------------------- the files, by name


def test_every_named_file_loads():
    bench = tiny.bench()
    for w in bench["workloads"]:
        cell, config, mix = harness.cell_parts(bench, w["name"])
        assert config["name"] == w["config"] and mix["loop"] in ("open", "closed")
        e2e = harness.metrics_of(bench, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.metrics_of(bench, w["name"], "per_layer")
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(harness.load_reader(m["name"]).read)


def test_benchmark_json_keeps_the_contract():
    bench = tiny.bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and os.path.isfile(os.path.join(ROOT, c["file"]))
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(pairs) // 2)
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m.get("workloads", cells):
            assert m["moves"] in {x["name"] for x in harness.metrics_of(bench, cell, "end_to_end")}


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = copy.deepcopy(tiny.bench())
    config = harness.load_json(os.path.join(ROOT, "chipbench/configs/ahe-grid10x1.json"))
    config["name"] = "ahe-grid10x1-i8"
    (tmp_path / "chipbench/configs/ahe-grid10x1-i8.json").write_text(json.dumps(config))
    (tmp_path / "chipbench/traffic/icu-burst.json").write_text(
        json.dumps(dict(_mix("icu-steady"), rate_per_s=50.0)))
    (tmp_path / "chipbench/metrics/queue_rows.py").write_text("def read(run):\n    return 1.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="ahe-grid10x1-i8",
                                 file="chipbench/configs/ahe-grid10x1-i8.json"))
    bench["workloads"].append({"name": "ahe-grid10x1-i8.icu-burst", "config": "ahe-grid10x1-i8",
                               "traffic": "icu-burst", "chips": 1, "why": "a new cell"})
    bench["per_layer"].append({"name": "queue_rows", "unit": "rows", "better": "lower",
                               "source": "program_counter", "layer": "serve front end",
                               "moves": "p50_ms", "workloads": ["ahe-grid10x1-i8.icu-burst"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "p50_ms":
            m["workloads"].append("ahe-grid10x1-i8.icu-burst")
    cell, config, mix = harness.cell_parts(bench, "ahe-grid10x1-i8.icu-burst", root=str(tmp_path))
    assert config["name"] == "ahe-grid10x1-i8" and mix["rate_per_s"] == 50.0
    layer = harness.metrics_of(bench, "ahe-grid10x1-i8.icu-burst", "per_layer")
    assert [m["name"] for m in layer] == ["queue_rows"]
    assert harness.load_reader("queue_rows", root=str(tmp_path)).read(None) == 1.0


# ------------------------------------------------------ trace reduction


def _synthetic_trace():
    us = 1e3
    ops = tr.Ops.of([
        ("%while.4 = (s32[]) while(s32[] %x), body=%body", 0 * us, 20 * us),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0 * us, 10 * us),
        ("%l1_pallas.7 = (f32[8,10], s32[8,10]) custom-call(f32[8,32,1] %q)", 12 * us, 20 * us),
        ("%all-gather.3 = f32[4,8,10] all-gather(f32[8,10] %l1_pallas.7)", 30 * us, 35 * us),
        ("%fusion.2 = f32[8] fusion(f32[8] %all-gather.3)", 50 * us, 60 * us),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 62 * us, 66 * us),
    ])
    host = {"bench.window": [(0.0, 100 * us)],
            "bench.pump": [(0.0, 40 * us), (45 * us, 70 * us)],
            "bench.wait": [(40 * us, 45 * us), (70 * us, 100 * us)]}
    return tr.Trace([ops], host)


def test_trace_reduction_on_a_synthetic_trace():
    t = _synthetic_trace()
    ops = t.chips[0]
    us = 1e3
    assert tr.busy_ns(ops, [t.window]) == pytest.approx(39 * us)
    assert tr.busy_ns(ops, t.host["bench.pump"]) == pytest.approx(39 * us)
    assert tr.op_ns(ops, [t.window], "l1_topk") == (pytest.approx(8 * us), 1)
    assert tr.op_ns(ops, [t.window], "collective") == (pytest.approx(5 * us), 1)
    assert tr.op_ns(ops, [t.window], "hash_pack") == (0.0, 0)
    np.testing.assert_allclose(
        tr.per_span_ns(ops, t.host["bench.pump"], np.ones(6, bool)), [43 * us, 14 * us])
    top = tr.top_ops(t, [t.window])  # the loop's span holds its body: left out
    assert top[0] == ["%fusion.1 = f32[8] fusion(f32[8] %a)", pytest.approx(14e-6)]
    assert len(top) == 4
    gaps = tr.idle_gaps(t, n=2)
    assert gaps == [["bench.wait", pytest.approx(34e-6)], ["bench.wait", pytest.approx(15e-6)]]
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]

    run = harness.Run({}, {}, {}, 0, 1.0, 1, "TPU v5 lite")
    run.trace = t
    run.batches = [harness.Batch(3, 8, [], None), harness.Batch(5, 8, [], None)]
    assert readers.device_ms_per_batch(run) == pytest.approx(39e-3 / 2)
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("device_idle_in_batch.lat") == pytest.approx(100 * (1 - 39 / 65))
    assert read("device_idle.tput") == pytest.approx(61.0)
    assert read("pad_share.lat") == pytest.approx(100 * 8 / 16)
    assert read("rows_per_batch.lat") == 4.0


def test_trace_load_reads_a_recorded_host_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.ones((256,))
    f(x).block_until_ready()
    harness.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.pump"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    assert len(t.host["bench.pump"]) == 3 and len(t.host["bench.window"]) == 1
    assert t.chips == []  # a CPU run has no device plane: no device metric


def test_roofline_arithmetic_at_the_cell_shapes():
    config = harness.load_json(os.path.join(ROOT, "chipbench/configs/ahe-grid10x1.json"))
    assert roofline.chunks(128, 64) == [64, 64] and roofline.chunks(8, 64) == [8]
    assert roofline.compact_width(config["slsh"], 137000, 63) == 1024
    assert roofline.compact_width(dict(config["slsh"], c_comp=0), 137000, 1) == 640
    calls = roofline.l1_topk_calls(config, 128, 1)
    assert len(calls) == 2 * 10
    assert calls[0] == (3 * 64 * 1024 * 30, 4 * 64 * 1024 * 30 + 64 * 1024 + 4 * 64 * 30 + 8 * 64 * 10)
    hp = roofline.hash_pack_calls(config, 32, 1)
    assert len(hp) == 2 * 10 + 1  # per cell outer and inner, and the router
    assert hp[0][0] == 32 * 63 * 125
    assert hp[1][0] == 2 * 32 * 30 * 20 * 65
    assert hp[-1][0] == 32 * 63 * 125
    t, bound = roofline.least_time(calls, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(20 * calls[0][1] / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ------------------------------------------------ reference and control


def _tiny_data(seed=2**31 + 9, n=2048):
    return data.dataset(seed, n, 64, 30)


def test_the_bfloat16_control_fails_the_check():
    seed = 2**31 + 9
    points, queries = _tiny_data(seed)
    config = harness.load_json(os.path.join(ROOT, "chipbench/configs/ahe-grid10x1.json"))
    prm = dict(config["slsh"], **tiny.TINY_SLSH)
    want = reference.Reference(points, seed, prm, 2, 2).query(queries)
    low = reference.Reference(points, seed, prm, 2, 2, "bfloat16").query(queries)
    values = compare.numbers({k: low[k] for k in low}, want)
    assert not compare.passed(compare.judge(config, dict(values, unanswered=0)))
    assert values["dist_gap"] > 10 * config["limits"]["dist_gap"]


def test_the_reference_answer_does_not_depend_on_the_cores():
    """A node's tables split over more cores give the same distinct
    neighbours: a point two cores both return is one neighbour. (Only
    without the compaction cap: ``c_comp`` keeps each cell's smallest
    indices, and a cell of fewer tables keeps others.)"""
    seed = 2**31 + 11
    points, queries = _tiny_data(seed)
    prm = dict(harness.load_json(os.path.join(ROOT, "chipbench/configs/ahe-grid10x1.json"))["slsh"],
               **dict(tiny.TINY_SLSH, c_comp=0))
    one = reference.Reference(points, seed, prm, 2, 1).query(queries)
    two = reference.Reference(points, seed, prm, 2, 2).query(queries)
    np.testing.assert_array_equal(one["knn_idx"], two["knn_idx"])
    np.testing.assert_array_equal(one["knn_dist"], two["knn_dist"])
    assert compare.repeated_rows(two["knn_idx"]) == 0
    assert compare.repeated_rows(np.array([[3, 1, 3], [1, 2, -1], [-1, -1, -1]])) == 1


# ------------------------------------------------ faults in the timed path


def _altered(index, query):
    def run(q, **kw):
        res = query(q, **kw)
        return res._replace(knn_idx=res.knn_idx.at[0, 0].add(1))
    return run


def _half_batch(index, query):
    """The second half of a micro-batch's real rows is not computed: those
    rows get the first row's answer."""
    def run(q, **kw):
        q = np.array(q)
        pad = np.all(q == q[:1], axis=1)[::-1]  # padding repeats row 0
        n_real = len(q) - int(np.argmin(pad)) if not pad.all() else 1
        q[-(-n_real // 2):] = q[0]
        return query(q, **kw)
    return run


def _exchange_left_out(index, query):
    def run(q, **kw):
        drop = np.arange(index.deploy.nu) > 0  # only node 0's answers merge
        return query(q, drop_mask=drop, **kw)
    return run


FAULTS = {"none": None, "answer_altered": _altered, "half_batch_left_out": _half_batch,
          "exchange_left_out": _exchange_left_out}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_timed_path_reads_incorrect(fault):
    name = "ahe-grid10x1.icu-steady"
    parts = tiny.parts(name, n_points=2048, rate=400.0)  # one-row requests coalesce
    parts[2]["ladder"] = [8]
    out = harness.run_cell(tiny.bench(), name, 2**31 + 21, 0.5, False, t_start=0.0,
                           require_tpu=False, parts=parts, patch=FAULTS[fault])
    assert out["correct"] == (fault == "none"), out["compared"]
    assert list(out)[-1] == "compared"
    if fault == "none":  # the reference agrees with the program exactly
        values = {k: v["value"] for k, v in out["compared"].items()}
        assert values.pop("dist_gap") < 1e-6 and not any(values.values())


# ------------------------------------------------------------- the command


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ahe-grid10x1.icu-steady",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_the_command_refuses_without_an_accelerator():
    out = _command(ROOT)
    assert out.returncode != 0 and "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_the_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    out = _command(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
