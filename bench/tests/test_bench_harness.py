"""The harness's own arithmetic on the CPU: trace reduction, metric
readers, traffic that repeats by seed, network shapes that do not, the
peaks table, and the refusal to run without a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench_testroot import BENCH, REPO
from benchkit import trace as tr
from benchkit.harness import NoChip, device_check, lookup_peaks
from benchkit.layout import Layout
from benchkit.traffic import Request, buckets_for, make_plan

RECORDED = BENCH / "tests" / "data" / "trace_events.json"


def reader(name):
    return Layout(REPO).metric_reader(name).read


# ---------------------------------------------------------------- trace
def test_union_and_coverage():
    merged = tr.union([(5, 9), (0, 2), (1, 3), (9, 10), (20, 20)])
    assert merged == [(0, 3), (5, 10)]
    assert tr.covered(merged, 2, 6) == 2
    assert tr.covered(merged, -5, 50) == 8
    assert tr.clip([(0, 10)], 3, 4) == [(3, 4)]


def _events():
    """Two dispatch spans inside a 100 ns window; device ops overlap
    the second span partly."""
    return {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 30, 40], ["fusion.2", 35, 50],
                ["copy.3", 70, 75], ["fusion.1", 120, 130]],
        "modules": [["jit__run_lanes_impl", 30, 50],
                    ["jit_other", 70, 75]]}},
        "host": [["bench.window", 0, 100], ["bench.run_lanes", 20, 60],
                 ["bench.engine.run_lanes", 25, 55],
                 ["bench.run_lanes", 65, 90]]}


def test_trace_reductions_by_hand():
    t = tr.Trace(_events())
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(25e-9)           # 30-50, 70-75
    assert t.busy_s(module="jit__run_lanes_impl") == pytest.approx(20e-9)
    assert t.host_only_s("bench.run_lanes") == \
        pytest.approx([20e-9, 20e-9])
    assert t.top_ops(2) == [["fusion.1", pytest.approx(10e-9)],
                            ["fusion.2", pytest.approx(15e-9)]][::-1]
    gaps = t.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 25e-9, 20e-9])
    assert [g[0] for g in gaps] == ["bench.window", "bench.run_lanes",
                                    "bench.window"]


def _busy_by_sweep(ops, w0, w1):
    """Busy time by a plain sweep over start/end points."""
    points = sorted([(max(a, w0), 1) for _, a, b in ops if b > w0
                     and a < w1] + [(min(b, w1), -1) for _, a, b in ops
                                    if b > w0 and a < w1])
    busy, depth, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_reduction_of_a_recorded_chip_trace():
    events = json.loads(RECORDED.read_text())
    t = tr.Trace(events)
    (dev, v), = events["devices"].items()
    assert t.busy_s() == pytest.approx(
        _busy_by_sweep(v["ops"], t.w0, t.w1) * 1e-9)
    assert 0 < t.busy_s() <= t.window_s
    spans = t.spans("bench.run_lanes")
    host = t.host_only_s("bench.run_lanes")
    assert len(host) == len(spans) > 0
    assert all(0 <= h <= (b - a) * 1e-9 for h, (a, b) in zip(host, spans))
    top = t.top_ops()
    assert len(top) <= 10 and [x[1] for x in top] == \
        sorted((x[1] for x in top), reverse=True)
    gaps = t.idle_gaps()
    assert sum(g[1] for g in gaps) <= t.window_s - t.busy_s() + 1e-9


def test_extract_keeps_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    tr.start(tmp_path)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.run_lanes"):
            f(x).block_until_ready()
    tr.stop()
    events = tr.extract(tr.find_xplane(tmp_path))
    names = [s[0] for s in events["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.run_lanes") == 1
    w = next(s for s in events["host"] if s[0] == "bench.window")
    r = next(s for s in events["host"] if s[0] == "bench.run_lanes")
    assert w[1] <= r[1] <= r[2] <= w[2]


def test_op_and_module_names():
    assert tr.op_name("%fusion.6 = (u32[1]) fusion(u32[2] %key.1)") == \
        "fusion.6"
    assert tr.module_name("jit__run_lanes_impl(15917661236964653833)") \
        == "jit__run_lanes_impl"


# -------------------------------------------------------------- metrics
def _run(requests, **kw):
    base = dict(requests=requests, setup_s=1.0, trace=None, peaks=None,
                server_stats={}, events={}, chips=1)
    base.update(kw)
    run = SimpleNamespace(**base)
    run.answered = [r for r in requests if r.answered]
    if run.answered:
        run.t0 = min(r.due for r in requests)
        run.t_end = max(r.done for r in run.answered)
    return run


def _req(i, due, done, steps=16, answered=True):
    r = Request(index=i, counts=np.zeros((steps, 4), np.int32), due=due,
                done=done)
    r.result = SimpleNamespace(queue_wait_ms=2.0 * i) if answered \
        else None
    return r


def test_rate_is_all_steps_over_the_whole_window():
    reqs = [_req(0, 0.0, 1.0), _req(1, 0.0, 1.0), _req(2, 1.0, 4.0)]
    assert reader("steps_per_s")(_run(reqs)) == pytest.approx(48 / 4.0)


def test_latency_is_timed_from_due_and_the_tail_is_over_all():
    reqs = [_req(i, float(i), float(i) + 0.001 * (i + 1))
            for i in range(100)]
    lat = np.arange(1, 101, dtype=float)
    assert reader("window_p50_ms")(_run(reqs)) == \
        pytest.approx(np.percentile(lat, 50))
    assert reader("window_p95_ms.serve")(_run(reqs)) == \
        pytest.approx(np.percentile(lat, 95))
    # a window never answered is infinitely late: no finite tail
    reqs[-1].result = None
    reqs += [_req(100 + i, 0.0, 0.0, answered=False) for i in range(5)]
    assert reader("window_p95_ms.serve")(_run(reqs)) is None


def test_queue_and_batch_readers():
    reqs = [_req(i, 0.0, 1.0) for i in range(4)]
    run = _run(reqs, server_stats={"batches": 2, "mean_batch_size": 2.0},
               plan=SimpleNamespace(max_batch=8))
    assert reader("queue_wait_ms.serve")(run) == pytest.approx(3.0)
    assert reader("batch_fill.serve")(run) == pytest.approx(25.0)


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_is_necessary_time_over_step_time(chips):
    peaks = {"hbm_bytes_per_s": 1e9, "int8_ops_per_s": 1e12}
    reqs = [_req(0, 0.0, 1.0, steps=10)]
    # busy time is averaged over the chips, the work spread over them
    trace = SimpleNamespace(busy_s=lambda module=None: 2.0)
    run = _run(reqs, trace=trace, peaks=peaks, events={0: 1000},
               net=SimpleNamespace(n_neurons=100), chips=chips)
    # 10 lane-steps x 100 neurons x 8 B + 1000 events x 6 B = 14,000 B
    assert reader("mfu_step.sim")(run) == pytest.approx(
        100 * 14_000 / 1e9 / 2.0 / chips)
    assert reader("mfu_step.sim")(_run(reqs)) is None


def test_device_idle_needs_a_device():
    t = tr.Trace(_events())
    assert reader("device_idle.sim")(_run([], trace=t)) == \
        pytest.approx(75.0)
    no_device = tr.Trace({"devices": {}, "host": _events()["host"]})
    assert reader("device_idle.sim")(_run([], trace=no_device)) is None


# -------------------------------------------------------------- traffic
def _inputs():
    layout = Layout(REPO)
    return layout, {k: layout.input_maker(k).make
                    for k in ("bernoulli", "digits")}


def test_open_loop_traffic_repeats_by_seed_at_fixed_arrivals():
    layout, mk = _inputs()
    params = layout.traffic("mlp_poisson")
    a = make_plan(params, mk["digits"], 784, 2 ** 31 + 5, 30.0)
    b = make_plan(params, mk["digits"], 784, 2 ** 31 + 5, 30.0)
    c = make_plan(params, mk["digits"], 784, 77, 30.0)
    assert len(a.requests) == round(params["rate_per_s"] * 30.0)
    for x, y in zip(a.requests, b.requests):
        assert x.due == y.due and x.seed == y.seed
        np.testing.assert_array_equal(x.counts, y.counts)
    # every seed offers the same arrivals; what the windows carry differs
    assert [r.due for r in a.requests] == [r.due for r in c.requests]
    assert not np.array_equal(a.requests[0].counts, c.requests[0].counts)
    assert a.requests[0].due == 0.0 and a.requests[-1].due < 30.0
    gaps = np.diff([r.due for r in a.requests])
    assert np.all(gaps > 0)
    assert np.mean(gaps) == pytest.approx(1 / params["rate_per_s"],
                                          rel=0.05)


def test_closed_loop_windows_repeat_by_seed():
    layout, mk = _inputs()
    params = layout.traffic("core_sessions")
    a = make_plan(params, mk["bernoulli"], 1024, 3, 30.0)
    b = make_plan(params, mk["bernoulli"], 1024, 3, 30.0)
    np.testing.assert_array_equal(a.session_window(2, 5),
                                  b.session_window(2, 5))
    assert not np.array_equal(a.session_window(2, 5),
                              a.session_window(3, 5))
    w = a.session_window(0, 0)
    assert w.shape == (16, 1024) and 0.03 < w.mean() < 0.07


def test_buckets_follow_the_servers_padding():
    assert buckets_for({"loop": "closed", "sessions": 8,
                        "max_batch": 8}) == [8]
    assert buckets_for({"loop": "closed", "sessions": 11,
                        "max_batch": 8}) == [4, 8]
    assert buckets_for({"loop": "open", "max_batch": 8}) == [1, 2, 4, 8]


# ------------------------------------------------------- device checks
def test_peaks_table_refuses_an_unknown_device():
    layout = Layout(REPO)
    assert lookup_peaks(layout, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        lookup_peaks(layout, "TPU v9")


def test_device_check_refuses_the_cpu():
    with pytest.raises(NoChip):
        device_check(1)


def test_run_exits_without_a_result_when_there_is_no_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "core125k.sim", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_reference_side_imports_nothing_of_the_program():
    for name in ("reference", "check", "parallel", "traffic", "trace",
                 "network", "work", "stats", "layout", "loadgen"):
        text = (BENCH / "benchkit" / f"{name}.py").read_text()
        assert "repro" not in text, name
    for path in list((BENCH / "networks").glob("*.py")) \
            + list((BENCH / "traffic").rglob("*.py")):
        assert "import repro" not in path.read_text(), path


def test_split_metric_shares_its_quantity_reader(tmp_path):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for name, body in (("idle", "def read(run):\n    return 1\n"),
                       ("idle.sim", "def read(run):\n    return 2\n")):
        (tmp_path / "bench" / "metrics" / f"{name}.py").write_text(body)
    layout = Layout(tmp_path)
    assert layout.metric_reader("idle.serve").read(None) == 1
    assert layout.metric_reader("idle.sim").read(None) == 2
    with pytest.raises(FileNotFoundError):
        layout.metric_reader("busy.sim")


def test_every_metric_has_a_reader():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(reader(m["name"])), m["name"]
    assert math.isfinite(len(spec["workloads"]))


@pytest.mark.parametrize("bound", [46, 60])
def test_random_lif_shapes_do_not_depend_on_the_seed(bound):
    """Every seed gives each source the same rows (targets spread over
    the 16 row slots) and the largest in-degree exactly `bound`, also
    where the seed draws neurons above it (46 is near the mean, 42)."""
    builder = Layout(REPO).network_builder("random_lif")
    cfg = {"cores": 1, "neurons_per_core": 600, "axons": 32,
           "synapses_per_source": 40, "max_in_degree": bound,
           "neuron": {"model": "LIF", "threshold": 60000, "nu": 0,
                      "lam": 4}}
    posts = []
    for seed in (1, 2 ** 31 + 5):
        net = builder.build(cfg, seed)
        deg = np.bincount(net.post, minlength=net.n_neurons)
        assert deg.max() == bound and (deg == bound).sum() >= 1
        slots = np.bincount(net.src * 16 + net.post % 16,
                            minlength=net.n_sources * 16)
        assert set(np.unique(slots.reshape(-1, 16).max(axis=1))) == {3}
        assert np.all(np.bincount(net.src) == 40)
        posts.append(net.post)
    assert not np.array_equal(*posts)
