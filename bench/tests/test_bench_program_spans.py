"""The program's own names for its time, read by the harness on the CPU:
op phases from scope paths, the `run_lanes` anchors that put the
program's spans on the trace's clock, idle gaps named by program spans,
the dispatcher's stage totals, and the readers that return nothing for
a program or a run without them."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench_testroot import BENCH, REPO
from benchkit import program_trace as pt
from benchkit import trace as tr
from benchkit.layout import Layout
from benchkit.traffic import Request

RECORDED = BENCH / "tests" / "data" / "program_trace.json"


def reader(name):
    return Layout(REPO).metric_reader(name).read


def test_phase_is_the_innermost_named_scope():
    base = "jit(_run_lanes_impl)/vmap()/while/body/closed_call"
    assert pt.phase_of(f"{base}/accumulate/gather") == "accumulate"
    assert pt.phase_of(f"{base}/noise/jit(_randint)/vmap()/add") == \
        "noise"
    assert pt.phase_of(f"{base}/gate/accumulate/x") == "accumulate"
    assert pt.phase_of(f"{base}/dynamic_update_slice") is None
    assert pt.phase_of("accumulated/fires") is None     # whole segments
    assert pt.phase_of(f"{base}/integrate:") == "integrate"
    assert pt.phase_of("") is None and pt.phase_of(None) is None


def _events():
    """A 100 ns window: two lane-step runs of two ops each, a third
    program's op, and two anchors whose offsets differ by 2 ns."""
    return {"window": [0, 100], "anchors": [[20, 1020], [62, 1060]],
            "devices": {"/device:TPU:0": {
                "ops": [["fusion.62", "accumulate", 22, 32],
                        ["fusion.3", "fire", 32, 36],
                        ["while.1", None, 21, 40],
                        ["copy.4", None, 44, 46],
                        ["fusion.62", "accumulate", 64, 74],
                        ["dynamic-update-slice.2", None, 74, 76]],
                "modules": [["jit__run_lanes_impl", 21, 40],
                            ["jit_other", 44, 46],
                            ["jit__run_lanes_impl", 63, 77]]}}}


def test_reductions_by_hand():
    ph = pt.Phases(_events())
    by = ph.phase_s(pt.STEP_PROGRAM)
    assert by == pytest.approx({"accumulate": 20e-9, "fire": 4e-9,
                                None: 2e-9})
    assert ph.phase_s()[None] == pytest.approx(4e-9)    # with copy.4
    assert ph.named_share() == pytest.approx(24 / 26)
    assert ph.named_ops() == {"fusion.62": "accumulate",
                              "fusion.3": "fire"}
    assert ph.offsets() == [-1000, -998] and ph.offset() == -999
    spans = [{"name": "run_lanes", "start": 1020, "end": 1058},
             {"name": "serve.resolve", "start": 1058, "end": 1100},
             {"name": "open", "start": 1000, "end": None}]
    placed = ph.program_spans(spans)
    assert placed == [("run_lanes", 21, 59), ("serve.resolve", 59, 101)]
    host = [(tr.WINDOW_SPAN, 0, 100)] + placed
    gaps = ph.idle_gaps(host)
    # busy 21-40 (the while op counts), 44-46, 64-76
    assert [g[1] for g in gaps] == pytest.approx(
        [24e-9, 21e-9, 18e-9, 4e-9])
    assert [g[0] for g in gaps] == ["serve.resolve", tr.WINDOW_SPAN,
                                    "run_lanes", "run_lanes"]
    # every idle instant to the innermost span open then: the gap 46-64
    # splits at 59 between run_lanes and serve.resolve
    assert ph.idle_by_span(host) == pytest.approx(
        {tr.WINDOW_SPAN: 21e-9, "run_lanes": 17e-9,
         "serve.resolve": 29e-9})


def test_ops_xla_puts_between_two_of_one_phase_take_it():
    ev = {"window": [0, 100], "anchors": [], "devices": {"/device:TPU:0": {
        "ops": [["fusion.62", "accumulate", 10, 20],
                ["while.9", None, 20, 30],
                ["dynamic-update-slice.2", None, 21, 25],
                ["reshape.2", None, 26, 30],
                ["multiply_reduce_fusion.8", "accumulate", 30, 40],
                ["copy.1", None, 40, 42],
                ["fusion.63", "integrate", 42, 44],
                ["copy.2", None, 50, 52],
                ["fusion.62", "accumulate", 60, 70]],
        "modules": [["jit__run_lanes_impl", 10, 45],
                    ["jit__run_lanes_impl", 50, 75]]}}}
    ph = pt.Phases(ev)
    # copy.1 lies between two phases, copy.2 between two program runs
    assert ph.phase_s(own=True) == pytest.approx(
        {"accumulate": 30e-9, "integrate": 2e-9, None: 12e-9})
    assert ph.phase_s() == pytest.approx(
        {"accumulate": 38e-9, "integrate": 2e-9, None: 4e-9})
    assert ph.named_share(own=True) == pytest.approx(32 / 44)
    assert ph.named_share() == pytest.approx(40 / 44)
    # one name, two programs, two phases
    ev["devices"]["/device:TPU:0"]["ops"][-1][0] = "fusion.63"
    assert pt.Phases(ev).named_ops()["fusion.63"] == "accumulate|integrate"


def test_a_program_without_names_reads_nothing():
    ev = _events()
    for op in ev["devices"]["/device:TPU:0"]["ops"]:
        op[1] = None
    ev["anchors"] = []
    ph = pt.Phases(ev)
    assert ph.named_share() is None and ph.offset() is None
    assert ph.program_spans([{"name": "x", "start": 0, "end": 1}]) == []
    with pytest.raises(ValueError):
        pt.Phases(dict(ev, window=None))


def _answered(bucket, batch, steps=16):
    r = Request(index=0, counts=np.zeros((steps, 4), np.int32), due=0.0,
                done=1.0)
    r.result = SimpleNamespace(bucket=bucket, batch_size=batch)
    return r


def test_lane_steps_count_the_bucket_of_each_dispatch():
    # a dispatch of 3 requests padded to 4 lanes and one of 8 in 8
    reqs = [_answered(4, 3) for _ in range(3)] + \
        [_answered(8, 8) for _ in range(8)]
    reqs.append(Request(index=1, counts=np.zeros((16, 4), np.int32)))
    assert pt.lane_steps(reqs) == 4 * 16 + 8 * 16


STAGES = {"serve.assemble": {"dispatches": 4, "total_ms": 2.0},
          "serve.resolve": {"dispatches": 4, "total_ms": 6.0},
          "run_lanes.encode": {"dispatches": 4, "total_ms": 1.0},
          "run_lanes.keys": {"dispatches": 4, "total_ms": 4.0},
          "run_lanes.upload": {"dispatches": 4, "total_ms": 3.0},
          "run_lanes.download": {"dispatches": 4, "total_ms": 8.0}}


@pytest.mark.parametrize("name,want", [
    ("lane_host_ms.sim", (1 + 4 + 3 + 8) / 4),
    ("lane_host_ms.serve", (1 + 4 + 3 + 8) / 4),
    ("serve_host_ms.serve", (2 + 6) / 4)])
def test_stage_readers(name, want):
    run = SimpleNamespace(server_stats={"stages": STAGES})
    assert reader(name)(run) == pytest.approx(want)
    # a program that keeps no stage totals, and one that missed a stage
    # of some dispatch, read nothing
    assert reader(name)(SimpleNamespace(server_stats={})) is None
    short = {k: dict(v) for k, v in STAGES.items()}
    for v in short.values():
        v["dispatches"] = 3
    short["serve.assemble"]["dispatches"] = 4
    short["run_lanes.encode"]["dispatches"] = 4
    assert reader(name)(SimpleNamespace(
        server_stats={"stages": short})) is None


@pytest.mark.parametrize("name", ["accumulate_us.sim",
                                  "accumulate_us.serve"])
def test_accumulate_reader_needs_a_trace_with_phases(name, monkeypatch):
    read = reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    mod = Layout(REPO).metric_reader(name)
    window = SimpleNamespace(w0=0, w1=100, top_ops=lambda: [])
    reqs = [_answered(2, 2, steps=5) for _ in range(2)]
    run = SimpleNamespace(trace=window, requests=reqs)
    monkeypatch.setattr(mod.pt, "find", lambda root, w: _events())
    # 20 ns of accumulate over one dispatch of 2 lanes x 5 steps
    assert read(run) == pytest.approx(1e6 * 20e-9 / 10)
    monkeypatch.setattr(mod.pt, "find", lambda root, w: None)
    assert read(run) is None
    unnamed = _events()
    for op in unnamed["devices"]["/device:TPU:0"]["ops"]:
        op[1] = None
    monkeypatch.setattr(mod.pt, "find", lambda root, w: unnamed)
    assert read(run) is None


def test_find_picks_the_run_whose_window_matches(tmp_path):
    import jax

    from repro.core.compile import compile_spec
    from repro.core.deploy import deploy
    from repro.core.neuron import LIF_neuron
    from repro.core.spec import NetworkSpec
    from repro.obs import Tracer

    spec = NetworkSpec()
    ax = spec.add_axons(3)
    spec.add_neurons(5, LIF_neuron(threshold=2))
    spec.connect(np.repeat(ax, 2), [0, 1, 2, 3, 4, 0], [3] * 6)
    dep = deploy(compile_spec(spec, target="engine"), seed=0)
    dep.tracer = Tracer()
    w = np.ones((2, 4, 3), np.int32)
    dep.run_lanes([-1, -1], w)
    dep.tracer.clear()
    windows = {}
    for cell in ("a", "b"):
        tr.start(tmp_path / cell)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            dep.run_lanes([-1, -1], w)
        tr.stop()
        ev = tr.extract(tr.find_xplane(tmp_path / cell))
        windows[cell] = next(s[1:] for s in ev["host"]
                             if s[0] == tr.WINDOW_SPAN)
    got = pt.find(tmp_path, windows["a"])
    assert got["window"] == list(windows["a"])
    assert pt.find(tmp_path, [0, 1]) is None
    (anchor,) = got["anchors"]
    starts = [s.start for s in dep.tracer.spans() if s.name == "run_lanes"]
    assert anchor[1] == starts[0]
    assert got["devices"] == {}              # the CPU has no device plane


# ------------------------------------------------- a recorded chip trace
def _recorded():
    fx = json.loads(RECORDED.read_text())
    return pt.Phases(fx["events"]), fx["spans"]


def test_recorded_anchors_put_the_spans_on_the_trace_clock():
    ph, spans = _recorded()
    offs = ph.offsets()
    assert len(offs) >= 2 and max(offs) - min(offs) <= 50_000     # ns
    placed = ph.program_spans(s for s in spans if s["name"] == "run_lanes")
    starts = sorted(a for a, _ in ph.anchors)
    assert len(placed) == len(starts)
    for (_, a, _), anchor in zip(sorted(placed, key=lambda s: s[1]),
                                 starts):
        assert abs(a - anchor) <= max(offs) - min(offs)
    # each lane step ran on the device inside its run_lanes span (the
    # profiler's own device-to-host clock sync, not the anchors, leaves
    # device events up to about a millisecond early, so not the step's)
    runs = [(a, b) for v in ph.devices.values()
            for m, a, b in v["modules"] if m == pt.STEP_PROGRAM]
    assert len(runs) == len(placed)
    for a, b in runs:
        assert any(x <= a and b <= y for _, x, y in placed)


def test_recorded_lane_step_time_is_put_down_to_its_phases():
    ph, _ = _recorded()
    by = ph.phase_s(pt.STEP_PROGRAM)
    assert ph.named_share() >= 0.95
    named = {p: s for p, s in by.items() if p is not None}
    assert max(named, key=named.get) == "accumulate"
    assert set(named) <= set(pt.PHASES)


def test_recorded_idle_time_is_owned_by_program_spans():
    ph, spans = _recorded()
    host = [(tr.WINDOW_SPAN, ph.w0, ph.w1)] + ph.program_spans(
        s for s in spans if s["name"].startswith(("serve.", "run_lanes")))
    gaps = ph.idle_gaps(host)
    assert gaps and all(name != tr.WINDOW_SPAN for name, _ in gaps)
    owners = ph.idle_by_span(host)
    assert sum(owners.values()) == pytest.approx(
        sum(b - a for a, b in ph._gaps()) * 1e-9)
    assert owners.get(tr.WINDOW_SPAN, 0.0) < 0.05 * sum(owners.values())
