"""Run one cell's window several times in one process, with the
program's telemetry on or off, and lay the program's own spans over a
profiled window.

    python3 bench/spans.py --workload mlp.serve --seed 5 --seconds 51 \
        --arms on off trace --out bench/.cache/spans

Builds and warms the cell once, as `bench/run.py` does, then offers the
cell's traffic once per arm, from the same seed each time (a closed
loop's sessions are closed, so their lanes reset, between arms):

  on     telemetry on (the default of a `SpikeServer`);
  off    telemetry off (`Telemetry.on = False`): no spans, no metrics;
  trace  telemetry on under the profiler. The program's spans are put
         on the trace's clock by the `run_lanes` anchors and the report
         gives the anchor offsets' spread, the step program's device
         time by phase and the share a phase names, the longest idle
         gaps named by the innermost dispatcher span (`serve.*`,
         `run_lanes*`) open at their midpoint, and all idle time split
         by the innermost dispatcher span open. `--out` gets the
         extracted trace and the spans.

Prints one JSON line per arm: the cell's end-to-end metrics and the mean
ms per dispatch of each dispatcher span. The answers are not checked
against the reference here; `bench/run.py` does that."""
import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

TRACE_CAPACITY = 1 << 17          # spans: no window of a cell drops any
DISPATCHER = ("serve.", "run_lanes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 bench/spans.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--arms", nargs="+", default=["on", "off"],
                    choices=("on", "off", "trace"))
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    import repro.serve.server as server_mod
    from benchkit import loadgen, program
    from benchkit import program_trace as pt
    from benchkit import trace as tr
    from benchkit.harness import CACHE, Run, derive_seeds, device_check
    from benchkit.layout import Layout
    from benchkit.traffic import make_plan
    from repro.obs import Telemetry

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    chips = int(cell["chips"])
    cfg = layout.config(cell["config"])
    program.deploy_target(cfg, chips)
    device_check(chips)
    params = layout.traffic(cell["traffic"])
    net_seed, dep_seed, traffic_seed = derive_seeds(args.seed)
    net = layout.network_builder(cfg["kind"]).build(cfg, net_seed)
    make_input = layout.input_maker(params["input"]["kind"]).make
    plan = make_plan(params, make_input, net.n_axons, traffic_seed,
                     args.seconds)
    # a ring that holds every span of a window, for the trace arm
    server_mod.Telemetry = functools.partial(
        Telemetry, trace_capacity=TRACE_CAPACITY)
    prog = program.Served(program.compile_network(net, cfg, log),
                          chips=chips, seed=dep_seed, window=plan.window,
                          lanes=plan.sessions if plan.loop == "closed"
                          else 1, max_batch=plan.max_batch,
                          max_wait_ms=plan.max_wait_ms,
                          buckets=plan.buckets, log=log)
    setup_s = time.perf_counter() - t_start
    tracer = prog.server.tel.tracer
    trace_dir = ROOT / CACHE / "trace" / "spans"
    for arm in args.arms:
        plan = make_plan(params, make_input, net.n_axons, traffic_seed,
                         args.seconds)
        prog.server.tel.on = arm != "off"
        prog.server.reset_stats()
        tracer.clear()
        if arm == "trace":
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            tr.start(trace_dir)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            requests = loadgen.LOOPS[plan.loop](prog, plan, args.seconds)
        if arm == "trace":
            tr.stop()
        run = Run(net=net, plan=plan, requests=requests, setup_s=setup_s,
                  server_stats=prog.server.stats())
        run.t0 = min((r.due for r in requests), default=0.0)
        run.t_end = max((r.done for r in requests if r.answered),
                        default=0.0)
        out = {"arm": arm, "seed": args.seed,
               "answered": len(run.answered), "attempted": len(requests),
               "metrics": {m["name"]: layout.metric_reader(m["name"])
                           .read(run)
                           for m in layout.metrics(args.workload, False)},
               "stages_ms": {
                   name: v["total_ms"] / v["dispatches"]
                   for name, v in run.server_stats["stages"].items()},
               "dispatches": run.server_stats["batches"]}
        if arm == "trace":
            out.update(lay_over(pt, tr, trace_dir, tracer, args.out))
        print(json.dumps(out), flush=True)
        for sid in [s.id for s in prog.model.sessions.all()]:
            prog.server.close_session(program.MODEL, sid)
    prog.close()
    return 0


def lay_over(pt, tr, trace_dir: Path, tracer, out_dir) -> dict:
    """The program's spans on the profiled window's clock."""
    events = pt.extract(tr.find_xplane(trace_dir))
    spans = [s.to_dict() for s in tracer.spans()]
    ph = pt.Phases(events)
    offs = ph.offsets()
    # the dispatcher's own tree nests; per-request spans overlap it
    host = [(tr.WINDOW_SPAN, ph.w0, ph.w1)] + ph.program_spans(
        s for s in spans if s["name"].startswith(DISPATCHER))
    by = ph.phase_s(pt.STEP_PROGRAM)
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "events.json").write_text(json.dumps(events))
        (out_dir / "spans.json").write_text(json.dumps(spans))
    return {
        "spans": len(spans), "spans_dropped": tracer.dropped,
        "anchors": len(offs),
        "anchor_spread_us": (max(offs) - min(offs)) / 1e3 if offs
        else None,
        "phase_s": {str(k): v for k, v in by.items()},
        "named_share": ph.named_share(),
        "idle_gaps": ph.idle_gaps(host),
        "idle_s_by_span": ph.idle_by_span(host)}


if __name__ == "__main__":
    raise SystemExit(main())
