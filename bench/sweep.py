"""Find the knee of an open-loop cell: offer its traffic at several
fixed rates in one process and report, for each, the latency tail and
the backlog left when the offered window closed.

    python3 bench/sweep.py --workload mlp.serve --seed 5 --seconds 20 \
        --rates 6 8 10 12 14

The knee is the highest rate whose backlog stays small and whose tail
stays near the low-load tail; the cell's traffic file then offers about
four fifths of it. Prints one JSON line per rate."""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from benchkit import loadgen, program
    from benchkit.harness import device_check, derive_seeds
    from benchkit.stats import latency_percentile
    from benchkit.layout import Layout
    from benchkit.traffic import make_plan

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    chips = int(cell["chips"])
    cfg = layout.config(cell["config"])
    program.deploy_target(cfg, chips)
    device_check(chips)
    params = layout.traffic(cell["traffic"])
    if params["loop"] != "open":
        raise SystemExit("the sweep is for open-loop traffic")
    net_seed, dep_seed, traffic_seed = derive_seeds(args.seed)
    net = layout.network_builder(cfg["kind"]).build(cfg, net_seed)
    make_input = layout.input_maker(params["input"]["kind"]).make
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    compiled = program.compile_network(net, cfg, log)
    plan = make_plan(params, make_input, net.n_axons, traffic_seed, 1.0)
    prog = program.Served(compiled, chips=chips, seed=dep_seed,
                          window=plan.window, lanes=1,
                          max_batch=plan.max_batch,
                          max_wait_ms=plan.max_wait_ms,
                          buckets=plan.buckets, log=log)
    for rate in args.rates:
        plan = make_plan(dict(params, rate_per_s=rate), make_input,
                         net.n_axons, traffic_seed, args.seconds)
        prog.server.reset_stats()
        t0 = time.perf_counter()
        reqs = loadgen.open_loop(prog, plan, args.seconds)
        close = t0 + args.seconds
        ok = [r for r in reqs if r.answered]
        backlog = sum(1 for r in reqs if not r.answered
                      or r.done > close)
        stats = prog.server.stats()
        print(json.dumps({
            "rate_per_s": rate, "sent": len(reqs), "answered": len(ok),
            "p50_ms": latency_percentile(reqs, 50),
            "p95_ms": latency_percentile(reqs, 95),
            "backlog_at_close": backlog,
            "answered_per_s": len(ok) / (max(r.done for r in ok) - t0),
            "mean_batch": stats["mean_batch_size"]}), flush=True)
    prog.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
