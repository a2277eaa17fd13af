"""The lower-precision control of a cell, at the cell's own size.

    python3 bench/control.py --workload core125k.sim --seeds 11 12 13 \
        --seconds 30 --rounds 8

For each seed it makes the network and the requests a run of that seed
sends (open loop: every window due in `--seconds`; closed loop:
`--rounds` windows of every session), answers them with the plain
reference computed with int8 weights (the top 8 bits of each int16
weight) in place of the program, and prints the numbers `correct`
compares, as the run's check would read them. A sound control reads
above every limit of `benchkit.check`. The benchmark's own runs never
run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchkit import check  # noqa: E402
from benchkit.harness import derive_seeds  # noqa: E402
from benchkit.layout import Layout  # noqa: E402
from benchkit.reference import Reference  # noqa: E402
from benchkit.traffic import Request, make_plan  # noqa: E402

WEIGHT_BITS = 8     # int8: the precision below the configurations' int16


def control_readings(root: Path, workload: str, seed: int, seconds: float,
                     rounds: int) -> dict:
    layout = Layout(root)
    cell = layout.cell(workload)
    cfg = layout.config(cell["config"])
    tparams = layout.traffic(cell["traffic"])
    net_seed, dep_seed, traffic_seed = derive_seeds(seed)
    net = layout.network_builder(cfg["kind"]).build(cfg, net_seed)
    make_input = layout.input_maker(tparams["input"]["kind"]).make
    plan = make_plan(tparams, make_input, net.n_axons, traffic_seed,
                     seconds)
    if plan.loop == "closed":
        reqs = [Request(index=i * plan.sessions + s,
                        counts=plan.session_window(s, i), session=s,
                        k=i, lane=s)
                for i in range(rounds) for s in range(plan.sessions)]
    else:
        reqs = plan.requests
    want = check.replay(Reference(net), reqs, dep_seed)
    got = check.replay(Reference(net, weight_bits=WEIGHT_BITS), reqs,
                       dep_seed)
    return check.count(reqs, want, lambda r: got[r.index])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rounds", type=int, default=8)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = control_readings(ROOT, args.workload, seed, args.seconds,
                               args.rounds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "weight_bits": WEIGHT_BITS, "readings": got,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
