"""The system under test, driven through the entry points a user calls:
`NetworkSpec` -> `compile_spec` -> `SpikeServer.add_model` -> `submit`.
This is the only harness module that imports `repro`."""
from __future__ import annotations

import gc
import time
from typing import List

import jax
import numpy as np

from benchkit.network import Network

MODEL = "net"


def to_spec(net: Network):
    from repro.core.neuron import ANN_neuron, LIF_neuron
    from repro.core.spec import NetworkSpec

    spec = NetworkSpec()
    axons = spec.add_axons(net.n_axons)
    for g in net.groups:
        model = LIF_neuron(g.threshold, g.nu, g.lam) if g.model == "LIF" \
            else ANN_neuron(g.threshold, g.nu)
        spec.add_neurons(g.count, model)
    pre = np.where(net.src < net.n_axons, axons[np.minimum(
        net.src, max(net.n_axons - 1, 0))], net.src - net.n_axons)
    spec.connect(pre, net.post, net.weight)
    spec.set_outputs(net.outputs)
    return spec


TARGETS = ("engine", "hiaer", "mesh")
# the Hierarchy a hiaer or mesh configuration states, in its own keys
HIERARCHY_KEYS = ("servers", "fpgas_per_server", "cores_per_fpga",
                  "neurons_per_core")


class Refused(ValueError):
    """A cell whose configuration cannot be deployed over its chips."""


def deploy_target(cfg: dict, chips: int) -> str:
    """The target `cfg` names, refused where the cell's `chips` cannot
    hold it: a mesh spreads whole cores evenly over the chips; the
    engine and hiaer run on one."""
    target = cfg.get("target")
    if target not in TARGETS:
        raise Refused(f"configuration {cfg.get('name')!r} names target "
                      f"{target!r}; the benchmark deploys one of "
                      f"{TARGETS}")
    missing = [k for k in HIERARCHY_KEYS if k not in cfg]
    if target != "engine" and missing:
        raise Refused(f"a {target} configuration states its hierarchy; "
                      f"{cfg.get('name')!r} lacks {missing}")
    if target != "mesh":
        if chips != 1:
            raise Refused(f"a {target} configuration runs on one chip; "
                          f"the cell asks for {chips}")
        return target
    cores = int(cfg["servers"]) * int(cfg["fpgas_per_server"]) \
        * int(cfg["cores_per_fpga"])
    if chips < 1 or cores % chips:
        raise Refused(f"a mesh of {cores} cores cannot be spread evenly "
                      f"over {chips} chips")
    return target


def compile_network(net: Network, cfg: dict, log):
    """`compile_spec` of the network for the target `cfg` names, as a
    user compiles it. hiaer and mesh get the configuration's hierarchy
    and contiguous blocks of `neurons_per_core` as the placement: a
    random network has no locality for the partitioner to find, and at
    full size the partitioner is most of the host build."""
    from repro.core.compile import compile_spec
    from repro.core.partition import Hierarchy

    target = cfg["target"]
    t0 = time.perf_counter()
    spec = to_spec(net)
    kw = {}
    if target != "engine":
        per_core = int(cfg["neurons_per_core"])
        kw["hierarchy"] = Hierarchy(*(int(cfg[k]) for k in HIERARCHY_KEYS))
        kw["placement"] = dict(enumerate(
            (np.arange(net.n_neurons) // per_core).tolist()))
    compiled = compile_spec(spec, target=target, **kw)
    log(f"compile_spec(target={target!r}): {net.n_neurons} neurons, "
        f"{net.n_synapses} synapses, {time.perf_counter() - t0:.2f} s")
    return compiled


def _annotate(obj, name: str):
    """Wrap `obj.run_lanes` in a profiler annotation named `name` (host
    spans on the trace's clock), without editing the program."""
    inner = obj.run_lanes

    def run_lanes(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            return inner(*args, **kw)

    obj.run_lanes = run_lanes


class Served:
    """One network resident in a `SpikeServer`, warmed for the batch
    shapes its traffic dispatches."""

    def __init__(self, compiled, *, chips: int, seed: int, window: int,
                 lanes: int, max_batch: int, max_wait_ms: float, buckets,
                 log):
        from repro.serve import SpikeServer

        self.server = SpikeServer(max_batch=max_batch,
                                  max_wait_ms=max_wait_ms)
        target = compiled.target
        kw = {"n_devices": chips} if target == "mesh" else {}
        t0 = time.perf_counter()
        self.model = self.server.add_model(MODEL, compiled, window=window,
                                           n_sessions=lanes, seed=seed,
                                           **kw)
        dep = self.model.dep
        log(f"deploy ({target}, n_devices "
            f"{getattr(dep.impl, 'n_devices', 1)}): "
            f"{time.perf_counter() - t0:.2f} s")
        _annotate(dep, "bench.run_lanes")
        _annotate(dep.impl, f"bench.{target}.run_lanes")
        t0 = time.perf_counter()
        for b in buckets:
            dep.run_lanes([-1] * b, np.zeros((b, window, compiled.n_axons),
                                             np.int32))
        log(f"warm-up of batch buckets {list(buckets)}: "
            f"{time.perf_counter() - t0:.2f} s")
        self.started = False

    def start(self) -> None:
        """Start the dispatcher, once."""
        if not self.started:
            self.server.start()
            self.started = True

    def open_sessions(self, n: int) -> List[int]:
        return [self.server.open_session(MODEL) for _ in range(n)]

    def lane_of(self, session: int) -> int:
        return self.model.sessions.get(session).lane

    def submit(self, counts, *, session=None, seed: int = 0):
        return self.server.submit(MODEL, counts, session=session, seed=seed)

    def close(self) -> None:
        """Stop the server and let go of the deployment's device
        arrays."""
        self.server.shutdown()
        self.server.models.clear()
        self.server = self.model = None
        gc.collect()
