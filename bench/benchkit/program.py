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


def compile_network(net: Network, log):
    """`compile_spec(target="engine")` of the network, as a user
    compiles it."""
    from repro.core.compile import compile_spec

    t0 = time.perf_counter()
    compiled = compile_spec(to_spec(net), target="engine")
    log(f"compile_spec: {net.n_neurons} neurons, {net.n_synapses} "
        f"synapses, {time.perf_counter() - t0:.2f} s")
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

    def __init__(self, compiled, *, seed: int, window: int, lanes: int,
                 max_batch: int, max_wait_ms: float, buckets, log):
        from repro.serve import SpikeServer

        self.server = SpikeServer(max_batch=max_batch,
                                  max_wait_ms=max_wait_ms)
        t0 = time.perf_counter()
        self.model = self.server.add_model(MODEL, compiled, window=window,
                                           n_sessions=lanes, seed=seed)
        log(f"deploy: {time.perf_counter() - t0:.2f} s")
        dep = self.model.dep
        _annotate(dep, "bench.run_lanes")
        _annotate(dep.impl, "bench.engine.run_lanes")
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
