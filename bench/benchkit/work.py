"""The work a lane step cannot do without, counted from a run's own
served spikes and the network's shapes, whatever the program does to
get there. Per lane-step (one timestep of one lane):

  bytes  each membrane read once and written once (int32, 8 B a
         neuron); per delivered synaptic event its target id (int32)
         and its weight (int16): 6 B;
  ops    per neuron the fire / leak / integrate arithmetic: noise add,
         threshold compare, reset select, leak shift, leak subtract,
         input add: 6; per event one add.

A program that skips silent synapses or packs weights does less than
it does today, never less than this, so a share of the roofline taken
against it stays at or under 100%."""
from __future__ import annotations

from typing import Tuple

MEMBRANE_BYTES = 8
EVENT_BYTES = 6
NEURON_OPS = 6
EVENT_OPS = 1


def necessary(n_neurons: int, lane_steps: int, events: int
              ) -> Tuple[float, float]:
    """(bytes, ops) for `lane_steps` lane-steps delivering `events`."""
    return (float(lane_steps) * n_neurons * MEMBRANE_BYTES
            + float(events) * EVENT_BYTES,
            float(lane_steps) * n_neurons * NEURON_OPS
            + float(events) * EVENT_OPS)


def least_time(n_bytes: float, n_ops: float, peaks: dict, chips: int
               ) -> Tuple[float, str]:
    """Seconds `chips` chips of one chip's `peaks` need at least, and
    which bound sets it: the work spread evenly over them, so against
    a device time averaged over the chips (`Trace.busy_s`)."""
    t_mem = n_bytes / (chips * float(peaks["hbm_bytes_per_s"]))
    t_ops = n_ops / (chips * float(peaks["int8_ops_per_s"]))
    return (t_mem, "bandwidth") if t_mem >= t_ops else (t_ops, "compute")
