"""Fully connected feed-forward network (kind "layered"), the paper's
Table 2 MLPs: `inputs` axons, then one neuron layer per entry of
`layers`, each connected to every neuron of the layer before. Weights
are int16, uniform over the whole range, drawn from the seed; zero
weights are kept as records, so the synapse count is the product of the
published widths."""
from __future__ import annotations

import numpy as np

from benchkit.network import W_MAX, W_MIN, Network, NeuronGroup


def build(cfg: dict, seed: int) -> Network:
    widths = [int(cfg["inputs"])] + [int(w) for w in cfg["layers"]]
    a = widths[0]
    rng = np.random.default_rng(seed)
    srcs, posts = [], []
    first_src = 0               # source index of the layer before
    first_nid = 0               # neuron id of this layer's first neuron
    layers = []
    for i, (w_in, w_out) in enumerate(zip(widths[:-1], widths[1:])):
        srcs.append(np.repeat(np.arange(first_src, first_src + w_in),
                              w_out))
        posts.append(np.tile(np.arange(first_nid, first_nid + w_out),
                             w_in))
        layers.append((f"layer{i + 1}", first_nid, first_nid + w_out))
        first_src = a + first_nid
        first_nid += w_out
    src = np.concatenate(srcs).astype(np.int64)
    post = np.concatenate(posts).astype(np.int64)
    weight = rng.integers(W_MIN, W_MAX + 1, src.size, dtype=np.int16)
    n = first_nid
    hidden = n - widths[-1]
    return Network(
        n_axons=a, n_neurons=n, src=src, post=post, weight=weight,
        groups=[NeuronGroup.from_config(hidden, cfg["hidden_neuron"]),
                NeuronGroup.from_config(widths[-1], cfg["output_neuron"])],
        outputs=np.arange(hidden, n), layers=layers)
