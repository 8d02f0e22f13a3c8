"""Bytes and operations the grouped products of the dropless expert layer
(``ops/moe.py``: the kernels under the scope ``moe_gmm``) must move and do in
ONE decode step, from the configuration's shapes and from what the step
itself reported: the roofline of ``moe_gmm_roofline_pct``.

An expert is three matrices of ``hidden_size x moe_intermediate_size``
(gate, up, down).  A step reads the weights of the experts that RECEIVED a
token, once each: ``experts_hit`` is the program's own count, summed over the
expert layers (``ServingMetrics``: ``moe_experts_hit``), never the number of
experts a layer has.  Beside the weights it moves the rows of the
token-expert pairs: into the gate/up product ``hidden_size`` values a pair,
out of it ``2 x moe_intermediate_size``, into the down product
``moe_intermediate_size``, out of it ``hidden_size``.  A pair costs one
multiply-add (2 operations) per weight of its expert.

Only this is counted.  What a kernel does beyond it lowers the share and is
the kernel's to save: a tile's rows that belong to no pair, an expert's
weights read again where its rows straddle two row tiles, the sort and the
gather in front (they run outside ``moe_gmm``).  The share can therefore not
pass 100%.  At 32 rows a step the bound is memory: 17.3 MB of weights an
expert hit against 3 rows of work.
"""


def _shape(config: dict):
    layers = int(config["num_hidden_layers"]) - int(config["first_k_dense_replace"])
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return (int(config["hidden_size"]), int(config["moe_intermediate_size"]),
            layers, itemsize)


def expert_layers(config: dict) -> int:
    return _shape(config)[2]


def bytes_per_step(config: dict, experts_hit: float, pairs_per_layer: float) -> float:
    """``experts_hit``: experts that got a token, summed over the expert
    layers; ``pairs_per_layer``: live rows x experts a token."""
    dim, width, layers, itemsize = _shape(config)
    weights = experts_hit * 3 * dim * width
    rows = pairs_per_layer * layers * (dim + 2 * width + width + dim)
    return float((weights + rows) * itemsize)


def flops_per_step(config: dict, pairs_per_layer: float) -> float:
    dim, width, layers, _ = _shape(config)
    return float(pairs_per_layer * layers * 3 * dim * width * 2)
