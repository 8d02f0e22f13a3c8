"""Bytes and operations the grouped products of a LATENT expert layer
(``ops/moe.py::DroplessMoE`` with ``latent``: the kernels under the scope
``moe_gmm``) must move and do in ONE decode step, from the configuration's
shapes and from what the step itself reported: the roofline of
``latent_moe_gmm_roofline_pct``.

An expert is TWO matrices of ``moe_latent_size x moe_intermediate_size`` (up
and down; ``relu2`` is not gated, and the expert lives in the latent, not at
``hidden_size``): a sixth of what ``kernels/moe_gmm.py`` counts for a gated
expert of the model's width, which is why that reader is not this cell's.  A
step reads the weights of the held experts that RECEIVED a token, once each
(``experts_hit``: the program's own count, summed over the expert layers).
Beside the weights it moves the rows of the token-expert pairs: into the up
product ``moe_latent_size`` values a pair, out of it and into the down
product ``moe_intermediate_size`` each, out of it ``moe_latent_size``.  A
pair costs one multiply-add (2 operations) per weight of its expert.

Only this is counted, as ``kernels/moe_gmm.py`` says of its own: what a
kernel does beyond it lowers the share, so the share cannot pass 100%.  The
shared projections into and out of the latent run outside ``moe_gmm`` and
are not counted.  At 32 rows a step the bound is memory: 11.0 MB of weights
an expert hit against a few rows of work.
"""


def _shape(config: dict):
    layers = str(config["hybrid_override_pattern"])[
        : int(config["num_hidden_layers"])].count("E")
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return (int(config["moe_latent_size"]), int(config["moe_intermediate_size"]),
            layers, itemsize)


def expert_layers(config: dict) -> int:
    return _shape(config)[2]


def bytes_per_step(config: dict, experts_hit: float, pairs: float) -> float:
    """``experts_hit``: held experts that got a token, summed over the expert
    layers; ``pairs``: token-expert pairs computed, summed likewise."""
    latent, width, _, itemsize = _shape(config)
    weights = experts_hit * 2 * latent * width
    rows = pairs * (latent + width + width + latent)
    return float((weights + rows) * itemsize)


def flops_per_step(config: dict, pairs: float) -> float:
    latent, width, _, _ = _shape(config)
    return float(pairs * 2 * latent * width * 2)
