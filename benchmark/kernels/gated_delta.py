"""Bytes and operations the delta-rule recurrence of the Gated DeltaNet
layers (``ops/gated_delta.py``) must move and do, from the configuration's
shapes: the rooflines of ``gdn_step_roofline_pct`` and
``gdn_scan_roofline_pct``.  The counts are those of ``kernels/kda.py`` with
the state rectangular and the decay ONE number a head.

A linear layer (``layer_types``: ``linear_attention``) keeps, a sequence, a
float32 state of ``linear_num_value_heads x linear_key_head_dim x
linear_value_head_dim``.

One DECODE step reads every live row's state once and writes it once:
``2 x 4 x heads x d_k x d_v`` bytes a row a layer, the LOGICAL bytes (the
device pads the 192 lanes of a row to 256: a step that moves the padding too
reads lower, as it should).  Only that is counted: a step that copies the
state on the way, or touches rows that are not live, reads lower.

One PREFILL token costs the recurrence ``6 x d_k x d_v`` operations a head
(decay, the read ``S'^T k``, the rank-one update, the read ``S^T q``: three
passes of a multiply and an add over the state) and moves the rows of q, k
(``d_k`` each), v and the output (``d_v`` each) and the decay (one number), at
the configuration's ``dtype``.  It reads the same whatever implements the
scan (a chunked form does more operations than these and is judged by the
same count), so the share cannot pass 100%.
"""


def _shape(config: dict):
    layers = sum(
        kind == "linear_attention"
        for kind in config["layer_types"][:int(config["num_hidden_layers"])])
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return (int(config["linear_num_value_heads"]), int(config["linear_key_head_dim"]),
            int(config["linear_value_head_dim"]), layers, itemsize)


def gdn_layers(config: dict) -> int:
    return _shape(config)[3]


def state_bytes_per_step(config: dict, live_rows: float) -> float:
    """Read and write of the float32 state of ``live_rows`` sequences in
    every linear layer."""
    heads, d_k, d_v, layers, _ = _shape(config)
    return float(live_rows * layers * heads * d_k * d_v * 4 * 2)


def scan_flops_per_token(config: dict) -> float:
    heads, d_k, d_v, layers, _ = _shape(config)
    return float(layers * heads * 6 * d_k * d_v)


def scan_bytes_per_token(config: dict) -> float:
    heads, d_k, d_v, layers, itemsize = _shape(config)
    return float(layers * heads * (2 * d_k + 2 * d_v + 1) * itemsize)
