"""Bytes and operations the delta-rule recurrence of the KDA layers
(``ops/kda.py``) must move and do, from the configuration's shapes: the
rooflines of ``kda_step_roofline_pct`` and ``kda_scan_roofline_pct``.

A KDA layer keeps, a sequence, a float32 state of ``heads x d_k x d_v``
(``linear_attn_config``: ``num_heads``, ``head_dim`` for both).

One DECODE step reads every live row's state once and writes it once:
``2 x 4 x heads x d_k x d_v`` bytes a row a layer.  Only that is counted (the
step's 2 x 3 x d_k x d_v operations a head are a thousandth of what the
chip does in the time the bytes take): a step that copies the state on the
way, or touches rows that are not live, reads lower.

One PREFILL token costs the recurrence ``6 x d_k x d_v`` operations a head
(decay, the read ``S'^T k``, the rank-one update, the read ``S^T q``: three
passes of a multiply and an add over the state) and moves the rows of q, k,
the decay (``d_k`` each), v and the output (``d_v`` each), at the
configuration's ``dtype``.  It reads the same whatever implements the scan
(a chunked form does more operations than these and is judged by the same
count), so the share cannot pass 100%.
"""


def _shape(config: dict):
    linear = config["linear_attn_config"]
    layers = sum(
        i not in config["gqa_layers"] for i in range(int(config["num_hidden_layers"])))
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return int(linear["num_heads"]), int(linear["head_dim"]), layers, itemsize


def kda_layers(config: dict) -> int:
    return _shape(config)[2]


def state_bytes_per_step(config: dict, live_rows: float) -> float:
    """Read and write of the float32 state of ``live_rows`` sequences in
    every KDA layer."""
    heads, dim, layers, _ = _shape(config)
    return float(live_rows * layers * heads * dim * dim * 4 * 2)


def scan_flops_per_token(config: dict) -> float:
    heads, dim, layers, _ = _shape(config)
    return float(layers * heads * 6 * dim * dim)


def scan_bytes_per_token(config: dict) -> float:
    heads, dim, layers, itemsize = _shape(config)
    return float(layers * heads * 5 * dim * itemsize)
