"""Bytes and operations the state-space recurrence of the Mamba-2 layers
(``ops/mamba2.py``) must move and do, from the configuration's shapes: the
rooflines of ``mamba_step_roofline_pct`` and ``mamba_scan_roofline_pct``.

A Mamba-2 layer keeps, a sequence, a float32 state of ``mamba_num_heads x
mamba_head_dim x ssm_state_size`` and the last ``conv_kernel - 1`` rows of
its convolution's input (``heads x head_dim + 2 x n_groups x
ssm_state_size`` channels at the configuration's ``dtype``).

One DECODE step reads every live row's state and convolution rows once and
writes them once.  Only that is counted (the step's 5 operations a state
element are a thousandth of what the chip does in the time the bytes take):
a step that copies the state on the way, or touches rows that are not live,
reads lower.

One PREFILL token costs the recurrence as written ``5 x head_dim x
state_size`` operations a head (the decay's multiply, the rank-one update's
multiply and add, the read ``S C``'s multiply and add) and moves a head's
``x`` and ``y`` (``head_dim`` each), its step ``dt``, and its group's ``B``
and ``C`` (``state_size`` each, shared by the heads of the group), at the
configuration's ``dtype``.  It reads the same whatever implements the scan
(a chunked form does other, and at these shapes more, operations and is
judged by the same count), so the share cannot pass 100%.
"""


def _shape(config: dict):
    layers = str(config["hybrid_override_pattern"])[
        : int(config["num_hidden_layers"])].count("M")
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return (int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
            int(config["n_groups"]), int(config["ssm_state_size"]),
            int(config["conv_kernel"]), layers, itemsize)


def mamba_layers(config: dict) -> int:
    return _shape(config)[5]


def state_bytes_per_step(config: dict, live_rows: float) -> float:
    """Read and write of the float32 state and of the convolution rows of
    ``live_rows`` sequences in every Mamba-2 layer."""
    heads, dim, groups, state, taps, layers, itemsize = _shape(config)
    conv = (taps - 1) * (heads * dim + 2 * groups * state) * itemsize
    return float(live_rows * layers * 2 * (heads * dim * state * 4 + conv))


def scan_flops_per_token(config: dict) -> float:
    heads, dim, _, state, _, layers, _ = _shape(config)
    return float(layers * heads * 5 * dim * state)


def scan_bytes_per_token(config: dict) -> float:
    heads, dim, groups, state, _, layers, itemsize = _shape(config)
    return float(layers * (heads * (2 * dim + 1) + groups * 2 * state) * itemsize)
