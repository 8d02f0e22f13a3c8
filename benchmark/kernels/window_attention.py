"""Bytes the attention layers of a model with WINDOW layers must read in ONE
decode step, from the configuration's shapes and from what the step itself
reported: the rooflines of ``window_attention_roofline_pct`` and
``full_attention_roofline_pct``.

A cached position of one layer is a key and a value of ``num_key_value_heads
x head_dim`` each, at the configuration's ``dtype``: ``2 x 8 x 128 x 2 B =
4,096 B`` at the published widths.  A step's query reads, a live row:

- in a WINDOW layer (``layer_types``: ``sliding_attention``) the row's
  ``min(L, sliding_window)`` newest positions and nothing older, ``L`` the
  row's length with the query's own position: ``window_keys``, the sum over
  the live rows, is a field of the program's ``decode_step`` span;
- in a FULL layer (``full_attention``) all ``L`` of them: ``full_keys``.

Only those reads are counted, once each: the write of the step's own row,
the query, the output and whatever an implementation reads beyond the kept
positions (a block's dead tail, a whole ring) lower the share and are the
implementation's to save.  The share can therefore not pass 100%, whatever
implements the scope.  The bound is memory: a position costs 4,096 B and
``2 x heads x 128 x 2`` operations, 8 to 12 operations a byte against the
chip's 240.
"""


def _layers(config: dict, kind: str) -> int:
    return sum(k == kind for k in config["layer_types"][:int(config["num_hidden_layers"])])


def window_layers(config: dict) -> int:
    return _layers(config, "sliding_attention")


def full_layers(config: dict) -> int:
    return _layers(config, "full_attention")


def position_bytes(config: dict) -> int:
    """Key and value of one position of one layer."""
    itemsize = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    return 2 * int(config["num_key_value_heads"]) * int(config["head_dim"]) * itemsize


def window_bytes_per_step(config: dict, window_keys: float) -> float:
    """``window_keys``: sum over a step's live rows of ``min(L, window)``."""
    return float(window_keys * window_layers(config) * position_bytes(config))


def full_bytes_per_step(config: dict, full_keys: float) -> float:
    """``full_keys``: sum over a step's live rows of ``L``."""
    return float(full_keys * full_layers(config) * position_bytes(config))
