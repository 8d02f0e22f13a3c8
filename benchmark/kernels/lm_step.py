"""Operations one training sample of the decoder-only LM requires, forward
and backward, recomputed operations not counted.

Copied from ``bench.py``'s MFU arithmetic (PaLM appendix B): every matmul
parameter does 6 FLOPs a token (2 forward, 4 backward), and attention adds
``12 * L * S * E`` a token (QK^T and PV, forward and backward, the whole
square: the convention the pre-round figures used, kept so they compare).
Embedding lookups and elementwise work are not counted.
"""


def matmul_params(model: dict, vocab: int) -> int:
    e, depth = int(model["embed_dim"]), int(model["depth"])
    hidden = int(e * float(model.get("mlp_ratio", 4.0)))
    per_block = e * 3 * e + e * e + e * hidden + hidden * e
    return depth * per_block + e * vocab  # + the untied head


def flops_per_token(model: dict, vocab: int, seq_len: int) -> float:
    e, depth = int(model["embed_dim"]), int(model["depth"])
    return 6.0 * matmul_params(model, vocab) + 12.0 * depth * seq_len * e


def flops_per_sample(config: dict, traffic: dict) -> float:
    seq = int(traffic["seq_len"])
    return seq * flops_per_token(config["model"], int(config["vocab_size"]), seq)
