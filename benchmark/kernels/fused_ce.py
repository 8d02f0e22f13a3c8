"""Bytes the two fused cross-entropy kernels of one training step must move
between HBM and the chip, from the configuration's shapes: the roofline of
``fused_ce_roofline_pct``.

The logits are ``[rows, vocab]`` with ``rows = batch * seq_len`` in float32
(the model's head is ``nn.Dense(vocab, dtype=float32)``).  Softmax
cross-entropy needs every logit once to make the loss, and once more to make
its gradient, which is as large as the logits:

- ``fused_ce_fwd`` reads the logits ONCE (one pass finds the row's maximum,
  its log-sum-exp and the label's logit) and the labels, and writes two
  floats a row (loss, log-sum-exp);
- ``fused_ce_bwd`` reads the logits ONCE, the labels and the log-sum-exp, and
  writes the gradient ONCE.

That is ``3 * rows * vocab * 4`` bytes and ``6 * 4`` bytes a row beside them;
nothing else is counted, so the share cannot pass 100%.  The bound is memory:
a few operations a logit against 12 bytes moved.
"""

LOGIT_BYTES = 4          # float32
ROW_BYTES = 6 * 4        # labels twice, loss, log-sum-exp written and read, scale


def bytes_per_step(config: dict, traffic: dict) -> float:
    rows = int(traffic["batch_size"]) * int(traffic["seq_len"])
    vocab = int(config["vocab_size"])
    return float(3 * rows * vocab * LOGIT_BYTES + rows * ROW_BYTES)
