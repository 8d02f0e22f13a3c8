"""Operations the flash-attention kernels of one training step must do, from
the configuration's shapes: the roofline of ``flash_roofline_pct``.

Causal attention of one head over ``S`` positions touches the ``S (S + 1) / 2``
query-key pairs on and under the diagonal.  A pair costs one multiply-add
(2 FLOPs) per element of the head in each matmul it takes part in:

- forward, 2 matmuls: the scores ``Q K^T`` and the output ``P V``;
- backward, 5: the scores again (flash attention keeps no ``S x S`` matrix
  and has to recompute them), ``dP = dO V^T``, ``dV = P^T dO``,
  ``dQ = dS K`` and ``dK = dS^T Q``.

So a head needs ``7 * 2 * D * S (S + 1) / 2`` FLOPs a layer, and a step
``batch * heads * layers`` times that.  Only this is counted.  What a kernel
does beyond it lowers the share and is the kernel's to save: the masked half
of the tiles on the diagonal, the second recomputation of scores and ``dP``
where the backward runs as two kernels (``flash_bwd_dq`` + ``flash_bwd_dkv``,
7 matmuls for the 5), the exponentials, the row sums.  The share can
therefore not pass 100%.  (``kernels/lm_step.py`` counts the whole square for
``mfu_pct``, the convention of the pre-round figures: the two are different
quantities.)  The bound is compute: at 128-wide heads the kernels move
``O(S D)`` bytes a head for ``O(S^2 D)`` operations.
"""

MATMULS_FORWARD, MATMULS_BACKWARD = 2, 5


def causal_flops_per_step(config: dict, traffic: dict) -> float:
    model = config["model"]
    seq, batch = int(traffic["seq_len"]), int(traffic["batch_size"])
    heads, depth = int(model["num_heads"]), int(model["depth"])
    head_dim = int(model["embed_dim"]) // heads
    pairs = seq * (seq + 1) // 2
    per_head = (MATMULS_FORWARD + MATMULS_BACKWARD) * 2 * head_dim * pairs
    return float(batch * heads * depth * per_head)
