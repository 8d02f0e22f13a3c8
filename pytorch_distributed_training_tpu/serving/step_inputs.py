"""The host arrays of one single-position decode step.

``decode_step`` (``serving/decode.py``) is ONE fixed-width program: every
call hands it ``slots`` rows, the live ones filled in and the rest riding
along at position -1, where the pool scatter drops and the sampled token is
ignored.  Whoever calls it — the scheduler's ring, the supervisor's probe,
the restart's replay, a speculative draft's steps — differs only in WHICH
rows are live and in what each row is fed, so the arrays are built here and
nowhere else: a new per-row input of the program is one more field of a row
and one more line of :func:`step_inputs`.

:func:`step_inputs` is pure: numbers and ``numpy`` rows go in, seven
``numpy`` arrays come out, in the order the program takes them.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["StepInputs", "StepRow", "step_inputs"]


# (slot, prompt length, index of the token the step samples, the token the
# step is fed: the one before it, or None where the device carries it,
# the row's block table, adapter id, sampling key row or None = the pad key)
StepRow = Tuple[
    int, int, int, Optional[int], Sequence[int], int, Optional[np.ndarray]
]


class StepInputs(NamedTuple):
    """What ``decode_step`` takes behind ``(params, pool, prev_tok)``, in its
    own order: ``decode_step(params, pool, carry, *inputs, *state_rows)``."""

    fresh_mask: np.ndarray  # bool [slots]: the rows fed ``fresh_tok``
    fresh_tok: np.ndarray  # int32 [slots]
    pos: np.ndarray  # int32 [slots]: where the fed token sits; -1 = dead
    tables: np.ndarray  # int32 [slots, table_blocks]
    keys: np.ndarray  # uint32 [slots, 2]
    gen_idx: np.ndarray  # int32 [slots]: folded into the row's key
    aids: np.ndarray  # int32 [slots]: -1 = the base model


def step_inputs(
    slots: int,
    table_blocks: int,
    pad_key: np.ndarray,
    rows: Iterable[StepRow],
) -> StepInputs:
    """The inputs of a step in which ``rows`` (:data:`StepRow`) are live.

    A row that samples its token ``index`` is fed token ``index - 1``, which
    sits at global position ``prompt_len + index - 1``.  Where the caller
    holds that token it passes it and the row is ``fresh``; where the token
    is still on the device (a step of the ring not yet read) it passes None
    and the program feeds the row its own carried output.  A table shorter
    than ``table_blocks`` is padded with block 0, which no live position
    reaches.
    """
    fresh_mask = np.zeros((slots,), bool)
    fresh_tok = np.zeros((slots,), np.int32)
    pos = np.full((slots,), -1, np.int32)
    tables = np.zeros((slots, table_blocks), np.int32)
    gen_idx = np.zeros((slots,), np.int32)
    aids = np.full((slots,), -1, np.int32)
    keys = np.tile(pad_key, (slots, 1))
    for i, prompt_len, index, token, table_ids, adapter, key in rows:
        if token is not None:
            fresh_mask[i] = True
            fresh_tok[i] = token
        pos[i] = prompt_len + index - 1
        tables[i, : len(table_ids)] = table_ids
        gen_idx[i] = index
        aids[i] = adapter
        if key is not None:
            keys[i] = key
    return StepInputs(fresh_mask, fresh_tok, pos, tables, keys, gen_idx, aids)
