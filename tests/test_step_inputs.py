"""``serving/step_inputs.py``: the one builder of a decode step's host arrays,
each caller's row form against arrays written out by hand.  No model, no
program: four slots, tables of three blocks."""
import numpy as np
import pytest

from pytorch_distributed_training_tpu.serving.step_inputs import (
    StepInputs,
    step_inputs,
)

PAD = np.asarray([0, 0], np.uint32)
KEY_A = np.asarray([7, 11], np.uint32)
KEY_B = np.asarray([13, 17], np.uint32)

# rows: (slot, prompt length, token index, fed token or None = carried,
#        block table, adapter, key row or None = the pad key)
CASES = {
    # the ring at depth 1: slot 2 was just prefilled (its token is known),
    # slot 0 has a step in flight (the device carries its token)
    "ring_with_a_carried_row": (
        [(0, 5, 3, None, [4, 9, 2], -1, KEY_A), (2, 7, 1, 41, [6, 1, 8], 1, KEY_B)],
        dict(fresh_mask=[False, False, True, False], fresh_tok=[0, 0, 41, 0],
             pos=[7, -1, 7, -1],
             tables=[[4, 9, 2], [0, 0, 0], [6, 1, 8], [0, 0, 0]],
             keys=[KEY_A, PAD, KEY_B, PAD], gen_idx=[3, 0, 1, 0],
             aids=[-1, -1, 1, -1]),
    ),
    # the supervisor's probe: a subset of the slots, every token known
    "probe": (
        [(1, 4, 2, 30, [5, 3, 7], -1, KEY_A), (3, 2, 6, 12, [2, 4, 6], 0, KEY_B)],
        dict(fresh_mask=[False, True, False, True], fresh_tok=[0, 30, 0, 12],
             pos=[-1, 5, -1, 7],
             tables=[[0, 0, 0], [5, 3, 7], [0, 0, 0], [2, 4, 6]],
             keys=[PAD, KEY_A, PAD, KEY_B], gen_idx=[0, 2, 0, 6],
             aids=[-1, -1, -1, 0]),
    ),
    # the replay's re-feed at index k = 3: delivered token k - 1 goes in at
    # position prompt_len + k - 1, whatever the request's own count says
    "replay_at_index_k": (
        [(0, 6, 3, 22, [1, 2, 3], 2, KEY_B)],
        dict(fresh_mask=[True, False, False, False], fresh_tok=[22, 0, 0, 0],
             pos=[8, -1, -1, -1],
             tables=[[1, 2, 3], [0, 0, 0], [0, 0, 0], [0, 0, 0]],
             keys=[KEY_B, PAD, PAD, PAD], gen_idx=[3, 0, 0, 0],
             aids=[2, -1, -1, -1]),
    ),
    # a speculative draft's step j = 1 behind 4 committed tokens: the
    # draft pool's own table, the pad key, no adapter without draft factors
    "draft": (
        [(1, 3, 4 + 1, 19, [8, 0, 5], -1, None), (2, 9, 2 + 1, 33, [7, 6, 4], -1, None)],
        dict(fresh_mask=[False, True, True, False], fresh_tok=[0, 19, 33, 0],
             pos=[-1, 7, 11, -1],
             tables=[[0, 0, 0], [8, 0, 5], [7, 6, 4], [0, 0, 0]],
             keys=[PAD, PAD, PAD, PAD], gen_idx=[0, 5, 3, 0],
             aids=[-1, -1, -1, -1]),
    ),
    # no live row at all (the warm-up's step): every slot dead at -1
    "dead_slots": (
        [],
        dict(fresh_mask=[False] * 4, fresh_tok=[0] * 4, pos=[-1] * 4,
             tables=[[0, 0, 0]] * 4, keys=[PAD] * 4, gen_idx=[0] * 4,
             aids=[-1] * 4),
    ),
    # a request whose footprint is two blocks of the three a table has:
    # the tail keeps block 0, which no live position reaches
    "table_shorter_than_table_blocks": (
        [(3, 1, 1, 2, [9, 4], -1, KEY_A)],
        dict(fresh_mask=[False, False, False, True], fresh_tok=[0, 0, 0, 2],
             pos=[-1, -1, -1, 1],
             tables=[[0, 0, 0], [0, 0, 0], [0, 0, 0], [9, 4, 0]],
             keys=[PAD, PAD, PAD, KEY_A], gen_idx=[0, 0, 0, 1],
             aids=[-1, -1, -1, -1]),
    ),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_steps_inputs_are_what_the_callers_rows_say(case):
    rows, want = CASES[case]
    got = step_inputs(4, 3, PAD, iter(rows))  # callers pass generators
    # the fields in the order decode_step takes them behind the carry
    assert StepInputs._fields == (
        "fresh_mask", "fresh_tok", "pos", "tables", "keys", "gen_idx", "aids")
    dtypes = dict(fresh_mask=bool, keys=np.uint32)
    for name, value in got._asdict().items():
        assert type(value) is np.ndarray
        assert value.dtype == dtypes.get(name, np.int32), name
        np.testing.assert_array_equal(value, np.asarray(want[name]), err_msg=name)
    assert got.tables.shape == (4, 3) and got.keys.shape == (4, 2)
    # each call's arrays are its own: a caller may keep one across a tick
    again = step_inputs(4, 3, PAD, rows)
    assert not any(np.shares_memory(a, b) for a, b in zip(got, again))
    assert not np.shares_memory(got.keys, PAD)
