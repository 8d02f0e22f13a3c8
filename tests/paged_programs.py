"""Helpers of the tests that ask which kernels a family's paged programs
hold: the programs lowered as a TPU would get them, from the CPU, over
shapes alone."""
import re

import jax
import jax.numpy as jnp
import pytest


def lowered_for_tpu(program, args, monkeypatch) -> str:
    """StableHLO of ``program`` as a TPU would get it: the routing asks
    ``flash_enabled()``, which is the CPU's answer here."""
    from pytorch_distributed_training_tpu.ops import flash_attention as gate

    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    return program.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def call_shapes(params, pool, *, rows, positions, table, slots=0):
    """``(prefill args, decode_step args)``: a prefill of ``rows`` x
    ``positions`` and the fixed-width step over ``slots`` slots, which a
    model that carries a state names (``rows`` where it is 0), tables of
    ``table`` entries; every argument but the trees a shape."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    keys = lambda n: jax.ShapeDtypeStruct((n, 2), jnp.uint32)  # noqa: E731
    state = lambda n: (i32(n),) if slots else ()  # noqa: E731
    w = slots or rows
    prefill = (params, pool, i32(rows, positions), i32(rows, positions),
               i32(rows, table), i32(rows), keys(rows), i32(rows), i32(rows),
               *state(rows))
    decode = (params, pool, i32(w), jax.ShapeDtypeStruct((w,), jnp.bool_), i32(w),
              i32(w), i32(w, table), keys(w), i32(w), i32(w), *state(w))
    return prefill, decode


def flash_forwards(text: str):
    """``(kernels lowered, calls of them)`` of the causal flash forward in a
    program's StableHLO: the kernel is a jitted function, lowered once a
    program and called a layer."""
    lowered = len(re.findall(r'kernel_name = "flash_fwd(?:_stream)?"', text))
    calls = len(re.findall(r"= call @_forward\w*\(", text))
    return lowered, calls


def check_prefill_alone_holds_the_flash_forward(sched, full_layers, monkeypatch):
    """On a TPU, at a bucket of 128 (a shape the kernel takes), ``sched``'s
    prefill program scores its ``full_layers`` full-attention layers through
    the causal flash forward, lowered ONCE and called a layer, as many as the
    scheduler counts (``_flash_layers``); a bucket of 16 keeps the gather
    arm, and the decode step, one position a row, holds no such kernel."""
    prefill, decode = call_shapes(
        sched.params, sched._pool, rows=1, positions=128,
        table=sched.table_blocks, slots=sched.slots_n)
    text = lowered_for_tpu(sched._fns.prefill, prefill, monkeypatch)
    assert [sched._flash_layers(sb) for sb in (16, 128)] == [0, full_layers]
    assert flash_forwards(text) == (1, full_layers)
    text = lowered_for_tpu(sched._fns.decode_step, decode, monkeypatch)
    assert flash_forwards(text) == (0, 0)


def check_a_call_past_position_zero_is_refused(sched, prompt, block, model_name):
    """A model that carries a state is prefilled a whole prompt a call (its
    scan starts from the slot's zero state at column 0, and a full layer may
    score the call's own keys: ``ops/attention.py::paged_attention``,
    ``whole_prompts``), so the scheduler, which decides what a call holds,
    refuses a call whose rows start anywhere else: here a request made to
    look as if a prefix of one block were cached."""
    future = sched.submit(prompt)
    calls = sched._prefill_calls

    def a_piece(newly):
        for req in newly:
            req.admission.cached_len = block
        return calls(newly)

    sched._prefill_calls = a_piece
    with pytest.raises(ValueError, match=rf"rows start at \[{block}\] cannot serve "
                                         rf"{model_name}.*prefilled whole, from position 0"):
        while not future.done():
            sched._tick_inner()  # under tick()'s restarts the same refusal
