"""Autoregressive generation over the TransformerLM KV-cache decode mode.

One jit program pair per (batch bucket, sequence bucket): ``prefill``
consumes the prompt batch in a single pass (filling the KV cache and
sampling the first token), ``decode`` runs a ``lax.while_loop`` of
single-token steps.  The whole batch shares the programs, but every row
carries its own ``prompt_len`` — prompts are right-padded to the bucket's
sequence length and the per-row cache positions (ops/attention.py) keep
padded rows exact.

``while_loop`` rather than ``scan`` so a batch whose rows all hit EOS
stops paying decode steps (the EOS early-exit of the ISSUE): the carry is
scan-shaped, the trip count is data-dependent.

The two phases are separate XLA programs (round 6) so the engine can time
them independently — prefill is compute-bound (one big batched forward),
decode is latency-bound (max_new_tokens tiny steps); one fused program
hides which side a serving regression lives on.  ``build_generate_fn``
returns a callable object: ``__call__`` chains the phases (the original
contract), ``.prefill`` / ``.decode`` expose them for phase-timed serving.

Sampling is keyed PER ROW, PER TOKEN INDEX: row ``r`` of a batch draws
token ``i`` with ``fold_in(fold_in(rng, r), i)`` (token 0 is the one the
prefill program samples).  A row's token stream therefore depends only on
its own key and its own logits — never on batch composition — which is
what lets the continuous scheduler (serving/scheduler.py) re-batch rows
between decode steps and still reproduce the whole-batch path token for
token (the sampled-mode half of the decode-parity oracle).

``build_paged_fns`` is the paged twin over the block-table cache mode of
``ops/attention.py``: one prefill program per (batch, seq) bucket and ONE
single-token step program shared by every decode iteration, both over a
pool pytree threaded through the calls instead of a per-batch cache.  The
pool is DONATED to every program that returns it: the cache scatter
updates the caller's buffers in place, and the pool passed in is dead
once the call is made.

Multi-tenant decode modes (PR 17), all default-off:

  - ``quant=True`` (ops/quant.py): the DECODE programs expect the
    int8-quantized params tree and dequantize in-graph — weights rest in
    device memory at half/quarter the bytes, which is what memory-bound
    decode streams every step.  Prefill (compute-bound) keeps the plain
    tree, so each builder's two phases take DIFFERENT trees in quant
    mode; the engine/scheduler hold both.
  - ``adapter_ids`` (ops/lora.py): every paged program takes the per-row
    adapter-id array; it reaches the model only when the model was
    cloned with LoRA factors (-1 rows run the base model), so non-LoRA
    builds trace it as an ignored input and program counts are
    unchanged.
  - ``verify`` (serving/speculative.py): a prefill-shaped program that
    returns the FULL per-position logits instead of sampling one token —
    the target model scores a draft's k proposals in one batched step
    and the host does exact accept/reject on the logits.
  - ``copy_rows``: pool row gather/scatter for the speculative branch
    fork — copies a boundary block's committed rows into the branch's
    spare block (serving/kv_pool.py fork pattern) in one fixed-shape
    program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import pool_leaf_role
from ..ops.quant import dequantize_tree

__all__ = ["build_generate_fn", "build_paged_fns"]


def _make_sampler(temperature: float):
    """``sample(logits [B, V], keys [B]) -> tok [B]``: greedy argmax at
    temperature 0 (keys ignored), else a per-row categorical draw — vmapped
    so row r's draw consumes ONLY ``keys[r]`` and ``logits[r]`` and is
    bitwise independent of every other row."""
    @jax.named_scope("sample")
    def sample(logits, keys):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        draw = lambda k, l: jax.random.categorical(k, l / temperature)
        return jax.vmap(draw)(keys, logits).astype(jnp.int32)

    return sample


def _row_keys(rng, b: int):
    """One independent PRNG key per batch row: ``fold_in(rng, row)``."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        rng, jnp.arange(b, dtype=jnp.int32)
    )


def _token_keys(row_keys, index):
    """Key for generated-token ``index`` (scalar or [B]) of each row."""
    axis = 0 if jnp.ndim(index) else None
    return jax.vmap(jax.random.fold_in, in_axes=(0, axis))(row_keys, index)


class _GenerateFn:
    """``prefill`` + ``decode`` jit pair with the fused-call contract.

    ``prefill(params, tokens, prompt_len, rng) -> carry`` — fills the KV
    cache from the padded prompts and samples generated token 0.
    ``decode(params, prompt_len, carry) -> (out_tokens, gen_len)`` — the
    EOS-early-exit while_loop over single-token steps.
    ``__call__`` chains them, matching the pre-split ``generate`` contract
    (``decode_params`` overrides the tree the decode phase gets — the
    int8 tree when the builder was made with ``quant=True``).
    """

    def __init__(self, prefill, decode):
        self.prefill = prefill
        self.decode = decode

    def __call__(self, params, tokens, prompt_len, rng, decode_params=None):
        carry = self.prefill(params, tokens, prompt_len, rng)
        dp = params if decode_params is None else decode_params
        return self.decode(dp, prompt_len, carry)

    def _cache_size(self) -> int:
        """Total distinct XLA programs compiled (both phases) — feeds the
        engine's ``compile_count`` bucket-grid bound."""
        return self.prefill._cache_size() + self.decode._cache_size()


def build_generate_fn(
    model,
    max_new_tokens: int,
    temperature: float = 0.0,
    eos_id: Optional[int] = None,
    quant: bool = False,
):
    """Compile ``generate(params, tokens, prompt_len, rng)``.

    ``model``: a :class:`..models.transformer_lm.TransformerLM` (decode
    flag irrelevant — it is cloned with ``decode=True`` here).

    Returns a :class:`_GenerateFn` whose ``__call__`` maps ``tokens``
    [B, S] int32 (prompts right-padded to S) and ``prompt_len`` [B] int32
    (1 <= len <= S) to ``(out_tokens [B, max_new_tokens] int32,
    gen_len [B] int32)`` where ``gen_len`` counts valid generated tokens
    per row (including the EOS token when one was produced); positions
    past ``gen_len`` are 0.

    ``temperature == 0.0`` (static) is greedy argmax and ignores ``rng``;
    otherwise tokens are drawn from ``softmax(logits / temperature)``.

    ``quant=True``: the DECODE program's ``params`` argument is the
    int8-quantized tree (ops/quant.quantize_tree) and is dequantized
    in-graph; prefill still takes the plain tree.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if getattr(model, "state_shape", None) is not None:
        raise ValueError(
            f"{type(model).__name__} carries a fixed-size state a sequence "
            f"(state_shape {model.state_shape}) and the contiguous generate "
            "path has no slots to keep it in: serve it through the "
            "continuous scheduler (serving.scheduler.enabled: true)"
        )
    decode_model = model.clone(decode=True)
    max_len = model.max_len
    sample = _make_sampler(temperature)

    def hit_eos(tok):
        if eos_id is None:
            return jnp.zeros(tok.shape, bool)
        return tok == eos_id

    @jax.jit
    def prefill(params, tokens, prompt_len, rng):
        b, s = tokens.shape
        if s + max_new_tokens > max_len:
            # the last generated token's position is prompt_len-1+max_new
            # <= s-1+max_new; beyond the table the position gather would
            # clamp and silently reuse rows (same guard as training)
            raise ValueError(
                f"seq bucket {s} + max_new_tokens {max_new_tokens} exceeds "
                f"max_len {max_len}"
            )
        prefill_logits, variables = decode_model.apply(
            {"params": params}, tokens, mutable=["cache"]
        )
        cache = variables["cache"]
        # the first generated token comes from the prefill logits at each
        # row's last REAL position (right-padding means that is not s-1)
        last = jnp.take_along_axis(
            prefill_logits, (prompt_len - 1)[:, None, None], axis=1
        )[:, 0]
        row_keys = _row_keys(rng, b)
        tok = sample(last, _token_keys(row_keys, 0))
        done = hit_eos(tok)
        out = jnp.zeros((b, max_new_tokens), jnp.int32).at[:, 0].set(tok)
        gen_len = jnp.ones((b,), jnp.int32)
        return cache, tok, out, done, gen_len, row_keys

    @jax.jit
    def decode(params, prompt_len, carry):
        if quant:
            params = dequantize_tree(params, jnp.float32)
        cache0, tok0, out0, done0, gen_len0, row_keys0 = carry

        def cond(c):
            i, _, _, _, done, _, _ = c
            return (i < max_new_tokens) & ~done.all()

        def body(c):
            i, cache, prev, out, done, gen_len, row_keys = c
            # prev = generated token i-1, which sits at sequence position
            # prompt_len + i - 1; feeding it yields the logits for token i
            pos = prompt_len + i - 1
            logits, variables = decode_model.apply(
                {"params": params, "cache": cache},
                prev[:, None],
                jnp.minimum(pos, max_len - 1),
                mutable=["cache"],
            )
            cache = variables["cache"]
            tok = sample(logits[:, 0], _token_keys(row_keys, i))
            out = out.at[:, i].set(jnp.where(done, 0, tok))
            gen_len = gen_len + jnp.where(done, 0, 1).astype(jnp.int32)
            done = done | hit_eos(tok) | (pos + 1 >= max_len)
            return (i + 1, cache, tok, out, done, gen_len, row_keys)

        full = (jnp.int32(1), cache0, tok0, out0, done0, gen_len0, row_keys0)
        _, _, _, out, _, gen_len, _ = jax.lax.while_loop(cond, body, full)
        return out, gen_len

    return _GenerateFn(prefill, decode)


class _PagedFns:
    """Jit set + pool factory for the paged (block-table) cache mode.

    Each program consumes the pool it is given; use the one it returns.
    ``pool`` is donated (``donate_argnames``) in all four that take it, so
    the scatter of a step's rows writes the buffers the caller handed over
    instead of a copy of them, and nothing may hold a pool LEAF across a call (a
    slice or a ``tree_map`` result is a new buffer and is safe).  A call
    that raises before its dispatch (bad arguments, the scheduler's
    injected faults) leaves the pool as it was; one that raises after it
    can leave the leaves deleted (``leaf.is_deleted()``), and the caller
    then has no pool: the scheduler rebuilds one and replays
    (``ContinuousScheduler._pool_lost`` → hot restart), it does not probe.

    ``prefill(params, pool, tokens, positions, block_tables, last_col,
    row_keys, gen_index, adapter_ids) -> (tok, finite, pool)`` — scatter
    the suffix K/V into the pool and sample each row's token
    ``gen_index[r]`` from the logits at ``last_col`` (0 for a fresh
    prompt; the hot-restart replay path passes the index of the last
    already-delivered token so the resample is bitwise reproducible).
    ``row_keys``, here and in the decode program, is ``uint32 [B, 2]``
    key DATA, one row a batch row (what a stack of legacy ``PRNGKey``s is);
    the scheduler hands it over as ONE host ``numpy`` array, and the
    program folds each row's ``gen_index`` into its key on the device.
    ``decode_step(params, pool, prev_tok, fresh_mask, fresh_tok, pos,
    block_tables, row_keys, gen_index, adapter_ids) -> (tok, finite, pool)``
    — ONE single-token step for every slot, and the ONE decode program a
    model has; the scheduler's host loop supplies fresh inputs per
    iteration, so it serves any mix of in-flight requests.  The token each
    row is fed is ``where(fresh_mask, fresh_tok, prev_tok)``: ``prev_tok``
    is a token array ON THE DEVICE, the previous step's own output fed back
    without a host round-trip (the scheduler's ring), and ``fresh_tok`` the
    tokens the host knows (a row just prefilled, refilled or replayed).
    The output ``tok`` is a valid ``prev_tok`` of the next call, so step
    k+1 can be dispatched before step k's tokens are read back.  A caller
    that knows every live row's token (the ring at depth 0, the
    supervisor's probe, the replay, a speculative draft's steps) passes a
    mask of those rows and any ``prev_tok`` of the right placement.  In quant mode ``params`` here
    is the int8 tree.
    ``finite`` [B] bool is the on-device output guard: True iff every
    logit the row sampled from is finite — the serving mirror of the
    training anomaly guard, letting the scheduler evict a NaN-producing
    request without a Python exception (padding rows read stale pool
    rows, so only ACTIVE rows' flags are meaningful).
    For a model with expert layers (``model.moe_shape``) the decode
    program returns a FOURTH value, ``moe_stats`` int32 [2]: the
    experts that received a token this step and the largest count at one
    expert, each summed over the expert layers; callers that do not want it
    unpack ``tok, finite, pool, *_``.
    ``verify(params, pool, tokens, positions, block_tables, adapter_ids)
    -> (logits [B, S, V] f32, pool)`` — the speculative-decoding scoring
    program: prefill-shaped (scatters the fed tokens' K/V), but returns
    EVERY position's logits so the host can accept/reject a draft's k
    proposals from one call.  Always takes the PLAIN params tree, even
    in quant mode: verification is the accuracy anchor.
    ``copy_rows(pool, src, dst) -> pool`` — copy pool rows ``src[i]`` to
    ``dst[i]`` across every cache leaf (OOB ``dst`` entries drop): the
    speculative fork's boundary-block CoW into the spare block.
    ``init_pool(params)`` — the zero pool pytree (``jax.eval_shape`` over
    the apply: correct flax cache paths, no throwaway compile).
    For a model that carries a state a sequence (``model.state_shape``) the
    three programs that run the model take ``state_rows`` [B] as their last
    argument and the pool tree holds the ``[slots, ...]`` state leaves too
    (:func:`build_paged_fns`).
    """

    def __init__(self, prefill, decode_step, init_pool, verify, copy_rows):
        self.prefill = prefill
        self.decode_step = decode_step
        self.init_pool = init_pool
        self.verify = verify
        self.copy_rows = copy_rows

    def _cache_size(self) -> int:
        """Distinct XLA programs compiled across all phases — the
        scheduler's compile count is bounded by the bucket grid for
        prefill plus ONE program each for decode/verify/copy,
        independent of traffic."""
        return (
            self.prefill._cache_size()
            + self.decode_step._cache_size()
            + self.verify._cache_size()
            + self.copy_rows._cache_size()
        )


def build_paged_fns(
    model,
    block_size: int,
    num_blocks: int,
    temperature: float = 0.0,
    quant: bool = False,
    state_slots: int = 0,
):
    """Compile the paged prefill/decode/verify set over a shared block pool.

    Shapes are the scheduler's contract: ``tokens``/``positions`` are
    [B, S] (positions are GLOBAL sequence positions per token, -1 =
    padding — one program handles cold prefill, prefix-hit suffix prefill,
    and S=1 decode alike), ``block_tables`` is [B, T] physical block ids
    covering each row's whole reserved footprint, ``last_col`` [B] is the
    column of each row's final real token, ``row_keys`` ``uint32 [B, 2]``
    the per-row PRNG keys as key data, ``gen_index`` [B] each row's
    generated-token index (rows sit at DIFFERENT indices under continuous
    batching).  Every array is fixed-width; inactive rows ride along with
    position -1 (their scatter drops, their sampled token is ignored
    host-side).

    ``adapter_ids`` [B] int32 (-1 = base model) reaches the model only
    when it was cloned with LoRA factors — non-LoRA builds trace it as an
    unused input, so signatures (and compile counts) stay uniform across
    modes.  ``quant=True`` makes ``decode_step`` expect the int8 tree
    (ops/quant.quantize_tree) and dequantize in-graph; prefill and verify
    keep the plain tree.

    A model that carries a fixed-size state a sequence says so
    (``model.state_shape`` is not None).  Its cache tree then holds, beside
    the pool's ``[pool_rows, ...]`` leaves and under the same donated
    ``pool`` argument, ``[state_slots, ...]`` leaves (``init_pool`` makes
    both), and every program that takes the pool takes ``state_rows`` [B]
    int32 as its last argument: the slot each batch row's state lives in
    (-1 = padding: nothing is written).  A row whose first position is 0
    starts from a zero state, so a slot needs no clearing between requests.
    The decode program is the scheduler's fixed-width step, whose
    batch row ``i`` IS slot ``i``: it says so to the model
    (``rows_are_slots=True``) and ``state_rows`` there only tells the live
    rows from the padding.  ``copy_rows`` passes the state leaves by.  For any other model
    ``state_rows`` stays None and the programs are what they were.
    """
    carries_state = getattr(model, "state_shape", None) is not None
    if carries_state and state_slots < 1:
        raise ValueError(
            f"{type(model).__name__} carries a state a sequence: "
            f"build_paged_fns needs state_slots >= 1, got {state_slots}"
        )
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if num_blocks < 1:
        raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    paged_model = model.clone(
        decode=True, paged=True,
        kv_block_size=int(block_size), kv_num_blocks=int(num_blocks),
        **({"state_slots": int(state_slots)} if carries_state else {}),
    )
    has_lora = getattr(paged_model, "lora_adapters", 0) > 0
    # a model with expert layers (it says so) sows two counts a call into
    # ``moe_stats``: the decode program returns them as a FOURTH output,
    # int32 [2] = (experts that got a token, largest count at one expert),
    # each summed over the expert layers.  A model with none: the programs
    # and their outputs are what they were.
    has_experts = getattr(paged_model, "moe_shape", None) is not None
    mutable = ["cache", "moe_stats"] if has_experts else ["cache"]
    pool_rows = int(num_blocks) * int(block_size)
    # no eos_id here: EOS detection is the HOST's job in paged mode — the
    # scheduler reads every token anyway (to stream it and retire slots),
    # so the programs stay pure token-samplers and the stop conditions
    # (eos / per-request max_new) live in one place
    sample = _make_sampler(temperature)
    # what the decode program adds to a call of a model that carries a state
    fixed_width = {"rows_are_slots": True} if carries_state else {}

    def _apply(params, pool, tokens, positions, block_tables, adapter_ids,
               state_rows, **more):
        args = (tokens, positions, block_tables)
        if has_lora:
            args = args + (adapter_ids,)
        if carries_state:
            if state_rows is None:
                raise ValueError(
                    f"{type(model).__name__} carries a state a sequence: "
                    "every paged call names its rows' slots (state_rows)"
                )
            more["state_rows"] = state_rows
        return paged_model.apply(
            {"params": params, "cache": pool}, *args, mutable=mutable, **more,
        )

    def _step_outputs(tok, logits, variables):
        out = (tok, jnp.isfinite(logits).all(axis=-1), variables["cache"])
        if has_experts:
            stats = variables["moe_stats"]
            out += (jnp.stack([
                jnp.sum(jnp.stack(stats["experts_hit"])),
                jnp.sum(jnp.stack(stats["expert_load_max"])),
            ]).astype(jnp.int32),)
        return out

    @functools.partial(jax.jit, donate_argnames="pool")
    def prefill(
        params, pool, tokens, positions, block_tables, last_col, row_keys,
        gen_index, adapter_ids=None, state_rows=None,
    ):
        if getattr(paged_model, "takes_logit_cols", False):
            # the model gives the logits of one column a row (a [B, S, V]
            # in float32 need not fit beside its weights)
            logits, variables = _apply(
                params, pool, tokens, positions, block_tables, adapter_ids,
                state_rows, logit_cols=last_col,
            )
            last = logits[:, 0]
        else:
            logits, variables = _apply(
                params, pool, tokens, positions, block_tables, adapter_ids,
                state_rows,
            )
            last = jnp.take_along_axis(
                logits, last_col[:, None, None], axis=1)[:, 0]
        tok = sample(last, _token_keys(row_keys, gen_index))
        return tok, jnp.isfinite(last).all(axis=-1), variables["cache"]

    @functools.partial(jax.jit, donate_argnames="pool")
    def decode_step(
        params, pool, prev_tok, fresh_mask, fresh_tok, pos, block_tables,
        row_keys, gen_index, adapter_ids=None, state_rows=None,
    ):
        if quant:
            params = dequantize_tree(params, jnp.float32)
        # prev_tok is a step's ON-DEVICE token output; the rows whose last
        # token the host knows (every live row of a probe, a replay or a
        # draft's step, the ones just (re)filled on the ring) get it
        # spliced in here, so the ring
        # never needs a host round-trip to mix fresh rows into the carry
        prev = jax.lax.select(fresh_mask, fresh_tok, prev_tok)
        logits, variables = _apply(
            params, pool, prev[:, None], pos[:, None], block_tables,
            adapter_ids, state_rows, **fixed_width,
        )
        tok = sample(logits[:, 0], _token_keys(row_keys, gen_index))
        return _step_outputs(tok, logits[:, 0], variables)

    @functools.partial(jax.jit, donate_argnames="pool")
    def verify(params, pool, tokens, positions, block_tables, adapter_ids=None,
               state_rows=None):
        logits, variables = _apply(
            params, pool, tokens, positions, block_tables, adapter_ids,
            state_rows,
        )
        return logits.astype(jnp.float32), variables["cache"]

    @functools.partial(jax.jit, donate_argnames="pool")
    def copy_rows(pool, src, dst):
        src_c = jnp.clip(src, 0, pool_rows - 1)

        def cp(path, leaf):
            # the pool's per-row leaves, by what the attention modules
            # declare; a per-slot state leaf is passed by whatever its size
            if pool_leaf_role(path, leaf, pool_rows):
                return leaf.at[dst].set(leaf[src_c], mode="drop")
            return leaf

        return jax.tree_util.tree_map_with_path(cp, pool)

    def init_pool(params):
        # any concrete shapes work — the pool's shape depends only on the
        # model config, and eval_shape never touches device memory
        init_args = [
            jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32),
        ]
        if has_lora:
            init_args.append(jnp.zeros((1,), jnp.int32))
        more = {"state_rows": jnp.zeros((1,), jnp.int32)} if carries_state else {}
        shapes = jax.eval_shape(
            lambda p: paged_model.apply(
                {"params": p}, *init_args, mutable=["cache"], **more,
            )[1]["cache"],
            params,
        )
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

    return _PagedFns(prefill, decode_step, init_pool, verify, copy_rows)
