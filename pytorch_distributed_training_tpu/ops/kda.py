"""Kimi Delta Attention (KDA): a gated delta-rule linear-attention layer
whose cache is a fixed-size STATE a sequence, not a row a token.

The layer (Kimi Linear, arXiv:2510.26692), ``H`` heads of ``d_k = d_v =
head_dim``, one token ``x_t`` at a time:

  q~, k~, v~ = x W_q, x W_k, x W_v                      (one matrix, ``w_qkv``)
  q, k, v    = silu(causal depthwise conv of ``conv_size`` taps, no bias)
  q^ = q / |q|_2 * d_k^-1/2,  k^ = k / |k|_2            (a head at a time)
  a_t = -exp(A_log_h) * softplus(W_f2 (W_f1 x_t) + dt_bias)   (log decay, a channel)
  b_t = sigmoid(W_b x_t)  (x 2 with ``allow_neg_eigval``)
  S' = diag(exp(a_t)) S_{t-1};  S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T;  o_t = S_t^T q^_t
  y = W_o [ RMSNorm_{d_v}(o_t) * sigmoid(W_g2 (W_g1 x_t)) ]

Two forms share the parameters and the state.  :func:`delta_rule_step` is
the update as written, one position a row (decode; scope ``kda_step``).
:func:`delta_rule_chunked` (prefill; scope ``kda_scan``) takes ``chunk``
positions at a time: inside a chunk the positions' updates are one
triangular system (the WY form of a product of Householder-like factors)
and products against the chunk's start state, between chunks the state is
carried by ``lax.scan``.  With ``A_i`` the running sum of the log decays
inside the chunk, every decay is formed as ``exp(A_i - A_j)`` with
``i >= j`` (an exponent <= 0, float32), never as a quotient of ``alpha``s.
Shared with :class:`..ops.gated_delta.GatedDeltaNet` (one decay a head, a
rectangular state): :func:`delta_rule_step`, which takes ``d_k`` and ``d_v``
apart and any decay that broadcasts over the keys, and ``_a_log_init``; not
:func:`delta_rule_chunked`, whose decay a channel stays inside the
contractions (the scalar form is ``gated_delta.delta_rule_chunked_scalar``).
Plain XLA, no Pallas kernel: the recurrence is three layers of four here
and a kernel is a ``perf_opt`` PR's to bring, measured by
``kda_scan_roofline_pct`` (PERF.md).

The cache, in the ``"cache"`` collection beside the paged pool's leaves but
addressed by SLOT, not by block table: ``kda_state [slots, H, d_k, d_v]``
float32 and ``kda_conv [slots, conv_size - 1, 3 H d_k]`` (the last rows of
``(q~, k~, v~)``).  A call names each batch row's slot in ``state_rows [B]``
(-1 = padding: read as slot 0, written nowhere); a row whose first position
is 0 starts from a zero state, so a slot is never cleared.  The scheduler's
decode step says so itself (``rows_are_slots=True``, from the decode program
of ``serving/decode.py``): one position a row, as many rows as slots, row
``i`` IS slot ``i`` and ``state_rows`` only says which rows live.  That step
updates the state leaf where it lies and the live rows only
(:func:`.state_rows.step_live_rows`: a row that is not live is neither read
nor written, its output is zeros; past half the slots live, one pass over
all of them).  Padding positions (-1, at a row's end) change neither state
nor convolution rows.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .attention import KDA_CONV, KDA_STATE
from .moe import in_token_chunks
from .state_rows import step_live_rows

__all__ = ["KimiDeltaAttention", "delta_rule_chunked", "delta_rule_step"]

_HIGHEST = jax.lax.Precision.HIGHEST
# positions a step of the prefill scan takes at once
CHUNK = 64
# tokens a group of rows may hold in a long call (a 32 x 4096 prefill
# holds one group's temporaries, not all)
TOKEN_BUDGET = 8192


def delta_rule_step(q, k, v, log_decay, beta, state):
    """One position: ``q, k, log_decay [B, H, d_k]``, ``v [B, H, d_v]``,
    ``beta [B, H]``, ``state [B, H, d_k, d_v]``, all float32 (``q``, ``k``
    already normalised).  Returns ``(o [B, H, d_v], state)``; the state is
    read once and written once."""
    decayed = state * jnp.exp(log_decay)[..., None]
    seen = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=_HIGHEST)
    delta = beta[..., None] * (v - seen)
    state = decayed + k[..., :, None] * delta[..., None, :]
    out = jnp.einsum("bhkv,bhk->bhv", state, q, precision=_HIGHEST)
    return out, state


def _chunk_update(state, xs):
    """One chunk of :func:`delta_rule_chunked`: ``q, k, g [B, H, C, d_k]``,
    ``v [B, H, C, d_v]``, ``beta [B, H, C]``; carries ``state``."""
    q, k, v, g, beta = xs
    c = q.shape[2]
    a = jnp.cumsum(g, axis=2)  # A_i: log decay from the chunk's start to i
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(A_i - A_j) a channel for j <= i; the exponent is masked BEFORE
    # the exp (above the diagonal it is positive and may overflow)
    diff = a[:, :, :, None, :] - a[:, :, None, :, :]
    decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
    kk = jnp.einsum("bhid,bhjd,bhijd->bhij", k, k, decay)  # k_i.(decay k_j)
    qk = jnp.einsum("bhid,bhjd,bhijd->bhij", q, k, decay)  # lower incl. diagonal
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    system = jnp.where(strict, beta[..., None] * kk, 0.0) + jnp.eye(c)
    k_in = k * jnp.exp(a)  # what position i sees of the start state
    rhs = beta[..., None] * (
        v - jnp.einsum("bhck,bhkv->bhcv", k_in, state, precision=_HIGHEST))
    # (I + diag(beta) strict(kk)) U = rhs: the chunk's deltas, all at once
    u = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    out = (
        jnp.einsum("bhck,bhkv->bhcv", q * jnp.exp(a), state, precision=_HIGHEST)
        + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HIGHEST)
    )
    total = a[:, :, -1:, :]  # A_C
    state = state * jnp.exp(total[:, :, 0, :, None]) + jnp.einsum(
        "bhck,bhcv->bhkv", k * jnp.exp(total - a), u, precision=_HIGHEST)
    return state, out


def delta_rule_chunked(q, k, v, log_decay, beta, state, chunk: int = CHUNK):
    """``S`` positions a row, ``chunk`` at a time: ``q, k, log_decay
    [B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``beta [B, S, H]``, ``state
    [B, H, d_k, d_v]``, float32.  Returns ``(o [B, S, H, d_v], state)``,
    equal to ``S`` calls of :func:`delta_rule_step` up to float32 rounding.
    A position with ``beta = 0`` and ``log_decay = 0`` (padding) leaves the
    state as it was."""
    b, s, h, dk = q.shape
    pad = -s % chunk

    def chunks(x):  # [B, S, H, ...] -> [n, B, H, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, (s + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    state, out = jax.lax.scan(
        _chunk_update, state,
        (chunks(q), chunks(k), chunks(v), chunks(log_decay), chunks(beta)),
    )
    # [n, B, H, C, d_v] -> [B, S, H, d_v]
    out = jnp.moveaxis(out, 0, 1).swapaxes(2, 3).reshape(b, s + pad, h, -1)
    return out[:, :s], state


def _a_log_init(key, shape, dtype):
    """``A_log = log U(1, 16)``: the public KDA layers' initialisation."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


class KimiDeltaAttention(nn.Module):
    """The layer above over ``x [B, S, dim]``.  ``decode=False``: every row
    starts from a zero state and nothing is kept (the full forward).
    ``decode=True``: state and convolution rows are read from and written to
    the slots ``state_rows`` names, ``positions [B, S]`` (-1 = padding, at a
    row's end) say which tokens count; ``S == 1`` takes the one-step form,
    longer calls the chunked one, in groups of rows of at most
    ``TOKEN_BUDGET`` tokens.  ``rows_are_slots`` (static): the caller's
    fixed-width decode step, whose row ``i`` is slot ``i``."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    decode: bool = False
    state_slots: int = 0

    @nn.compact
    def __call__(self, x, positions=None, state_rows=None,
                 rows_are_slots: bool = False):
        b, s, dim = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.conv_size
        ch = 3 * h * d
        init = nn.initializers.lecun_normal()
        p = {
            "w_qkv": self.param("w_qkv", init, (dim, ch), self.dtype),
            "conv_w": self.param(
                "conv_w", nn.initializers.normal(taps ** -0.5), (taps, ch), self.dtype),
            "w_f1": self.param("w_f1", init, (dim, d), self.dtype),
            "w_f2": self.param("w_f2", init, (d, h * d), self.dtype),
            "dt_bias": self.param("dt_bias", nn.initializers.zeros, (h * d,), self.dtype),
            "A_log": self.param("A_log", _a_log_init, (h,), self.dtype),
            "w_b": self.param("w_b", init, (dim, h), self.dtype),
            "w_g1": self.param("w_g1", init, (dim, d), self.dtype),
            "w_g2": self.param("w_g2", init, (d, h * d), self.dtype),
            "o_norm": self.param("o_norm", nn.initializers.ones, (d,), self.dtype),
            "w_o": self.param("w_o", init, (h * d, dim), self.dtype),
        }
        if not self.decode:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            state0 = jnp.zeros((b, h, d, d), jnp.float32)
            conv0 = jnp.zeros((b, taps - 1, ch), self.dtype)
            y, _, _ = self._rows(p, x, positions, state0, conv0, None)
            return y
        if self.state_slots < 1:
            raise ValueError(
                f"decode mode needs state_slots >= 1, got {self.state_slots}")
        if positions is None or state_rows is None:
            raise ValueError("decode mode needs positions and state_rows")
        slots = self.state_slots
        state = self.variable(
            "cache", KDA_STATE, jnp.zeros, (slots, h, d, d), jnp.float32)
        conv = self.variable(
            "cache", KDA_CONV, jnp.zeros, (slots, taps - 1, ch), self.dtype)
        if rows_are_slots:
            if s != 1 or b != slots:
                raise ValueError(
                    f"rows_are_slots is the decode step over all {slots} slots, "
                    f"one position a row; got {b} rows of {s} positions")
            # The leaves are read and written where they lie, no gathered
            # copy (at 32 rows a copy each way was 2.9 ms of a 13.8 ms
            # step; PERF.md PR 32), and of the state only the rows
            # ``state_rows`` says are live (a pass over all 32 slots costs
            # 0.60 ms a layer whatever lives, a live row 0.027; PERF.md
            # PR 42).  A row that names another slot breaks the contract
            # and is answered with NaN, which the output guard of the
            # serving programs evicts: loud, not wrong.
            live = state_rows >= 0
            y, state.value, conv.value = self._rows(
                p, x, positions, state.value, conv.value, live)
            aligned = ~live | (state_rows == jnp.arange(slots))
            return jnp.where(aligned[:, None, None], y, jnp.nan)
        read = jnp.clip(state_rows, 0, slots - 1)
        y, state1, conv1 = self._rows(
            p, x, positions, state.value[read], conv.value[read], None)
        # -1 (padding) is written nowhere: out of range, dropped
        write = jnp.where(state_rows >= 0, state_rows, slots)
        state.value = state.value.at[write].set(state1, mode="drop")
        conv.value = conv.value.at[write].set(conv1, mode="drop")
        return y

    def _rows(self, p, x, positions, state_in, conv_in, live):
        """The layer over all rows, a group of rows at a time where the
        call is long."""
        b, s, _ = x.shape
        group = max(1, TOKEN_BUDGET // s)
        if s == 1 or b <= group:
            return self._layer(p, x, positions, state_in, conv_in, live)
        y, state1, conv1 = in_token_chunks(
            lambda *piece: self._layer(p, *piece, None), group,
            x, positions, state_in, conv_in,
        )
        flat = lambda a: a.reshape((-1,) + a.shape[2:])[:b]  # noqa: E731
        return flat(y), flat(state1), flat(conv1)

    def _layer(self, p, x, positions, state_in, conv_in, live):
        """One group of rows: ``state_in``, ``conv_in`` are what the rows'
        slots hold; a row whose first position is 0 starts a sequence and
        reads zeros instead.  ``live [B]`` (the aligned decode step): a row
        that is not live keeps what its slot held, and its state is not
        touched.  The state's read, update and write all lie under
        ``kda_step`` / ``kda_scan``."""
        b, s, dim = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.conv_size
        f32 = jnp.float32
        valid = positions >= 0  # [B, S]; the valid tokens lead the row
        old = positions[:, 0] > 0
        with jax.named_scope("kda"):
            with jax.named_scope("kda_conv"):
                conv0 = jnp.where(old[:, None, None], conv_in, 0)
                pre = jnp.dot(x, p["w_qkv"])  # [B, S, 3Hd]
                cat = jnp.concatenate([conv0.astype(pre.dtype), pre], axis=1)
                w = p["conv_w"].astype(f32)
                mixed = sum(
                    w[j] * cat[:, j:j + s].astype(f32) for j in range(taps))
                qkv = jax.nn.silu(mixed).reshape(b, s, 3, h, d)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                q = q * jax.lax.rsqrt(
                    jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
                k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
                # the rows a later call's convolution needs: the last
                # ``taps - 1`` of what was there and the valid new ones
                n_valid = jnp.sum(valid, axis=1)
                at = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
                conv1 = jnp.take_along_axis(cat, at[:, :, None], axis=1)
                if live is not None:
                    conv1 = jnp.where(live[:, None, None], conv1, conv_in)
            with jax.named_scope("kda_gate"):
                rate = jnp.dot(jnp.dot(x, p["w_f1"]), p["w_f2"]).astype(f32)
                rate = jax.nn.softplus(rate + p["dt_bias"].astype(f32))
                log_decay = -jnp.exp(p["A_log"].astype(f32))[:, None] * rate.reshape(
                    b, s, h, d)
                beta = jax.nn.sigmoid(jnp.dot(x, p["w_b"]).astype(f32))
                if self.allow_neg_eigval:
                    beta = beta * 2.0
                gate = jax.nn.sigmoid(
                    jnp.dot(jnp.dot(x, p["w_g1"]), p["w_g2"]).astype(f32))
                # padding: no decay, no update
                log_decay = jnp.where(valid[:, :, None, None], log_decay, 0.0)
                beta = jnp.where(valid[:, :, None], beta, 0.0)
            if s == 1:
                with jax.named_scope("kda_step"):
                    state0 = jnp.where(old[:, None, None, None], state_in, 0.0)
                    step_in = (
                        q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0], beta[:, 0])
                    if live is not None:  # a fresh row is zeroed in the walk
                        out, state1 = step_live_rows(
                            delta_rule_step, state_in, live, old, step_in)
                    else:
                        out, state1 = delta_rule_step(*step_in, state0)
                    out = out[:, None]
            else:
                with jax.named_scope("kda_scan"):
                    state0 = jnp.where(old[:, None, None, None], state_in, 0.0)
                    out, state1 = delta_rule_chunked(
                        q, k, v, log_decay, beta, state0)
            with jax.named_scope("kda_out"):
                out = out * jax.lax.rsqrt(
                    jnp.mean(out * out, -1, keepdims=True) + self.rms_norm_eps)
                out = out * p["o_norm"].astype(f32)
                out = (out.reshape(b, s, h * d) * gate).astype(self.dtype)
                y = jnp.dot(out, p["w_o"])
        return y, state1, conv1
