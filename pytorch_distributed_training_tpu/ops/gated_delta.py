"""Gated DeltaNet (arXiv:2412.06464): the delta rule of :mod:`.kda` with ONE
decay a head and a rectangular state.

The layer, ``H`` heads of ``d_k`` keys and ``d_v`` values (the keys of the
public gated-delta-net layer: ``linear_num_key_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``), one token ``x_t``
at a time:

  q~, k~ = x W_q, x W_k  (H d_k each);  v~ = x W_v  (H d_v)   (one matrix, ``w_qkv``)
  q, k, v    = silu(causal depthwise conv of ``conv_size`` taps, no bias)
  q^ = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)
  a_t = -exp(A_log_h) * softplus(w_a,h . x_t + dt_bias_h)    (log decay, ONE a head)
  b_t = sigmoid(w_b,h . x_t)  (x 2 with ``allow_neg_eigval``)  (one matrix, ``w_ab``)
  S' = exp(a_t) S_{t-1};  S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T;  o_t = S_t^T q^_t
  y = W_o [ RMSNorm_{d_v}(o_t; w) * silu(W_z x_t) ]            (``W_z`` at full rank)

What it shares with :mod:`.kda`: the one-position update
(:func:`.kda.delta_rule_step`, which takes ``d_k`` and ``d_v`` apart and a
decay that broadcasts over the keys: here ``[B, H, 1]``), the walk of the
live rows (:func:`.state_rows.step_live_rows`) and the cache contract: two
``[slots, ...]`` leaves beside the paged pool, ``gdn_state [slots, H, d_k,
d_v]`` float32 and ``gdn_conv [slots, conv_size - 1, H (2 d_k + d_v)]``,
addressed by ``state_rows [B]`` (-1 = padding) or, in the scheduler's decode
step, row ``i`` IS slot ``i`` (``rows_are_slots``); a row whose first
position is 0 starts from zeros; padding positions change nothing.

What it cannot share is the chunked scan.  :func:`.kda.delta_rule_chunked`
forms ``exp(A_i - A_j)`` a CHANNEL, a ``[B, H, C, C, d_k]`` array inside
three-operand contractions that run on the vector unit.  With one decay a
head the factor leaves the contraction
(:func:`delta_rule_chunked_scalar`): ``(k k^T) * exp(A_i - A_j)`` is a
``[C, d_k] x [d_k, C]`` product on the matrix unit under a ``[C, C]`` mask,
the same for ``q k^T``, and the start state's part and the carry take one
factor a position; and the chunk's triangular solve, which does not need
the state, is done for all chunks at once outside the scan.  Plain XLA; a kernel is a ``perf_opt`` PR's to bring,
measured by ``gdn_scan_roofline_pct`` (PERF.md).

The state is kept ``[.., d_k, d_v]`` as the equations have it.  At the
published 96 x 192 neither is a whole 128-lane tile: the device pads the
192 lanes of a float32 row to 256 (PERF.md section 5 gives the bytes the
compiler reports and the step's measured rate).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .attention import GDN_CONV, GDN_STATE
from .kda import _a_log_init, delta_rule_step
from .state_rows import step_live_rows

__all__ = ["GatedDeltaNet", "delta_rule_chunked_scalar"]

_HIGHEST = jax.lax.Precision.HIGHEST
# positions a step of the prefill scan takes at once
CHUNK = 64
# tokens a group of rows may hold in a long call
TOKEN_BUDGET = 4096


def _chunk_parts(q, k, v, g, beta):
    """What a chunk's update needs that does NOT depend on the state, for
    every chunk at once: ``q, k [..., C, d_k]``, ``v [..., C, d_v]``, ``g,
    beta [..., C]``.  With ``A_i`` the running log decay inside the chunk
    and ``T = (I + diag(beta) strict((k k^T) * exp(A_i - A_j)))^-1``, the
    chunk's deltas are ``U = T (beta v) - T (beta k exp(A)) S`` for its
    start state ``S``: the triangular solve, the slow serial part on this
    device, is done once over all chunks and not inside the scan."""
    c = q.shape[-2]
    a = jnp.cumsum(g, axis=-1)  # A_i: log decay from the chunk's start to i
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(A_i - A_j) for j <= i; the exponent is masked BEFORE the exp
    # (above the diagonal it is positive and may overflow)
    decay = jnp.exp(jnp.where(lower, a[..., :, None] - a[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HIGHEST) * decay
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=_HIGHEST) * decay
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    system = jnp.where(strict, beta[..., None] * kk, 0.0) + jnp.eye(c)
    seen = jnp.exp(a)[..., None]  # what position i sees of the start state
    # (I + diag(beta) strict(kk)) [U0 | W] = beta [v | k exp(A)]
    rhs = beta[..., None] * jnp.concatenate([v, k * seen], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    u0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    total = a[..., -1:]  # A_C
    carry = k * jnp.exp(total - a)[..., None]  # what of k_i reaches the end
    return u0, w, q * seen, qk, carry, jnp.exp(total)[..., None]


def _chunk_update(state, parts):
    """One chunk against its start state ``[B, H, d_k, d_v]``: four
    products, no solve."""
    u0, w, q_seen, qk, carry, shrink = parts
    u = u0 - jnp.einsum("bhck,bhkv->bhcv", w, state, precision=_HIGHEST)
    out = (
        jnp.einsum("bhck,bhkv->bhcv", q_seen, state, precision=_HIGHEST)
        + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HIGHEST)
    )
    state = state * shrink + jnp.einsum(
        "bhck,bhcv->bhkv", carry, u, precision=_HIGHEST)
    return state, out


def delta_rule_chunked_scalar(q, k, v, log_decay, beta, state, chunk: int = CHUNK):
    """``S`` positions a row, ``chunk`` at a time, ONE decay a head: ``q, k
    [B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``log_decay, beta [B, S, H]``,
    ``state [B, H, d_k, d_v]``, float32.  Returns ``(o [B, S, H, d_v],
    state)``, equal to ``S`` calls of :func:`.kda.delta_rule_step` (and to
    :func:`.kda.delta_rule_chunked` fed the decay on every channel) up to
    float32 rounding.  A position with ``beta = 0`` and ``log_decay = 0``
    (padding) leaves the state as it was.  Everything inside a chunk that
    the state does not enter is computed for all chunks at once
    (:func:`_chunk_parts`); ``lax.scan`` carries the state through the
    chunks' four products."""
    b, s, h, _ = q.shape
    pad = -s % chunk

    def chunks(x):  # [B, S, H, ...] -> [n, B, H, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, (s + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    state, out = jax.lax.scan(_chunk_update, state, _chunk_parts(
        chunks(q), chunks(k), chunks(v), chunks(log_decay), chunks(beta)))
    # [n, B, H, C, d_v] -> [B, S, H, d_v]
    out = jnp.moveaxis(out, 0, 1).swapaxes(2, 3).reshape(b, s + pad, h, -1)
    return out[:, :s], state


class GatedDeltaNet(nn.Module):
    """The layer above over ``x [B, S, dim]``.  ``decode=False``: every row
    starts from a zero state and nothing is kept (the full forward).
    ``decode=True``: state and convolution rows are read from and written to
    the slots ``state_rows`` names, ``positions [B, S]`` (-1 = padding, at a
    row's end) say which tokens count; ``S == 1`` takes the one-step form,
    longer calls the chunked one, in groups of rows of at most
    ``TOKEN_BUDGET`` tokens.  ``rows_are_slots`` (static): the caller's
    fixed-width decode step, whose row ``i`` is slot ``i``."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    decode: bool = False
    state_slots: int = 0

    @nn.compact
    def __call__(self, x, positions=None, state_rows=None,
                 rows_are_slots: bool = False):
        b, s, dim = x.shape
        h, dk, dv, taps = self.num_heads, self.key_dim, self.value_dim, self.conv_size
        ch = h * (2 * dk + dv)
        init = nn.initializers.lecun_normal()
        p = {
            "w_qkv": self.param("w_qkv", init, (dim, ch), self.dtype),
            "conv_w": self.param(
                "conv_w", nn.initializers.normal(taps ** -0.5), (taps, ch), self.dtype),
            "w_ab": self.param("w_ab", init, (dim, 2 * h), self.dtype),
            "dt_bias": self.param("dt_bias", nn.initializers.zeros, (h,), self.dtype),
            "A_log": self.param("A_log", _a_log_init, (h,), self.dtype),
            "w_z": self.param("w_z", init, (dim, h * dv), self.dtype),
            "o_norm": self.param("o_norm", nn.initializers.ones, (dv,), self.dtype),
            "w_o": self.param("w_o", init, (h * dv, dim), self.dtype),
        }
        if not self.decode:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
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
            "cache", GDN_STATE, jnp.zeros, (slots, h, dk, dv), jnp.float32)
        conv = self.variable(
            "cache", GDN_CONV, jnp.zeros, (slots, taps - 1, ch), self.dtype)
        if rows_are_slots:
            if s != 1 or b != slots:
                raise ValueError(
                    f"rows_are_slots is the decode step over all {slots} slots, "
                    f"one position a row; got {b} rows of {s} positions")
            # the leaves are read and written where they lie, the state's
            # live rows only; a row that names another slot is answered
            # with NaN, which the serving programs' output guard evicts
            # (ops/kda.py)
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
        call is long; each group's outputs are written over its own inputs
        (ops/mamba2.py::_rows: no stacked copy a layer)."""
        b, s, _ = x.shape
        group = max(1, TOKEN_BUDGET // s)
        if s == 1 or b <= group:
            return self._layer(p, x, positions, state_in, conv_in, live)
        pad = -b % group

        def rows(a, fill=0):
            return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                           constant_values=fill)

        positions = rows(positions, -1)  # a padding row: no valid position

        def one_group(i, carried):
            def piece(a):
                return jax.lax.dynamic_slice_in_dim(a, i * group, group, axis=0)

            done = self._layer(p, piece(carried[0]), piece(positions),
                               piece(carried[1]), piece(carried[2]), None)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    whole, part.astype(whole.dtype), i * group, axis=0)
                for whole, part in zip(carried, done))

        y, state1, conv1 = jax.lax.fori_loop(
            0, (b + pad) // group, one_group,
            (rows(x), rows(state_in), rows(conv_in)))
        return y[:b], state1[:b], conv1[:b]

    def _layer(self, p, x, positions, state_in, conv_in, live):
        """One group of rows: ``state_in``, ``conv_in`` are what the rows'
        slots hold; a row whose first position is 0 starts a sequence and
        reads zeros instead.  ``live [B]`` (the aligned decode step): a row
        that is not live keeps what its slot held, and its state is not
        touched.  The state's read, update and write all lie under
        ``gdn_step`` / ``gdn_scan``."""
        b, s, _ = x.shape
        h, dk, dv, taps = self.num_heads, self.key_dim, self.value_dim, self.conv_size
        f32 = jnp.float32
        valid = positions >= 0  # [B, S]; the valid tokens lead the row
        old = positions[:, 0] > 0
        with jax.named_scope("gdn"):
            with jax.named_scope("gdn_conv"):
                conv0 = jnp.where(old[:, None, None], conv_in, 0)
                pre = jnp.dot(x, p["w_qkv"])  # [B, S, H (2 d_k + d_v)]
                cat = jnp.concatenate([conv0.astype(pre.dtype), pre], axis=1)
                w = p["conv_w"].astype(f32)
                mixed = jax.nn.silu(sum(
                    w[j] * cat[:, j:j + s].astype(f32) for j in range(taps)))
                q, k, v = jnp.split(mixed, [h * dk, 2 * h * dk], axis=-1)
                q, k = q.reshape(b, s, h, dk), k.reshape(b, s, h, dk)
                v = v.reshape(b, s, h, dv)
                q = q * jax.lax.rsqrt(
                    jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
                k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
                # the rows a later call's convolution needs: the last
                # ``taps - 1`` of what was there and the valid new ones
                n_valid = jnp.sum(valid, axis=1)
                at = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
                conv1 = jnp.take_along_axis(cat, at[:, :, None], axis=1)
                if live is not None:
                    conv1 = jnp.where(live[:, None, None], conv1, conv_in)
            with jax.named_scope("gdn_gate"):
                ab = jnp.dot(x, p["w_ab"], preferred_element_type=f32)
                rate = jax.nn.softplus(ab[..., :h] + p["dt_bias"].astype(f32))
                log_decay = -jnp.exp(p["A_log"].astype(f32)) * rate  # [B, S, H]
                beta = jax.nn.sigmoid(ab[..., h:])
                if self.allow_neg_eigval:
                    beta = beta * 2.0
                gate = jax.nn.silu(jnp.dot(x, p["w_z"]).astype(f32))
                # padding: no decay, no update
                log_decay = jnp.where(valid[..., None], log_decay, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
            if s == 1:
                with jax.named_scope("gdn_step"):
                    step_in = (q[:, 0], k[:, 0], v[:, 0],
                               log_decay[:, 0, :, None], beta[:, 0])
                    if live is not None:  # a fresh row is zeroed in the walk
                        out, state1 = step_live_rows(
                            delta_rule_step, state_in, live, old, step_in)
                    else:
                        state0 = jnp.where(old[:, None, None, None], state_in, 0.0)
                        out, state1 = delta_rule_step(*step_in, state0)
                    out = out[:, None]
            else:
                with jax.named_scope("gdn_scan"):
                    state0 = jnp.where(old[:, None, None, None], state_in, 0.0)
                    out, state1 = delta_rule_chunked_scalar(
                        q, k, v, log_decay, beta, state0)
            with jax.named_scope("gdn_out"):
                out = out * jax.lax.rsqrt(
                    jnp.mean(out * out, -1, keepdims=True) + self.rms_norm_eps)
                out = out * p["o_norm"].astype(f32)
                out = (out.reshape(b, s, h * dv) * gate).astype(self.dtype)
                y = jnp.dot(out, p["w_o"])
        return y, state1, conv1
