"""Mamba-2 (state-space duality, arXiv:2405.21060): a state-space mixer
whose cache is a fixed-size STATE a sequence, not a row a token.

The layer, ``H`` heads of ``P = head_dim`` channels, ``G`` groups of input
and output maps of ``N = state_size``, ``I = H P``, one token ``x_t`` at a
time:

  [z | xBC | dt] = x W_in                              (I | I + 2 G N | H)
  xBC_t <- silu(causal depthwise conv of ``conv_size`` taps, with bias)
  [x | B | C] = xBC   (I | G N | G N);  head h reads group h // (H / G)
  D_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) D_t)   (ONE scalar a head)
  S_t = a_t S_{t-1} + D_t x_t B_t^T      ([P, N] a head, float32)
  y_t = S_t C_t + D x_t
  out = W_out ( RMSNorm over groups of I / G of (y * silu(z)) * w_norm )

Two forms share the parameters and the state.  :func:`ssd_step` is the
update as written, one position a row (decode; scope ``mamba_step``): the
state is read once and written once.  :func:`ssd_chunked` (prefill; scope
``mamba_scan``) takes ``chunk`` positions at a time.  The decay is a scalar
a head a position, so unlike a channel-wise decay (:mod:`.kda`) everything
inside a chunk is a matrix product: with ``A_i`` the running sum of the log
decays inside the chunk,

  y_i = exp(A_i) C_i S_start + sum_{j <= i} exp(A_i - A_j) (C_i . B_j) D_j x_j
  S_end = exp(A_C) S_start + sum_j exp(A_C - A_j) D_j x_j B_j^T

every decay formed as ``exp`` of a difference with a non-positive exponent
(float32), never as a quotient.  The products inside the chunks run for all
chunks at once; only the chunk-start states are carried, by ``lax.scan``.
Their operands are in the layer's ``dtype`` and they accumulate in float32
(as the public kernels do); the state itself is float32 throughout.  Plain
XLA, no Pallas kernel: a kernel is a ``perf_opt`` PR's to bring, measured by
``mamba_scan_roofline_pct`` and ``mamba_step_roofline_pct`` (PERF.md).

The cache, in the ``"cache"`` collection beside the paged pool's leaves but
addressed by SLOT, not by block table: ``mamba_state [slots, H, P, N]``
float32 and ``mamba_conv [slots, conv_size - 1, I + 2 G N]`` (the last rows
of ``xBC`` before the convolution).  The contract is :mod:`.kda`'s: a call
names each batch row's slot in ``state_rows [B]`` (-1 = padding: read as
slot 0, written nowhere); a row whose first position is 0 starts from a zero
state, so a slot is never cleared; ``rows_are_slots=True`` is the scheduler's
fixed-width decode step, whose row ``i`` IS slot ``i`` and ``state_rows``
only says which rows live, and which updates the state leaf where it lies,
the live rows only (:func:`.state_rows.step_live_rows`); padding positions
(-1, at a row's end) change neither state nor convolution rows.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .attention import MAMBA_CONV, MAMBA_STATE
from .state_rows import step_live_rows

__all__ = ["Mamba2Mixer", "ssd_chunked", "ssd_step"]

# tokens a group of rows may hold in a long call (a 32 x 1024 prefill holds
# one group's temporaries, not all: about 1.5 GB at the published widths)
TOKEN_BUDGET = 4096


def ssd_step(x, b_in, c_out, dt, log_decay, state):
    """One position: ``x [B, H, P]``, ``b_in, c_out [B, G, N]``, ``dt,
    log_decay [B, H]``, ``state [B, H, P, N]``, all float32.  Returns ``(y
    [B, H, P], state)`` without the skip term; the state is read once and
    written once."""
    b, h, p = x.shape
    g = b_in.shape[1]
    grouped = state.reshape(b, g, h // g, p, -1)
    update = (dt[..., None] * x).reshape(b, g, h // g, p, 1) * b_in[:, :, None, None, :]
    grouped = jnp.exp(log_decay).reshape(b, g, h // g, 1, 1) * grouped + update
    y = jnp.sum(grouped * c_out[:, :, None, None, :], axis=-1)
    return y.reshape(b, h, p), grouped.reshape(state.shape)


def ssd_chunked(x, b_in, c_out, dt, log_decay, state, chunk: int = 128):
    """``S`` positions a row, ``chunk`` at a time: ``x [B, S, H, P]``,
    ``b_in, c_out [B, S, G, N]`` (these three in the dtype the products
    run in), ``dt, log_decay [B, S, H]`` and ``state [B, H, P, N]`` float32.
    Returns ``(y [B, S, H, P] float32, state)`` without the skip term,
    equal to ``S`` calls of :func:`ssd_step` up to rounding.  A position
    with ``dt = 0`` and ``log_decay = 0`` (padding) leaves the state as it
    was."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g  # heads that share a group's B and C
    pad = -s % chunk
    nc = (s + pad) // chunk
    f32 = jnp.float32

    def chunks(a):  # [B, S, ...] -> [B, nc, chunk, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((b, nc, chunk) + a.shape[2:])

    x, b_in, c_out, dt, log_decay = map(chunks, (x, b_in, c_out, dt, log_decay))
    run = jnp.cumsum(log_decay, axis=2)  # A_i [B, nc, C, H], <= 0
    total = run[:, :, -1]                # A_C [B, nc, H]
    # exp(A_i - A_j) for j <= i; the exponent is masked BEFORE the exp
    # (above the diagonal it is positive and may overflow)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = run[:, :, :, None, :] - run[:, :, None, :, :]  # [B, nc, i, j, H]
    decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", c_out, b_in, preferred_element_type=f32)
    mix = (
        decay.reshape(b, nc, chunk, chunk, g, r) * cb[..., None]
        * dt.reshape(b, nc, 1, chunk, g, r)
    ).astype(x.dtype)
    xg = x.reshape(b, nc, chunk, g, r, p)
    within = jnp.einsum("bcijgr,bcjgrp->bcigrp", mix, xg, preferred_element_type=f32)
    # what each chunk adds to the state by its own end
    to_end = (jnp.exp(total[:, :, None] - run) * dt).reshape(b, nc, chunk, g, r)
    added = jnp.einsum(
        "bcjgrp,bcjgn->bcgrpn", (xg * to_end[..., None]).astype(x.dtype), b_in,
        preferred_element_type=f32)

    def carry(start, piece):
        keep, add = piece  # [B, G, R], [B, G, R, P, N]
        return jnp.exp(keep)[..., None, None] * start + add, start

    state, starts = jax.lax.scan(
        carry, state.reshape(b, g, r, p, n),
        (jnp.moveaxis(total.reshape(b, nc, g, r), 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # [B, nc, G, R, P, N]
    across = jnp.einsum(
        "bcign,bcgrpn->bcigrp", c_out, starts.astype(x.dtype),
        preferred_element_type=f32,
    ) * jnp.exp(run).reshape(b, nc, chunk, g, r, 1)
    y = (within + across).reshape(b, s + pad, h, p)
    return y[:, :s], state.reshape(b, h, p, n)


def _a_log_init(key, shape, dtype):
    """``A_log = log U(1, 16)``: the public Mamba-2 initialisation."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(low: float, high: float, floor: float):
    """The inverse softplus of a step drawn log-uniform in ``(low, high)``,
    not below ``floor`` (the public initialisation; ``time_step_*``)."""
    def init(key, shape, dtype):
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(low), jnp.log(high)))
        step = jnp.maximum(step, floor)
        return jnp.log(jnp.expm1(step)).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
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
    n_groups: int
    state_size: int
    conv_size: int = 4
    chunk_size: int = 128
    conv_bias: bool = True
    rms_norm_eps: float = 1e-5
    dt_init: tuple = (0.001, 0.1, 1e-4)  # (time_step_min, _max, _floor)
    dtype: Any = jnp.float32
    decode: bool = False
    state_slots: int = 0

    @property
    def _widths(self):
        """``(I, conv channels)``."""
        inner = self.num_heads * self.head_dim
        return inner, inner + 2 * self.n_groups * self.state_size

    @nn.compact
    def __call__(self, x, positions=None, state_rows=None,
                 rows_are_slots: bool = False):
        b, s, dim = x.shape
        h, p, n, taps = self.num_heads, self.head_dim, self.state_size, self.conv_size
        if h % self.n_groups:
            raise ValueError(f"{h} heads are no multiple of {self.n_groups} groups")
        inner, ch = self._widths
        init = nn.initializers.lecun_normal()
        params = {
            "in_proj": self.param("in_proj", init, (dim, inner + ch + h), self.dtype),
            "conv_w": self.param(
                "conv_w", nn.initializers.normal(taps ** -0.5), (taps, ch), self.dtype),
            "dt_bias": self.param("dt_bias", _dt_bias_init(*self.dt_init), (h,), self.dtype),
            "A_log": self.param("A_log", _a_log_init, (h,), self.dtype),
            "D": self.param("D", nn.initializers.ones, (h,), self.dtype),
            "norm": self.param("norm", nn.initializers.ones, (inner,), self.dtype),
            "out_proj": self.param("out_proj", init, (inner, dim), self.dtype),
        }
        if self.conv_bias:
            params["conv_b"] = self.param(
                "conv_b", nn.initializers.zeros, (ch,), self.dtype)
        if not self.decode:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            state0 = jnp.zeros((b, h, p, n), jnp.float32)
            conv0 = jnp.zeros((b, taps - 1, ch), self.dtype)
            y, _, _ = self._rows(params, x, positions, state0, conv0, None)
            return y
        if self.state_slots < 1:
            raise ValueError(
                f"decode mode needs state_slots >= 1, got {self.state_slots}")
        if positions is None or state_rows is None:
            raise ValueError("decode mode needs positions and state_rows")
        slots = self.state_slots
        state = self.variable(
            "cache", MAMBA_STATE, jnp.zeros, (slots, h, p, n), jnp.float32)
        conv = self.variable(
            "cache", MAMBA_CONV, jnp.zeros, (slots, taps - 1, ch), self.dtype)
        if rows_are_slots:
            if s != 1 or b != slots:
                raise ValueError(
                    f"rows_are_slots is the decode step over all {slots} slots, "
                    f"one position a row; got {b} rows of {s} positions")
            # The leaves are read and written where they lie, no gathered
            # copy, and of the state only the rows ``state_rows`` says are
            # live.  A row that names another slot breaks the contract and
            # is answered with NaN, which the output guard of the serving
            # programs evicts: loud, not wrong (ops/kda.py).
            live = state_rows >= 0
            y, state.value, conv.value = self._rows(
                params, x, positions, state.value, conv.value, live)
            aligned = ~live | (state_rows == jnp.arange(slots))
            return jnp.where(aligned[:, None, None], y, jnp.nan)
        read = jnp.clip(state_rows, 0, slots - 1)
        y, state1, conv1 = self._rows(
            params, x, positions, state.value[read], conv.value[read], None)
        # -1 (padding) is written nowhere: out of range, dropped
        write = jnp.where(state_rows >= 0, state_rows, slots)
        state.value = state.value.at[write].set(state1, mode="drop")
        conv.value = conv.value.at[write].set(conv1, mode="drop")
        return y

    def _rows(self, params, x, positions, state_in, conv_in, live):
        """The layer over all rows, a group of rows at a time where the
        call is long.  Each group's outputs are written over its own inputs
        (they have the inputs' shapes), so the loop carries the call's own
        arrays and makes no stacked copy: a stacked output starts as zeros
        that depend on nothing, and the compiler was seen to allocate every
        layer's at the program's start (0.6 GB a layer at 32 x 1,024)."""
        b, s, _ = x.shape
        group = max(1, TOKEN_BUDGET // s)
        if s == 1 or b <= group:
            return self._layer(params, x, positions, state_in, conv_in, live)
        pad = -b % group

        def rows(a, fill=0):
            return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                           constant_values=fill)

        positions = rows(positions, -1)  # a padding row: no valid position

        def one_group(i, carried):
            def piece(a):
                return jax.lax.dynamic_slice_in_dim(a, i * group, group, axis=0)

            done = self._layer(params, piece(carried[0]), piece(positions),
                               piece(carried[1]), piece(carried[2]), None)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    whole, part.astype(whole.dtype), i * group, axis=0)
                for whole, part in zip(carried, done))

        y, state1, conv1 = jax.lax.fori_loop(
            0, (b + pad) // group, one_group,
            (rows(x), rows(state_in), rows(conv_in)))
        return y[:b], state1[:b], conv1[:b]

    def _layer(self, params, x, positions, state_in, conv_in, live):
        """One group of rows: ``state_in``, ``conv_in`` are what the rows'
        slots hold; a row whose first position is 0 starts a sequence and
        reads zeros instead.  ``live [B]`` (the aligned decode step): a row
        that is not live keeps what its slot held, and its state is not
        touched.  The state's read, update and write all lie under
        ``mamba_step`` / ``mamba_scan``."""
        b, s, _ = x.shape
        h, p, g, n = self.num_heads, self.head_dim, self.n_groups, self.state_size
        taps = self.conv_size
        inner, ch = self._widths
        f32 = jnp.float32
        valid = positions >= 0  # [B, S]; the valid tokens lead the row
        old = positions[:, 0] > 0
        with jax.named_scope("mamba"):
            with jax.named_scope("mamba_in"):
                proj = jnp.dot(x, params["in_proj"])  # [B, S, I + ch + H]
                z, pre, dt = jnp.split(proj, [inner, inner + ch], axis=-1)
                dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
                # padding: no step, so no decay and no update
                dt = jnp.where(valid[..., None], dt, 0.0)
                log_decay = -jnp.exp(params["A_log"].astype(f32)) * dt
            with jax.named_scope("mamba_conv"):
                conv0 = jnp.where(old[:, None, None], conv_in, 0)
                cat = jnp.concatenate([conv0.astype(pre.dtype), pre], axis=1)
                w = params["conv_w"].astype(f32)
                mixed = sum(w[j] * cat[:, j:j + s].astype(f32) for j in range(taps))
                if self.conv_bias:
                    mixed = mixed + params["conv_b"].astype(f32)
                xbc = jax.nn.silu(mixed)
                # the rows a later call's convolution needs: the last
                # ``taps - 1`` of what was there and the valid new ones
                n_valid = jnp.sum(valid, axis=1)
                at = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
                conv1 = jnp.take_along_axis(cat, at[:, :, None], axis=1)
                if live is not None:
                    conv1 = jnp.where(live[:, None, None], conv1, conv_in)
                xs = xbc[..., :inner].reshape(b, s, h, p)
                b_in = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
                c_out = xbc[..., inner + g * n:].reshape(b, s, g, n)
            state0 = jnp.where(old[:, None, None, None], state_in, 0.0)
            if s == 1:
                with jax.named_scope("mamba_step"):
                    step_in = (xs[:, 0], b_in[:, 0], c_out[:, 0], dt[:, 0],
                               log_decay[:, 0])
                    if live is not None:  # a fresh row is zeroed in the walk
                        y, state1 = step_live_rows(
                            ssd_step, state_in, live, old, step_in)
                    else:
                        y, state1 = ssd_step(*step_in, state0)
                    y = y[:, None]
            else:
                with jax.named_scope("mamba_scan"):
                    y, state1 = ssd_chunked(
                        xs.astype(self.dtype), b_in.astype(self.dtype),
                        c_out.astype(self.dtype), dt, log_decay, state0,
                        chunk=self.chunk_size)
            with jax.named_scope("mamba_out"):
                y = y + params["D"].astype(f32)[:, None] * xs
                y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(f32))
                y = y.reshape(b, s, g, inner // g)
                y = y * jax.lax.rsqrt(
                    jnp.mean(y * y, -1, keepdims=True) + self.rms_norm_eps)
                y = y.reshape(b, s, inner) * params["norm"].astype(f32)
                out = jnp.dot(y.astype(self.dtype), params["out_proj"])
        return out, state1, conv1
