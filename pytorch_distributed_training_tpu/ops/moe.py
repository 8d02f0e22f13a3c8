"""Mixture-of-Experts MLP with top-k routing and expert parallelism.

The reference has no MoE anywhere (SURVEY.md §2.4 lists expert parallelism
as absent) — this is a beyond-parity capability, built the idiomatic
XLA/GSPMD way (the GShard/Switch formulation): routing is expressed as
dense one-hot dispatch/combine einsums over a fixed per-expert capacity,
so the whole layer is static-shaped matmul work the MXU can tile — no
data-dependent gather/scatter, no dynamic shapes, nothing XLA cannot
partition.

Tokens are routed in GROUPS (GShard's key memory trick): each leading
batch row is one group, capacity is per group per expert
(``C = ceil(capacity_factor * k * S / E)`` for group size ``S``), and the
dispatch/combine tensors are ``[G, S, E, C]`` — linear in total tokens for
a fixed sequence length, where whole-batch routing would be quadratic
(the r2 code-review caught exactly that: at batch 64 x seq 2048 a global
capacity makes dispatch ~1e14 elements; per-group it is ~5e9 bf16-able
and shards over the data axis).

Expert parallelism rides the existing ``model`` mesh axis: the expert
weights are stacked ``[E, ...]`` and sharded on their leading dim
(``parallel.tensor`` adds the spec rule), so under ``training.
tensor_parallelism: N`` the SPMD partitioner places ``E/N`` experts per
device and inserts the token all-to-alls around the expert einsums itself
— the scaling-book recipe, not hand-written collectives.

Routing semantics (standard Switch/Mixtral hybrid, all documented here
because they are the part reviewers argue about):
  - router logits + softmax in float32 regardless of compute dtype
    (router numerics drive a discrete choice; bf16 ties flip experts),
  - top-k gates renormalized to sum to 1 over the chosen k (Mixtral
    convention) — EXCEPT k=1, which keeps the raw top-1 probability as the
    gate (Switch convention; renormalizing a single gate to 1.0 would zero
    the router's task-loss gradient),
  - slots fill SLOT-major within each group with slot-0 (primary expert)
    priority; tokens over capacity are DROPPED for that expert — their
    combine weight is 0, so with the transformer's residual connection
    they pass through unchanged (GShard behavior),
  - aux load-balancing loss (Switch eq. 4): ``E * sum_e f_e * P_e`` over
    ALL tokens (not per group — f and P are per-token statistics, so the
    global form is exact and group-count independent), where ``f_e`` is
    the fraction of tokens whose top-1 choice is expert ``e`` and ``P_e``
    the mean router probability; sown (already weighted by ``aux_weight``)
    into the ``intermediates`` collection under ``moe_aux`` — the train
    step adds every ``moe_aux`` entry to the objective (engine/tp_steps).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = ["MoEMLP", "DroplessMoE", "grouped_matmul", "in_token_chunks", "swiglu"]


class MoEMLP(nn.Module):
    """Drop-in MoE replacement for ``models.vit.MLP`` (same gelu two-layer
    experts, same ``[G, S, d] -> [G, S, out]`` contract; each leading-dim
    row is one routing group)."""

    num_experts: int
    top_k: int
    capacity_factor: float
    hidden: int
    out: int
    aux_weight: float = 0.01
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        if x.ndim != 3:
            raise ValueError(
                f"MoEMLP expects [groups, group_size, d] inputs, got {x.shape}"
            )
        g, s, d = x.shape
        E, k = self.num_experts, self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"top_k ({k}) must be in [1, num_experts={E}]")

        # ---- routing (f32) ------------------------------------------------
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # [g, s, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [g, s, k]
        if k > 1:
            gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        # k == 1 keeps the RAW probability as the gate (Switch): renormalizing
        # would collapse it to exactly 1.0 and cut the router off from the
        # task-loss gradient entirely (r2 code-review finding — the router
        # would then train on the aux loss alone)

        cap = max(1, int(math.ceil(self.capacity_factor * k * s / E)))
        # slot-major fill within each group: every token's primary (slot-0)
        # choice claims buffer positions before any secondary choice does,
        # so capacity pressure drops low-gate assignments first
        oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [g, s, k, E]
        slot_major = jnp.swapaxes(oh, 1, 2).reshape(g, k * s, E)
        pos = jnp.cumsum(slot_major, axis=1) * slot_major - 1  # [g, k*s, E]
        keep = (pos >= 0) & (pos < cap)
        disp_flat = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
        disp = jnp.swapaxes(
            disp_flat.reshape(g, k, s, E, cap), 1, 2
        )  # [g, s, k, E, cap], 0/1, disjoint slots
        dispatch = jnp.sum(disp, axis=2)  # [g, s, E, cap]
        combine = jnp.sum(disp * gate_vals[:, :, :, None, None], axis=2)

        # ---- aux load-balancing loss (Switch eq. 4, global over tokens) ---
        flat_probs = probs.reshape(-1, E)
        top1 = jax.nn.one_hot(gate_idx[:, :, 0].reshape(-1), E, dtype=jnp.float32)
        aux = E * jnp.sum(top1.mean(axis=0) * flat_probs.mean(axis=0))
        self.sow("intermediates", "moe_aux", self.aux_weight * aux)

        # ---- expert computation (stacked [E, ...] params) -----------------
        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (E, d, self.hidden), jnp.float32
        )
        bi = self.param("bi", nn.initializers.zeros_init(), (E, self.hidden), jnp.float32)
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (E, self.hidden, self.out), jnp.float32
        )
        bo = self.param("bo", nn.initializers.zeros_init(), (E, self.out), jnp.float32)

        dt = self.dtype
        xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(dt), x.astype(dt))
        h = nn.gelu(
            jnp.einsum("gecd,edh->gech", xe, wi.astype(dt))
            + bi[None, :, None, :].astype(dt)
        )
        ye = (
            jnp.einsum("gech,ehd->gecd", h, wo.astype(dt))
            + bo[None, :, None, :].astype(dt)
        )
        # bias on empty capacity slots is harmless: their combine weight is 0
        return jnp.einsum("gsec,gecd->gsd", combine.astype(dt), ye)


# --------------------------------------------------------------------------
# Dropless experts (serving): every routed token is computed.  Beside the
# one-hot capacity layer above, not inside it: that one is static-shaped
# einsum work for the SPMD partitioner and drops what overflows; this one
# sorts the token-expert pairs by expert and runs ONE grouped product a
# projection over the experts it holds, so its cost follows the pairs and
# the weights it reads follow the experts that got a token.

# rows of the sorted pairs a grouped product's tile takes (the rows are
# padded up to a multiple of it)
GMM_ROW_TILE = 128


def _gmm_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """(rows, contraction, columns) of one tile of the Pallas grouped
    product: whole contraction where it fits the fast memory beside a
    column tile, so that an expert's weights pass through once."""
    tn = next(t for t in (512, 256, 128) if n % t == 0 or t == 128)
    def fits(t):
        return k * t * 2 <= (2 << 20) and k % 128 == 0

    if not fits(tn) and k % 512:
        # a contraction that 512 does not divide (2,688): narrower columns
        # that let it through whole, rather than a ragged last piece
        tn = next((t for t in (256, 128) if t < tn and n % t == 0 and fits(t)), tn)
    tk = k if fits(tn) else 512
    return GMM_ROW_TILE, tk, tn


def grouped_matmul(lhs, rhs, group_sizes, out_dtype):
    """``lhs[rows of group g] @ rhs[g]`` for every group: ``lhs [m, k]``
    sorted by group, ``rhs [G, k, n]``, ``group_sizes [G]`` int32 whose sum
    may fall short of ``m`` (the rows past it belong to no group and come
    back undefined: the caller masks them).  On a TPU the megablox Pallas
    kernel (``moe_gmm`` in a trace); elsewhere ``lax.ragged_dot``."""
    from .flash_attention import flash_enabled

    with jax.named_scope("moe_gmm"):
        if not flash_enabled():
            return jax.lax.ragged_dot(
                lhs, rhs, group_sizes, preferred_element_type=out_dtype
            )
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=out_dtype,
            tiling=_gmm_tiling(lhs.shape[1], rhs.shape[2]),
        )


def in_token_chunks(fn, chunk: int, *arrays):
    """``fn`` over pieces of ``chunk`` leading rows of ``arrays`` (padded
    with zeros to a whole number of pieces), one piece at a time
    (``lax.map``): a long call holds one piece's temporaries, not all.
    Returns ``fn``'s outputs stacked ``[pieces, ...]``."""
    pad = -arrays[0].shape[0] % chunk
    pieces = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, chunk) + a.shape[1:])
        for a in arrays
    )
    return jax.lax.map(lambda piece: fn(*piece), pieces)


def _gated(h, dtype):
    """``silu(gate) * up`` of ``h = [gate | up]``, in float32."""
    half = h.shape[-1] // 2
    gate, up = h[..., :half].astype(jnp.float32), h[..., half:].astype(jnp.float32)
    return (jax.nn.silu(gate) * up).astype(dtype)


def swiglu(x, gate_up, down):
    """``(silu(x W_gate) * x W_up) W_down`` with gate and up side by side
    in one tensor, the gate first."""
    return jnp.dot(_gated(jnp.dot(x, gate_up), x.dtype), down)


def _relu2(h, dtype):
    """``relu(h)^2``, in float32."""
    return jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(dtype)


# an expert's activation: what it does to the output of its first product
# (``swiglu``: that product is ``[gate | up]``, twice the expert's width)
_ACTIVATIONS = {"swiglu": (_gated, 2), "relu2": (_relu2, 1)}


class DroplessMoE(nn.Module):
    """Top-k routed experts beside a shared expert, no capacity:
    ``y = sum_k s_k Expert_{i_k}(x) + Shared(x)`` over tokens ``x [N, dim]``.

    The router scores ALL ``num_experts`` in float32 (from the float32-cast
    input) and takes ``top_k`` of them.  ``scoring="softmax"``: the scores
    are a softmax and the ``top_k`` largest are chosen.  ``"sigmoid"``: each
    score is a sigmoid of its own logit, and the choice is made on ``score +
    e_score_correction_bias`` (a float32 vector a layer, learned to balance
    load): the bias moves the CHOICE and never the gate.  Either way the
    gates are the scores themselves at the chosen (``norm_topk_prob``
    renormalises them over the chosen k) times ``routed_scaling_factor``,
    applied in the combine.

    ``activation="swiglu"``: an expert is ``(silu(x W_gate) * x W_up)
    W_down``, gate and up side by side in ``w_gate_up``.  ``"relu2"``: an
    expert is NOT gated, ``relu(x W_up)^2 W_down``, one up product
    (``w_up``).  The shared expert has the same activation.

    ``latent = l > 0``: the routed experts live in a latent of width ``l``
    between a shared down-projection and a shared up-projection, ``r =
    (sum_k s_k Expert_{i_k}(x latent_down)) latent_up``; the router and the
    shared expert read the full width.  The up-projection is linear and has
    no bias, so the shares' routed parts still add up.

    ``experts_held = (first, count)`` tells the layer which experts live
    here (default: all).  It still routes over all of them and returns only
    the held experts' part of the sum — one chip's share of an expert-
    parallel layer, run without its exchange.  The shared expert is what
    every share computes alike: :meth:`routed_part` and :meth:`shared_part`
    give the two halves apart so that shares can be added up with it counted
    once; ``__call__`` is their sum.

    Parameters (``dtype``), with ``d = latent or dim``: ``router [dim, E]``,
    ``w_gate_up [held, d, 2h]`` (gate first; ``relu2``: ``w_up [held, d,
    h]``), ``w_down [held, h, d]``; with ``shared_hidden > 0``
    ``shared_gate_up [dim, 2s]`` (``relu2``: ``shared_up [dim, s]``),
    ``shared_down [s, dim]``; with ``latent`` ``latent_down [dim, l]``,
    ``latent_up [l, dim]``; with ``scoring="sigmoid"``
    ``e_score_correction_bias [E]`` (float32).
    """

    dim: int
    num_experts: int
    top_k: int
    hidden: int
    shared_hidden: int = 0
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    # tokens routed and computed in one piece; a longer call runs in pieces
    # of this many (a 32 x 2048 prefill would otherwise hold its six-fold
    # copy of the activations at once)
    token_chunk: int = 8192
    scoring: str = "softmax"
    activation: str = "swiglu"
    latent: int = 0

    @property
    def _held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def setup(self):
        E, k = self.num_experts, self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"top_k ({k}) must be in [1, num_experts={E}]")
        first, held = self._held
        if not (0 <= first and held >= 1 and first + held <= E):
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{E} experts"
            )
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r} is not softmax or sigmoid")
        if self.activation not in _ACTIVATIONS:
            raise ValueError(
                f"activation {self.activation!r} is not one of {sorted(_ACTIVATIONS)}")
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
        wide = _ACTIVATIONS[self.activation][1]
        up = "w_gate_up" if wide == 2 else "w_up"
        width = self.latent or self.dim
        self.router = self.param("router", init, (self.dim, E), self.dtype)
        if self.scoring == "sigmoid":
            self.correction_bias = self.param(
                "e_score_correction_bias", nn.initializers.zeros, (E,), jnp.float32)
        self.w_first = self.param(up, init, (held, width, wide * self.hidden), self.dtype)
        self.w_down = self.param(
            "w_down", init, (held, self.hidden, width), self.dtype)
        if self.latent:
            self.latent_down = self.param(
                "latent_down", init, (self.dim, self.latent), self.dtype)
            self.latent_up = self.param(
                "latent_up", init, (self.latent, self.dim), self.dtype)
        if self.shared_hidden:
            self.shared_first = self.param(
                "shared_" + up[2:], init, (self.dim, wide * self.shared_hidden),
                self.dtype)
            self.shared_down = self.param(
                "shared_down", init, (self.shared_hidden, self.dim), self.dtype)

    def __call__(self, x, token_mask=None):
        """``(y [N, dim], group_sizes [held])``: the layer's output and how
        many of this call's token-expert pairs each held expert computed."""
        routed, sizes = self.routed_part(x, token_mask)
        return routed + self.shared_part(x), sizes

    def shared_part(self, x):
        if not self.shared_hidden:
            return jnp.zeros_like(x)
        with jax.named_scope("moe_shared"):
            act = _ACTIVATIONS[self.activation][0]
            return jnp.dot(act(jnp.dot(x, self.shared_first), x.dtype), self.shared_down)

    def routed_part(self, x, token_mask=None):
        """The held experts' part of the gated sum.  ``token_mask [N]``
        (False = padding) keeps a token out of the routing altogether: it is
        neither computed nor counted."""
        n = x.shape[0]
        if token_mask is None:
            token_mask = jnp.ones((n,), bool)
        if n <= self.token_chunk:
            y, sizes = self._routed(x, token_mask)
        else:
            ys, sizes = in_token_chunks(self._routed, self.token_chunk, x, token_mask)
            y, sizes = ys.reshape(-1, ys.shape[-1])[:n], sizes.sum(axis=0)
        if self.latent:
            with jax.named_scope("moe_latent_up"):
                y = jnp.dot(y, self.latent_up)
        return y, sizes

    def _routed(self, x, token_mask):
        n, k = x.shape[0], self.top_k
        first, held = self._held
        rows_in = x
        if self.latent:
            # only the dispatched rows are latent: the router reads x whole
            with jax.named_scope("moe_latent_down"):
                rows_in = jnp.dot(x, self.latent_down)
        with jax.named_scope("moe_route"):
            logits = jnp.dot(
                x.astype(jnp.float32), self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            if self.scoring == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                _, experts = jax.lax.top_k(scores + self.correction_bias, k)
                gates = jnp.take_along_axis(scores, experts, axis=-1)
            else:
                scores = jax.nn.softmax(logits, axis=-1)
                gates, experts = jax.lax.top_k(scores, k)  # [n, k]
            if self.norm_topk_prob:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
            gates = gates * self.routed_scaling_factor
            # the pairs (token, expert) of this share, sorted by expert; a
            # pair whose expert lives elsewhere, or whose token is padding,
            # sorts behind every group and is never computed
            local = experts.reshape(-1) - first
            mine = (local >= 0) & (local < held) & jnp.repeat(token_mask, k)
            group = jnp.where(mine, local, held).astype(jnp.int32)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
            rows = n * k
            padded = -(-rows // GMM_ROW_TILE) * GMM_ROW_TILE
            token_of = jnp.pad(order // k, (0, padded - rows))
            lhs = rows_in[token_of]  # [padded, dim or latent]
        act = _ACTIVATIONS[self.activation][0]
        h = grouped_matmul(lhs, self.w_first, sizes, self.dtype)
        out = grouped_matmul(act(h, self.dtype), self.w_down, sizes, self.dtype)
        with jax.named_scope("moe_combine"):
            # back to (token, choice) order; the rows of no group are
            # undefined and are taken out by ``where``, never by a product
            pairs = out[jnp.argsort(order)].reshape(n, k, -1)
            y = jnp.sum(
                jnp.where(mine.reshape(n, k, 1), pairs.astype(jnp.float32), 0.0)
                * gates[..., None], axis=1,
            )
        return y.astype(x.dtype), sizes

