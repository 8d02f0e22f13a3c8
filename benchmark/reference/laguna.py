"""Plain reference of the Laguna decoder (``model_type: laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): window
and global softmax attention mixed, head counts that differ by layer, a
rotary term by layer kind, one output gate a head, dropless experts beside a
shared one after a dense first layer.  Written from the equations; imports
nothing of the program and takes nothing the program made: the weights come
from ``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``: no
cache, no batching, no ring.  Attention is a masked softmax over ALL keys of
the sequence, in blocks of query rows only so that 8,704 positions fit; the
window is a MASK (``i - window < j <= i``), it owes nothing to the program's
layout.  Every held expert is applied to every token under a mask of its
gates (one expert at a time, so that the float32 copy of its weights fits and
the loop compiles once).

The equations (config keys in brackets), layer ``l``:
  h = x + Attn_l(RMSNorm(x));  x' = h + FFN_l(RMSNorm(h))        (pre-norm)
  RMSNorm(x) = x * rsqrt(mean(x^2) + [rms_norm_eps]) * w;  no bias anywhere
  Attn_l, H_l = [num_attention_heads_per_layer][l] query heads,
       Hkv = [num_key_value_heads] K/V heads of hd = [head_dim]:
       q = x W_q -> [H_l, hd];  k, v = x W_k, x W_v -> [Hkv, hd]
       q, k = rot_l(q, p), rot_l(k, p) at position p
       query head h reads K/V head h // (H_l / Hkv)
       scores q_i . k_j / sqrt(hd) over the keys j <= i where
       [layer_types][l] is full_attention, over i - [sliding_window] < j <= i
       where it is sliding_attention ([sliding_window] keys with the query's
       own); softmax; o_h = P_h v
       g = sigmoid(x W_g), W_g hidden -> H_l: ONE number a head [gating]
       y = W_o [ g_h o_h ]_h
  rot_l, from [rope_parameters][layer_types[l]]: the first r = hd *
       [partial_rotary_factor] lanes of a head are rotated and the others
       pass unchanged; lane i pairs with lane i + r/2 (rotate_half):
       (a, b) -> (a cos - b sin, b cos + a sin), angle p * f_i.
       rope_type default: f_i = [rope_theta]^(-2i / r), cos and sin as they
       are.  rope_type yarn (public formula, :func:`yarn_frequencies`): with
       dim(n) = r ln([original_max_position_embeddings] / (2 pi n)) / (2 ln
       [rope_theta]), low = max(floor(dim([beta_fast])), 0), high =
       min(ceil(dim([beta_slow])), r - 1), ramp_i = clip((i - low) / (high -
       low), 0, 1): f_i = f_i / [factor] * ramp_i + f_i * (1 - ramp_i); cos
       and sin are multiplied by [attention_factor] (0.1 ln factor + 1) on
       the rotated lanes only.
  FFN_l where [mlp_layer_types][l] is dense:
       W_down(silu(W_gate x) * W_up x) of width [intermediate_size]
  FFN_l where it is sparse:
       r = softmax(x W_r) in float32 over all [num_experts]; the
       [num_experts_per_tok] largest; weights w_k = r_k / sum of the chosen,
       times [moe_routed_scaling_factor], on the experts' OUTPUT
       [moe_apply_router_weight_on_input false];
       y = sum_{chosen and held} w_k E_k(x) + Shared(x), E and Shared SwiGLU
       of [moe_intermediate_size] and [shared_expert_intermediate_size].  No
       capacity, no drop.  Only the HELD experts exist here
       (``experts_held``: this chip's share of an expert-parallel layer);
       what the absent ones would have added is left out, as in the program.
  after the last layer RMSNorm, then logits = x W_head (untied, no bias).

Assumed (no key of the config gives it; each is listed with its reason under
``assumed`` in the configuration file): the pre-norm block of the Qwen-MoE
lineage whose key names the config carries; no QK-norm; the window's
convention (the query's own key among the [sliding_window]); the gate a head
and sigmoid ([gating] true; the sibling config states "per-head", the
published parameter count leaves no room for a wider one; arXiv:2505.06708's
headwise form); rotate_half's pairing and the attention factor on cos and
sin (the HF convention); silu; softmax router scores renormalised over the
chosen (the sibling's norm_topk_prob; the config has no scoring_func); the
shared expert ungated.

Departures from the published description: none in the mathematics as read
above.  The published model runs in bfloat16 and rounds after every
operation; the reference keeps float32 throughout.

``mode`` chooses the arithmetic, for the controls only:
  ``f32``   the reference itself;
  ``bf16``  matmul operands rounded to bfloat16 (what the configuration states);
  ``int8``  matmul operands fake-quantised to int8 (per-row symmetric): the
            nearest precision below the one the configuration states.

Parameter layout ("reference layout"): ``tok_emb [V,D]``, ``head_w [D,V]``,
``norm_w [D]``, ``layers`` (a list, one dict a layer) and ``arch`` (the sizes
that no shape gives).  Every layer holds ``attn_norm [D]``, ``ffn_norm [D]``,
``wq [D,H_l*hd]``, ``wk``, ``wv [D,Hkv*hd]``, ``w_gate [D,H_l]``, ``wo
[H_l*hd,D]``; a dense layer ``m_gate``, ``m_up [D,I]``, ``m_down [I,D]``; a
sparse layer ``router [D,E]``, ``e_gate``, ``e_up [held,D,M]``, ``e_down
[held,M,D]``, ``s_gate``, ``s_up [D,Ms]``, ``s_down [Ms,D]``.  Every weight is
a bfloat16 array whose values were drawn in float32 and rounded once, so that
the program (which holds bfloat16) and the reference (which upcasts) start
from the same numbers.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "int8")
FULL, SLIDING = "full_attention", "sliding_attention"


class Arch(NamedTuple):
    kv_heads: int
    head_dim: int
    window: int
    top_k: int
    held_first: int
    held: int
    rms_eps: float
    routed_scaling: float
    pad_to: int
    query_block: int


class Rotary(NamedTuple):
    """One layer kind's rotary term: lanes rotated, the frequencies of the
    pairs, and what cos and sin are multiplied by."""
    lanes: int
    freq: Tuple[float, ...]
    amplitude: float


def yarn_frequencies(rope: dict, head_dim: int) -> Rotary:
    """The rotary term of one entry of ``rope_parameters``, the public YaRN
    formula written out (arXiv:2309.00071, as the HF implementation computes
    it)."""
    kind = rope.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"the reference does not write rope_type {kind!r}")
    lanes = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    freq = theta ** (-np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    if kind == "default":
        return Rotary(lanes, tuple(float(f) for f in freq.astype(np.float32)), 1.0)
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):  # the pair that turns this often over the original context
        return lanes * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), lanes - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(lanes // 2) - low) / (high - low), 0.0, 1.0)
    blended = freq / factor * ramp + freq * (1.0 - ramp)
    amplitude = rope.get("attention_factor")
    if amplitude is None:
        amplitude = 0.1 * math.log(factor) + 1.0
    return Rotary(
        lanes, tuple(float(f) for f in blended.astype(np.float32)), float(amplitude))


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys (they lie at the file's top level, under the names of the source;
    the experts' total and the share held are what ``serve.model`` runs)."""
    c = config
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False),
                      ("moe_apply_router_weight_on_input", False)):
        if c.get(key, want) != want:
            raise ValueError(f"the reference does not write {key}: {c[key]!r}")
    if c.get("gating", True) not in (True, "per-head"):
        raise ValueError(f"the reference writes a gate a head, not {c['gating']!r}")
    model = c.get("serve", {}).get("model", {})
    total = int(model.get("num_experts", c["num_experts"]))
    first, held = model.get("experts_held") or (0, total)
    if int(c["num_experts"]) != held:
        raise ValueError(
            f"the file's num_experts ({c['num_experts']}) counts the experts "
            f"held, but serve.model holds {held}")
    layers = int(c["num_hidden_layers"])
    kinds = tuple(c["layer_types"][:layers])
    if set(kinds) - {FULL, SLIDING}:
        raise ValueError(f"the reference does not write layer_types {kinds!r}")
    arch = Arch(
        kv_heads=int(c["num_key_value_heads"]), head_dim=int(c["head_dim"]),
        window=int(c["sliding_window"]), top_k=int(c["num_experts_per_tok"]),
        held_first=int(first), held=int(held), rms_eps=float(c["rms_norm_eps"]),
        routed_scaling=float(c["moe_routed_scaling_factor"]),
        # every sequence is padded to a multiple of this: a configuration
        # gives its longest (bucket + new tokens), so that ONE shape compiles
        pad_to=int(c.get("reference_pad_to", 256)),
        query_block=int(c.get("reference_query_block", 512)),
    )
    return {
        "H": int(c["num_attention_heads"]), "arch": arch, "V": int(c["vocab_size"]),
        "D": int(c["hidden_size"]), "L": layers, "I": int(c["intermediate_size"]),
        "M": int(c["moe_intermediate_size"]),
        "MS": int(c["shared_expert_intermediate_size"]), "E": total,
        "kinds": kinds,
        "heads": tuple(int(h) for h in c["num_attention_heads_per_layer"][:layers]),
        "sparse": tuple(kind == "sparse" for kind in c["mlp_layer_types"][:layers]),
        "rotary": {
            kind: yarn_frequencies(c["rope_parameters"][kind], arch.head_dim)
            for kind in set(kinds)
        },
        "router_std": float(c["assumed"]["router_logit_std"]),
        "gate_std": float(c["assumed"]["gate_logit_std"]),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=(
    "D", "I", "M", "MS", "E", "arch", "heads", "sparse"))
def _make_layer(key, router_std, gate_std, *, D, I, M, MS, E, arch, heads, sparse):
    k = iter(jax.random.split(key, 16))
    a = arch
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    hq, hkv = heads * a.head_dim, a.kv_heads * a.head_dim
    layer = {
        "attn_norm": ones((D,)), "ffn_norm": ones((D,)),
        "wq": _normal(next(k), (D, hq), D ** -0.5),
        "wk": _normal(next(k), (D, hkv), D ** -0.5),
        "wv": _normal(next(k), (D, hkv), D ** -0.5),
        # assumed: gate logits of standard deviation ``gate_std`` on a
        # unit-RMS input, so that g = sigmoid(.) spreads over (0.1, 0.9) and
        # a gate that is left out, or taken an element at a time, shows
        "w_gate": _normal(next(k), (D, heads), gate_std * D ** -0.5),
        "wo": _normal(next(k), (hq, D), hq ** -0.5),
    }
    if not sparse:
        layer.update(
            m_gate=_normal(next(k), (D, I), D ** -0.5),
            m_up=_normal(next(k), (D, I), D ** -0.5),
            m_down=_normal(next(k), (I, D), I ** -0.5),
        )
        return layer
    layer.update(
        # assumed: a router whose logits spread (standard deviation
        # ``router_std`` on a unit-RMS input), so that routing counts
        router=_normal(next(k), (D, E), router_std * D ** -0.5),
        e_gate=_normal(next(k), (a.held, D, M), D ** -0.5),
        e_up=_normal(next(k), (a.held, D, M), D ** -0.5),
        e_down=_normal(next(k), (a.held, M, D), M ** -0.5),
        s_gate=_normal(next(k), (D, MS), D ** -0.5),
        s_up=_normal(next(k), (D, MS), D ** -0.5),
        s_down=_normal(next(k), (MS, D), MS ** -0.5),
    )
    return layer


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, one jitted call a layer:
    embeddings N(0, 0.02), matrices N(0, 1/fan_in), norms 1, the router
    N(0, router_std^2/fan_in), the gate N(0, gate_std^2/fan_in), each drawn
    in float32 and rounded once to bfloat16."""
    key = seed_key(seed)
    arch = sizes["arch"]
    shape = {k: sizes[k] for k in ("D", "I", "M", "MS", "E")}
    layers = [
        _make_layer(jax.random.fold_in(key, i), sizes["router_std"],
                    sizes["gate_std"], arch=arch, heads=sizes["heads"][i],
                    sparse=sizes["sparse"][i], **shape)
        for i in range(sizes["L"])
    ]
    top = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    rotary = sizes["rotary"]
    return {
        "tok_emb": _normal(top[0], (sizes["V"], sizes["D"]), 0.02),
        "head_w": _normal(top[1], (sizes["D"], sizes["V"]), sizes["D"] ** -0.5),
        "norm_w": jnp.ones((sizes["D"],), jnp.bfloat16),
        "layers": layers,
        "arch": {name: np.asarray(value) for name, value in arch._asdict().items()},
        # which layers have a window, and each kind's rotary term (float64:
        # the numbers travel with the weights as they were computed)
        "windowed": np.asarray([kind == SLIDING for kind in sizes["kinds"]]),
        "rotary": {
            kind: {"lanes": np.asarray(term.lanes),
                   "freq": np.asarray(term.freq, np.float64),
                   "amplitude": np.asarray(term.amplitude, np.float64)}
            for kind, term in rotary.items()
        },
    }


def arch_of(params: dict) -> Arch:
    """The sizes that travel with the weights, as static python numbers."""
    kinds = get_type_hints(Arch)
    return Arch(**{
        name: kinds[name](np.asarray(value)) for name, value in params["arch"].items()
    })


def rotary_of(params: dict, windowed: bool) -> Rotary:
    term = params["rotary"][SLIDING if windowed else FULL]
    return Rotary(int(np.asarray(term["lanes"])),
                  tuple(float(f) for f in np.asarray(term["freq"])),
                  float(np.asarray(term["amplitude"])))


# ------------------------------------------------------------ layout bridge

def to_checkpoint_tree(params: dict) -> dict:
    """Reference layout -> the parameter tree of the program's checkpoint
    format: one ``layer{i}`` subtree a layer; the gate and up projections of
    an MLP or of the experts side by side in one tensor (``[.., 2 x width]``,
    the gate first), as the program's documented layout has them."""
    def host(x):
        return np.asarray(x)

    def side_by_side(*names):
        return lambda p: np.concatenate([host(p[n]) for n in names], -1)

    tree = {
        "tok_embedding": host(params["tok_emb"]),
        "norm": {"scale": host(params["norm_w"])},
        "head": {"kernel": host(params["head_w"])},
    }
    for i, p in enumerate(params["layers"]):
        layer = {
            "attn_norm": {"scale": host(p["attn_norm"])},
            "ffn_norm": {"scale": host(p["ffn_norm"])},
            "attn": {n: host(p[n]) for n in ("wq", "wk", "wv", "w_gate", "wo")},
        }
        if "router" in p:
            layer["moe"] = {
                "router": host(p["router"]),
                "w_gate_up": side_by_side("e_gate", "e_up")(p),
                "w_down": host(p["e_down"]),
                "shared_gate_up": side_by_side("s_gate", "s_up")(p),
                "shared_down": host(p["s_down"]),
            }
        else:
            layer["mlp"] = {
                "gate_up": side_by_side("m_gate", "m_up")(p),
                "down": host(p["m_down"]),
            }
        tree[f"layer{i}"] = layer
    return tree


# ------------------------------------------------------------------ forward

def _fake_int8(x, axis):
    """Symmetric int8 fake quantisation along ``axis``: 127 levels either
    side of zero, the scale from the largest magnitude."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, mode):
    """``x [..., K] @ w [K, N]`` in the arithmetic ``mode`` names."""
    if mode == "bf16":
        return jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _einsum(spec, a, b, mode):
    """The two attention products, in the arithmetic ``mode`` names (both
    contract their operands' last axis)."""
    if mode == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "int8":
        a, b = _fake_int8(a, -1), _fake_int8(b, -1)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rotate(x, rotary: Rotary):
    """``x [S, heads, hd]`` at positions ``0 .. S - 1``: the first
    ``rotary.lanes`` lanes of every head rotated, lane ``i`` with lane ``i +
    lanes / 2``; the others unchanged."""
    half = rotary.lanes // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        rotary.freq, jnp.float32)[None, :]  # [S, half]
    cos = (jnp.cos(angles) * rotary.amplitude)[:, None, :]
    sin = (jnp.sin(angles) * rotary.amplitude)[:, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], axis=-1)


def _attention(x, p, arch: Arch, rotary: Rotary, window: int, mode):
    """``window = 0``: every key ``j <= i``; else ``i - window < j <= i``."""
    a = arch
    s = x.shape[0]
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    heads = p["wq"].shape[1] // a.head_dim
    group = heads // a.kv_heads
    q = _rotate(_mm(x, up("wq"), mode).reshape(s, heads, a.head_dim), rotary)
    k = _rotate(_mm(x, up("wk"), mode).reshape(s, a.kv_heads, a.head_dim), rotary)
    v = _mm(x, up("wv"), mode).reshape(s, a.kv_heads, a.head_dim)
    q = q.reshape(s, a.kv_heads, group, a.head_dim)
    block = min(a.query_block, s)
    if s % block:
        raise ValueError(f"{s} positions are no multiple of the query block {block}")

    def rows(args):
        q_rows, first = args  # [block, Hkv, G, hd], the block's first position
        scores = _einsum("qhgd,khd->hgqk", q_rows, k, mode) * a.head_dim ** -0.5
        ahead = jnp.arange(s)[None, :] - (first + jnp.arange(block))[:, None]
        seen = ahead <= 0
        if window:
            seen &= ahead > -window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("hgqk,hdk->qhgd", probs, jnp.moveaxis(v, 0, -1), mode)

    out = jax.lax.map(rows, (
        q.reshape(s // block, block, a.kv_heads, group, a.head_dim),
        jnp.arange(0, s, block),
    )).reshape(s, heads, a.head_dim)
    gate = jax.nn.sigmoid(_mm(x, up("w_gate"), mode))  # [S, heads]
    return _mm((out * gate[:, :, None]).reshape(s, heads * a.head_dim), up("wo"), mode)


def _swiglu(x, gate, up, down, mode):
    return _mm(jax.nn.silu(_mm(x, gate, mode)) * _mm(x, up, mode), down, mode)


def _experts(x, p, arch: Arch, mode, routed=True, shared=True):
    """Shared expert plus the weighted sum of the held routed ones: every
    held expert applied to every token, the weight zero where the token did
    not choose it."""
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    y = jnp.zeros_like(x)
    if shared:
        y = _swiglu(x, up("s_gate"), up("s_up"), up("s_down"), mode)
    if not routed:
        return y
    scores = jax.nn.softmax(_mm(x, up("router"), mode), axis=-1)
    top_vals, top_idx = jax.lax.top_k(scores, arch.top_k)
    top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True) * arch.routed_scaling
    weights = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_idx
    ].set(top_vals)  # [S, E]
    held = jax.lax.dynamic_slice_in_dim(weights, arch.held_first, arch.held, axis=1)

    def one_expert(acc, xs):
        e_gate, e_up, e_down, w = xs  # one expert's bfloat16 weights, weights [S]
        out = _swiglu(x, e_gate.astype(jnp.float32), e_up.astype(jnp.float32),
                      e_down.astype(jnp.float32), mode)
        return acc + w[:, None] * out, None

    routed_sum, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (p["e_gate"], p["e_up"], p["e_down"], held.T)
    )
    return y + routed_sum


@functools.partial(jax.jit, static_argnames=("arch", "rotary", "window", "mode", "routed"))
def _layer(x, p, *, arch, rotary, window, mode, routed=True):
    eps = arch.rms_eps
    y = _rms_norm(x, p["attn_norm"].astype(jnp.float32), eps)
    h = x + _attention(y, p, arch, rotary, window, mode)
    y = _rms_norm(h, p["ffn_norm"].astype(jnp.float32), eps)
    if "router" in p:
        return h + _experts(y, p, arch, mode, routed)
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    return h + _swiglu(y, up("m_gate"), up("m_up"), up("m_down"), mode)


@functools.partial(jax.jit, static_argnames=("arch", "mode", "routed", "shared"))
def experts_layer(x, p, *, arch, mode="f32", routed=True, shared=True):
    """The expert layer alone over ``x [S, D]``: what the share test adds up."""
    return _experts(x, p, arch, mode, routed, shared)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm_w, head_w, *, eps, mode):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), mode)


def logits_one(params, tokens, mode="f32", routed=True, window_shift=0):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward,
    one jitted call a layer so that one layer's float32 weights live at a
    time.  ``routed=False`` leaves the routed experts out of the sum and
    ``window_shift`` widens every window by that many positions: the tests'
    controls, never the benchmark's."""
    arch = arch_of(params)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for p, windowed in zip(params["layers"], np.asarray(params["windowed"])):
        x = _layer(
            x, p, arch=arch, rotary=rotary_of(params, bool(windowed)),
            window=arch.window + window_shift if windowed else 0, mode=mode,
            routed=routed)
    return _head(x, params["norm_w"], params["head_w"], eps=arch.rms_eps, mode=mode)


def logits_for(params, tokens, heads, mode="f32"):
    """Logits of one sequence of any length: padded at the END to a
    multiple of the configuration's ``reference_pad_to`` (causal, and every
    other operation is a token's own or looks backwards, so the padding
    changes no kept row).  A float32 program at ``highest`` takes the chip's
    compiler ten seconds and more a shape, so a configuration names its
    longest sequence and one shape serves every request.  ``heads`` is what
    the driver passes (``sizes_of(config)["H"]``); the layers' own head
    counts travel with the weights' shapes."""
    del heads
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    arch = arch_of(params)
    padded = np.zeros((-(-n // arch.pad_to) * arch.pad_to,), np.int32)
    padded[:n] = tokens
    return logits_one(params, jnp.asarray(padded), mode)[:n]
