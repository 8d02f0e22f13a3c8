"""Plain reference of the Olmo-Hybrid decoder (``model_type: olmo_hybrid``,
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json): a
dense hybrid of Gated DeltaNet linear attention (arXiv:2412.06464; the
config's ``linear_*`` keys) and full softmax attention, three to one, under
Olmo's post-norm block.  Written from the equations; imports nothing of the
program and takes nothing the program made: the weights come from
``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``: no
cache, no batching, no chunks (the recurrence runs one position at a time in
``lax.scan``), attention in blocks of query rows only so that 3,072
positions fit.  The float32 copy of the 4.10 B parameters the benchmark's
cut holds is 16.4 GB, more than the chip: the weights are kept as the
bfloat16 arrays they were rounded to, which is exactly what they are, and
one layer at a time is widened to float32 inside its jitted call.

The equations (config keys in brackets), one layer ``l``:
  h = x + RMSNorm(Mixer_l(x));  x' = h + RMSNorm(MLP(h))
       the norm is on the sublayer's OUTPUT; nothing is normalised before it
  RMSNorm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w;  no bias but dt_bias
  MLP(x) = W_down(silu(W_gate x) * W_up x), width [intermediate_size]
  Mixer_l by [layer_types][l]:
  full_attention: q, k, v = x W_q, x W_k, x W_v (each hidden wide);
       q <- RMSNorm(q; w_q), k <- RMSNorm(k; w_k) over the WHOLE projection,
       before the heads are split; [num_attention_heads] =
       [num_key_value_heads] heads of hidden / heads ([head_dim] null);
       no rotary, no other position term ([rope_parameters.rope_theta] null);
       scores q.k / sqrt(head_dim), causal softmax, y = W_o (P v)
  linear_attention, H = [linear_num_key_heads] = [linear_num_value_heads]
       heads of d_k = [linear_key_head_dim], d_v = [linear_value_head_dim]:
       q~, k~ = x W_q, x W_k (hidden -> H d_k each), v~ = x W_v (hidden -> H d_v)
       q_t = silu(sum_{j=0..3} c^q_j * q~_{t-3+j})  (depthwise, causal,
       [linear_conv_kernel_dim] taps, no bias; rows before the sequence are
       zero), the same for k and v
       q^ = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)
       a_t = -exp(A_log_h) * softplus(w_a,h . x_t + dt_bias_h)   (ONE a head)
       b_t = sigmoid(w_b,h . x_t) * 2   ([linear_allow_neg_eigval]: the 2)
       S' = exp(a_t) S_{t-1};  S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T
       o_t = S_t^T q^_t;  S_0 = 0, float32, [H, d_k, d_v]
       y = W_o [ RMSNorm_{d_v}(o_t; w_o_norm) * silu(W_z x_t) ]
  after the last layer RMSNorm, then logits = x W_head (untied, no bias).

ASSUMED, where the config has no key (each also under ``assumed`` in the
configuration file):
  - the post-norm block (the Olmo 2 / Olmo 3 convention, arXiv:2501.00656);
  - QK-norm over the whole projection, with a learned weight (Olmo 2's);
  - no rotary term: the config gives no base (``rope_theta: null``) and the
    recurrent layers carry order; a rotary reading would change the full
    layers' scores and nothing else;
  - the output gate ``silu(W_z x)`` at full rank (hidden -> H d_v), which is
    what makes the parameter total meet the published "7B";
  - ``A_log`` and ``dt_bias`` of shape ``[H]``: one decay a head.
Departures from the published description: none in the mathematics as read
above.  The published model runs in bfloat16 and rounds after every
operation; the reference keeps float32 throughout.

``mode`` chooses the arithmetic, for the controls only:
  ``f32``         the reference itself;
  ``bf16``        matmul operands rounded to bfloat16 (what the configuration
                  states);
  ``int8``        matmul operands fake-quantised to int8 (per-row symmetric):
                  the nearest precision below the one the configuration states;
  ``bf16_state``  the reference, but the state rounded to bfloat16 after
                  every position: what a cache that kept it in bfloat16 gives.
Every rounding is ``lax.reduce_precision`` (an ``astype`` pair is dropped by
the TPU's compiler as excess precision).

Parameter layout ("reference layout"): ``tok_emb [V,D]``, ``head_w [D,V]``,
``norm_w [D]``, ``layers`` (a list, one dict a layer) and ``arch`` (the sizes
that no shape gives).  Every layer holds ``attn_norm [D]``, ``ffn_norm [D]``,
``m_gate``, ``m_up [D,M]``, ``m_down [M,D]``; a full layer ``wq``, ``wk``,
``wv [D,D]``, ``q_norm``, ``k_norm [D]``, ``wo [D,D]``; a linear layer ``gq``,
``gk [D,H*d_k]``, ``gv [D,H*d_v]``, ``conv_q``, ``conv_k [taps,H*d_k]``,
``conv_v [taps,H*d_v]``, ``wa``, ``wb [D,H]``, ``dt_bias``, ``a_log [H]``,
``wz [D,H*d_v]``, ``o_norm [d_v]``, ``go [H*d_v,D]``.  Every weight is a
bfloat16 array whose values were drawn in float32 and rounded once, so that
the program (which holds bfloat16) and the reference (which upcasts) start
from the same numbers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "int8", "bf16_state")
LINEAR, FULL = "linear_attention", "full_attention"


class Arch(NamedTuple):
    heads: int
    head_dim: int
    lin_heads: int
    key_dim: int
    value_dim: int
    taps: int
    rms_eps: float
    neg_eigval: bool
    pad_to: int
    query_block: int


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys (they lie at the file's top level, under the names of the source)."""
    c = config
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise ValueError(f"the reference does not write {key}: {c[key]!r}")
    if (c.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the reference writes no rotary term (rope_theta null)")
    heads, kv_heads = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    lin_heads = int(c["linear_num_value_heads"])
    if kv_heads != heads or int(c["linear_num_key_heads"]) != lin_heads:
        raise ValueError("the reference writes as many K/V as query heads")
    layers = int(c["num_hidden_layers"])
    kinds = tuple(c["layer_types"][:layers])
    if len(kinds) != layers or set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"layer_types does not name {layers} layers: {kinds!r}")
    arch = Arch(
        heads=heads,
        head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
        lin_heads=lin_heads, key_dim=int(c["linear_key_head_dim"]),
        value_dim=int(c["linear_value_head_dim"]),
        taps=int(c["linear_conv_kernel_dim"]), rms_eps=float(c["rms_norm_eps"]),
        neg_eigval=bool(c["linear_allow_neg_eigval"]),
        # every sequence is padded to a multiple of this: a configuration
        # gives its longest (bucket + new tokens), so that ONE shape compiles
        pad_to=int(c.get("reference_pad_to", 256)),
        query_block=int(c.get("reference_query_block", 512)),
    )
    return {
        "H": heads, "arch": arch, "V": int(c["vocab_size"]),
        "D": int(c["hidden_size"]), "L": layers,
        "M": int(c["intermediate_size"]), "kinds": kinds,
    }


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def _uniform(key, shape, low, high):
    return jax.random.uniform(key, shape, jnp.float32, low, high)


@functools.partial(jax.jit, static_argnames=("D", "M", "L", "arch", "kind"))
def _make_layer(key, *, D, M, L, arch, kind):
    k = iter(jax.random.split(key, 16))
    a = arch
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    # assumed: in a post-norm block every mixer reads the residual stream
    # un-normalised, and each sublayer adds a normalised output to it.
    # Norm weights of (2 L)^-1/2 keep it at unit RMS after all 2 L sublayers
    # (weights of 1 would let it grow to (2 L)^1/2 and with it every
    # projection of it: the decay's input, the MLP's gate)
    branch = jnp.full((D,), (2 * L) ** -0.5, jnp.bfloat16)
    layer = {
        "attn_norm": branch, "ffn_norm": branch,
        "m_gate": _normal(next(k), (D, M), D ** -0.5),
        "m_up": _normal(next(k), (D, M), D ** -0.5),
        "m_down": _normal(next(k), (M, D), M ** -0.5),
    }
    if kind == FULL:
        layer.update(
            wq=_normal(next(k), (D, D), D ** -0.5),
            wk=_normal(next(k), (D, D), D ** -0.5),
            wv=_normal(next(k), (D, D), D ** -0.5),
            q_norm=ones((D,)), k_norm=ones((D,)),
            wo=_normal(next(k), (D, D), D ** -0.5),
        )
        return layer
    hk, hv = a.lin_heads * a.key_dim, a.lin_heads * a.value_dim
    # assumed: decays that spread, as solar-open2-250b.json argues, one a
    # head: A = exp(A_log) uniform in (0.5, 2), dt_bias the inverse softplus
    # of a step drawn log-uniform in (0.001, 0.1), so that
    # alpha = exp(-A softplus(z + dt_bias)) with z of deviation up to 1 lies
    # mostly in (0.5, 0.999) and the state neither vanishes nor saturates
    # over some thousands of positions
    step = jnp.exp(_uniform(next(k), (a.lin_heads,), np.log(1e-3), np.log(1e-1)))
    layer.update(
        gq=_normal(next(k), (D, hk), D ** -0.5),
        gk=_normal(next(k), (D, hk), D ** -0.5),
        gv=_normal(next(k), (D, hv), D ** -0.5),
        conv_q=_normal(next(k), (a.taps, hk), a.taps ** -0.5),
        conv_k=_normal(next(k), (a.taps, hk), a.taps ** -0.5),
        conv_v=_normal(next(k), (a.taps, hv), a.taps ** -0.5),
        wa=_normal(next(k), (D, a.lin_heads), D ** -0.5),
        wb=_normal(next(k), (D, a.lin_heads), D ** -0.5),
        dt_bias=jnp.log(jnp.expm1(step)).astype(jnp.bfloat16),
        a_log=jnp.log(_uniform(next(k), (a.lin_heads,), 0.5, 2.0)).astype(jnp.bfloat16),
        wz=_normal(next(k), (D, hv), D ** -0.5),
        o_norm=ones((a.value_dim,)),
        go=_normal(next(k), (hv, D), hv ** -0.5),
    )
    return layer


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, one jitted call a layer:
    embeddings N(0, 0.02), matrices N(0, 1/fan_in), convolution taps
    N(0, 1/4), QK, output and final norms 1, the two post-norms and the
    decays as :func:`_make_layer` says, each drawn in float32 and rounded
    once to bfloat16."""
    key = seed_key(seed)
    arch = sizes["arch"]
    layers = [
        _make_layer(jax.random.fold_in(key, i), D=sizes["D"], M=sizes["M"],
                    L=sizes["L"], arch=arch, kind=sizes["kinds"][i])
        for i in range(sizes["L"])
    ]
    top = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {
        "tok_emb": _normal(top[0], (sizes["V"], sizes["D"]), 0.02),
        "head_w": _normal(top[1], (sizes["D"], sizes["V"]), sizes["D"] ** -0.5),
        "norm_w": jnp.ones((sizes["D"],), jnp.bfloat16),
        "layers": layers,
        "arch": {name: np.asarray(value) for name, value in arch._asdict().items()},
    }


def arch_of(params: dict) -> Arch:
    """The sizes that travel with the weights, as static python numbers."""
    kinds = get_type_hints(Arch)
    return Arch(**{
        name: kinds[name](np.asarray(value)) for name, value in params["arch"].items()
    })


# ------------------------------------------------------------ layout bridge

def to_checkpoint_tree(params: dict) -> dict:
    """Reference layout -> the parameter tree of the program's checkpoint
    format: one ``layer{i}`` subtree a layer; the MLP's gate and up
    projections side by side in one tensor (``[D, 2 M]``, the gate first); a
    linear layer's three projections and its three convolutions side by side
    too (q, k, v in that order), and its two one-a-head projections (the
    decay's first), as the program's documented layout has them."""
    def host(x):
        return np.asarray(x)

    def side_by_side(p, *names):
        return np.concatenate([host(p[n]) for n in names], -1)

    tree = {
        "tok_embedding": host(params["tok_emb"]),
        "norm": {"scale": host(params["norm_w"])},
        "head": {"kernel": host(params["head_w"])},
    }
    for i, p in enumerate(params["layers"]):
        layer = {
            "attn_norm": {"scale": host(p["attn_norm"])},
            "ffn_norm": {"scale": host(p["ffn_norm"])},
            "mlp": {"gate_up": side_by_side(p, "m_gate", "m_up"),
                    "down": host(p["m_down"])},
        }
        if "wq" in p:
            layer["attn"] = {
                n: host(p[n]) for n in ("wq", "wk", "wv", "q_norm", "k_norm", "wo")}
        else:
            layer["gdn"] = {
                "w_qkv": side_by_side(p, "gq", "gk", "gv"),
                "conv_w": side_by_side(p, "conv_q", "conv_k", "conv_v"),
                "w_ab": side_by_side(p, "wa", "wb"),
                "dt_bias": host(p["dt_bias"]), "A_log": host(p["a_log"]),
                "w_z": host(p["wz"]), "o_norm": host(p["o_norm"]),
                "w_o": host(p["go"]),
            }
        tree[f"layer{i}"] = layer
    return tree


# ------------------------------------------------------------------ forward

def _to_bf16(x):
    """Float32 values rounded to bfloat16's 8 bits of mantissa, still
    float32 (``reduce_precision``, not ``astype`` there and back)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _fake_int8(x, axis):
    """Symmetric int8 fake quantisation along ``axis``: 127 levels either
    side of zero, the scale from the largest magnitude."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rounded(a, b, mode, a_axis=-1, b_axis=-1):
    """The operands of one product in the arithmetic ``mode`` names; an
    int8 scale lies along each operand's contracted axis."""
    if mode == "bf16":
        return _to_bf16(a), _to_bf16(b)
    if mode == "int8":
        return _fake_int8(a, a_axis), _fake_int8(b, b_axis)
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    return a, b


def _mm(x, w, mode):
    """``x [..., K] @ w [K, N]`` in the arithmetic ``mode`` names."""
    x, w = _rounded(x, w, mode, -1, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _einsum(spec, a, b, mode):
    """The two attention products (both contract their operands' last
    axis)."""
    return jnp.einsum(spec, *_rounded(a, b, mode), precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _attention(x, p, arch: Arch, mode):
    a = arch
    s = x.shape[0]
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    heads = lambda t: t.reshape(s, a.heads, a.head_dim)  # noqa: E731
    q = heads(_rms_norm(_mm(x, up("wq"), mode), up("q_norm"), a.rms_eps))
    k = heads(_rms_norm(_mm(x, up("wk"), mode), up("k_norm"), a.rms_eps))
    v = heads(_mm(x, up("wv"), mode))
    block = min(a.query_block, s)
    if s % block:
        raise ValueError(f"{s} positions are no multiple of the query block {block}")

    def rows(args):
        q_rows, first = args  # [block, H, hd], the block's first position
        scores = _einsum("qhd,khd->hqk", q_rows, k, mode) * a.head_dim ** -0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("hqk,hdk->qhd", probs, jnp.moveaxis(v, 0, -1), mode)

    out = jax.lax.map(rows, (
        q.reshape(s // block, block, a.heads, a.head_dim),
        jnp.arange(0, s, block),
    )).reshape(s, a.heads * a.head_dim)
    return _mm(out, up("wo"), mode)


def _short_conv(pre, taps_w):
    """``silu(sum_j c_j * pre[t - (taps-1) + j])``, zeros before the sequence."""
    taps = taps_w.shape[0]
    padded = jnp.pad(pre, ((taps - 1, 0), (0, 0)))
    s = pre.shape[0]
    return jax.nn.silu(sum(taps_w[j] * padded[j:j + s] for j in range(taps)))


def _gated_delta(x, p, arch: Arch, mode, delta=True):
    a = arch
    s, h, dk, dv = x.shape[0], a.lin_heads, a.key_dim, a.value_dim
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    q = _short_conv(_mm(x, up("gq"), mode), up("conv_q")).reshape(s, h, dk)
    k = _short_conv(_mm(x, up("gk"), mode), up("conv_k")).reshape(s, h, dk)
    v = _short_conv(_mm(x, up("gv"), mode), up("conv_v")).reshape(s, h, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    rate = jax.nn.softplus(_mm(x, up("wa"), mode) + up("dt_bias"))
    log_decay = -jnp.exp(up("a_log")) * rate  # [S, H]: one a head
    beta = jax.nn.sigmoid(_mm(x, up("wb"), mode)) * (2.0 if a.neg_eigval else 1.0)

    def position(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs  # [H, d_k] x 2, [H, d_v], [H], [H]
        decayed = state * jnp.exp(a_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t, precision=HIGHEST)
        change = b_t[:, None] * (v_t - seen if delta else v_t)
        state = decayed + k_t[:, :, None] * change[:, None, :]
        out = jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)
        if mode == "bf16_state":
            state = _to_bf16(state)
        return state, out

    _, out = jax.lax.scan(
        position, jnp.zeros((h, dk, dv), jnp.float32), (q, k, v, log_decay, beta))
    out = _rms_norm(out, up("o_norm"), a.rms_eps).reshape(s, h * dv)
    out = out * jax.nn.silu(_mm(x, up("wz"), mode))
    return _mm(out, up("go"), mode)


def _mlp(x, p, mode):
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    hidden = jax.nn.silu(_mm(x, up("m_gate"), mode)) * _mm(x, up("m_up"), mode)
    return _mm(hidden, up("m_down"), mode)


@functools.partial(jax.jit, static_argnames=("arch", "mode", "delta"))
def _layer(x, p, *, arch, mode, delta=True):
    eps = arch.rms_eps
    mixed = (_attention(x, p, arch, mode) if "wq" in p
             else _gated_delta(x, p, arch, mode, delta))
    h = x + _rms_norm(mixed, p["attn_norm"].astype(jnp.float32), eps)
    return h + _rms_norm(_mlp(h, p, mode), p["ffn_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm_w, head_w, *, eps, mode):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), mode)


def logits_one(params, tokens, mode="f32", delta=True):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward,
    one jitted call a layer so that one layer's float32 weights live at a
    time.  ``delta=False`` leaves the delta term (``- S'^T k``) out of the
    update: the tests' control, never the benchmark's."""
    arch = arch_of(params)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for p in params["layers"]:
        x = _layer(x, p, arch=arch, mode=mode, delta=delta)
    return _head(x, params["norm_w"], params["head_w"], eps=arch.rms_eps, mode=mode)


def logits_for(params, tokens, heads, mode="f32"):
    """Logits of one sequence of any length: padded at the END to a
    multiple of the configuration's ``reference_pad_to`` (causal, and every
    other operation is a token's own or looks backwards, so the padding
    changes no kept row).  A float32 program at ``highest`` takes the chip's
    compiler ten seconds and more a shape, so a configuration names its
    longest sequence and one shape serves every request.  ``heads`` is what
    the driver passes; the weights carry it."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    arch = arch_of(params)
    if int(heads) != arch.heads:
        raise ValueError(f"heads {heads} but the weights were made for {arch.heads}")
    padded = np.zeros((-(-n // arch.pad_to) * arch.pad_to,), np.int32)
    padded[:n] = tokens
    return logits_one(params, jnp.asarray(padded), mode)[:n]
