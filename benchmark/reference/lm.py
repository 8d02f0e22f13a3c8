"""Plain reference of the decoder-only LM: a GPT-2 block (learned positions,
pre-LayerNorm, exact-erf GELU MLP, as many KV heads as query heads, untied
head with bias), its token-mean cross entropy, its gradients and torch-order
AdamW.  Written from the architecture; imports nothing of the program and
takes nothing the program made: the weights come from ``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``: no
kernel, no cache, no batching beyond a scan over rows so that it fits.

``mode`` chooses the matmul arithmetic, for the controls only:
  ``f32``   the reference itself;
  ``bf16``  operands rounded to bfloat16 (what the configuration states);
  ``int8``  operands fake-quantised to int8 (per-row symmetric): the nearest
            precision below the one the configuration states.

Parameter layout ("reference layout"): a flat dict, per-block tensors
stacked on a leading depth axis so the depth is a ``lax.scan``:
  tok_emb [V,E]  pos_emb [P,E]
  ln1_w ln1_b ln2_w ln2_b [L,E]   qkv_w [L,E,3E] qkv_b [L,3E]
  proj_w [L,E,E] proj_b [L,E]     fc1_w [L,E,M] fc1_b [L,M]
  fc2_w [L,M,E] fc2_b [L,E]       lnf_w lnf_b [E]   head_w [E,V] head_b [V]
The fused qkv output is heads-major ``(H, 3, head_dim)``: the program's
documented checkpoint layout (``models/torch_port.py``'s twin naming).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6  # the configuration's LayerNorm epsilon
BLOCK_KEYS = (
    "ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
    "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b",
)


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's model."""
    m = config["model"]
    e = int(m["embed_dim"])
    return {
        "V": int(config["vocab_size"]), "P": int(m["max_len"]), "E": e,
        "L": int(m["depth"]), "H": int(m["num_heads"]),
        "M": int(e * float(m.get("mlp_ratio", 4.0))),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


@functools.partial(jax.jit, static_argnames=("V", "P", "E", "L", "H", "M"))
def _make_params(key, *, V, P, E, L, H, M):
    k = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return std * jax.random.normal(next(k), shape, jnp.float32)

    ones, zeros = jnp.ones, jnp.zeros
    return {
        "tok_emb": normal((V, E), 0.02),
        "pos_emb": normal((P, E), 0.02),
        "ln1_w": ones((L, E)), "ln1_b": zeros((L, E)),
        "qkv_w": normal((L, E, 3 * E), 1 / math.sqrt(E)),
        "qkv_b": zeros((L, 3 * E)),
        "proj_w": normal((L, E, E), 1 / math.sqrt(E)),
        "proj_b": zeros((L, E)),
        "ln2_w": ones((L, E)), "ln2_b": zeros((L, E)),
        "fc1_w": normal((L, E, M), 1 / math.sqrt(E)),
        "fc1_b": zeros((L, M)),
        "fc2_w": normal((L, M, E), 1 / math.sqrt(M)),
        "fc2_b": zeros((L, E)),
        "lnf_w": ones((E,)), "lnf_b": zeros((E,)),
        "head_w": normal((E, V), 1 / math.sqrt(E)),
        "head_b": zeros((V,)),
    }


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, in one jitted call: embeddings
    N(0, 0.02), matrices N(0, 1/fan_in), LayerNorm 1/0, biases 0."""
    return _make_params(seed_key(seed), **sizes)


# ------------------------------------------------------------ layout bridges

def to_torch_state_dict(params: dict) -> dict:
    """Reference layout -> the program's documented torch-twin names
    (Linear weights are (out, in)).  Values are numpy arrays."""
    p = {k: np.asarray(v) for k, v in params.items()}
    out = {
        "tok_emb.weight": p["tok_emb"], "pos_emb": p["pos_emb"],
        "ln_f.weight": p["lnf_w"], "ln_f.bias": p["lnf_b"],
        "head.weight": p["head_w"].T, "head.bias": p["head_b"],
    }
    names = {
        "ln1": ("ln1_w", "ln1_b", False), "ln2": ("ln2_w", "ln2_b", False),
        "attn_qkv": ("qkv_w", "qkv_b", True),
        "attn_proj": ("proj_w", "proj_b", True),
        "fc1": ("fc1_w", "fc1_b", True), "fc2": ("fc2_w", "fc2_b", True),
    }
    for i in range(p["ln1_w"].shape[0]):
        for name, (w, b, linear) in names.items():
            out[f"blocks.{i}.{name}.weight"] = p[w][i].T if linear else p[w][i]
            out[f"blocks.{i}.{name}.bias"] = p[b][i]
    return out


_FLAX_BLOCK = {
    "ln1_w": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
    "ln2_w": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
    "qkv_w": ("attn", "qkv", "kernel"), "qkv_b": ("attn", "qkv", "bias"),
    "proj_w": ("attn", "proj", "kernel"), "proj_b": ("attn", "proj", "bias"),
    "fc1_w": ("mlp", "fc1", "kernel"), "fc1_b": ("mlp", "fc1", "bias"),
    "fc2_w": ("mlp", "fc2", "kernel"), "fc2_b": ("mlp", "fc2", "bias"),
}
_FLAX_TOP = {
    "tok_emb": ("tok_embedding",), "pos_emb": ("pos_embedding",),
    "lnf_w": ("ln", "scale"), "lnf_b": ("ln", "bias"),
    "head_w": ("head", "kernel"), "head_b": ("head", "bias"),
}


def _put(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def to_checkpoint_tree(params: dict) -> dict:
    """Reference layout -> the parameter tree of the program's checkpoint
    format (one ``block{i}`` subtree a layer)."""
    p = {k: np.asarray(v) for k, v in params.items()}
    tree: dict = {}
    for key, path in _FLAX_TOP.items():
        _put(tree, path, p[key])
    for i in range(p["ln1_w"].shape[0]):
        for key, path in _FLAX_BLOCK.items():
            _put(tree, (f"block{i}",) + path, p[key][i])
    return tree


def from_checkpoint_tree(tree, depth: int) -> dict:
    """The inverse: a parameter-shaped tree read back from the program
    (parameters, a gradient, a moment) -> reference layout, numpy."""
    out = {k: np.asarray(_get(tree, path)) for k, path in _FLAX_TOP.items()}
    for key, path in _FLAX_BLOCK.items():
        out[key] = np.stack([
            np.asarray(_get(tree, (f"block{i}",) + path)) for i in range(depth)
        ])
    return out


# ------------------------------------------------------------------ forward

def _fake_int8(x, axis):
    """Symmetric int8 fake quantisation along ``axis``: 127 levels either
    side of zero, the scale from the largest magnitude."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _int8_product(op, axis_a, axis_b, axis_g):
    """``op(a, b)`` as an int8 path would compute it: both operands
    quantised on the way in, and in the backward pass the incoming gradient
    quantised too before the two transposed products (straight through the
    quantisers themselves)."""
    @jax.custom_vjp
    def product(a, b):
        return op(_fake_int8(a, axis_a), _fake_int8(b, axis_b))

    def forward(a, b):
        qa, qb = _fake_int8(a, axis_a), _fake_int8(b, axis_b)
        return op(qa, qb), (qa, qb)

    def backward(kept, g):
        return jax.vjp(op, *kept)[1](_fake_int8(g, axis_g))

    product.defvjp(forward, backward)
    return product


_matmul = functools.partial(jnp.matmul, precision=HIGHEST)
_int8_matmul = _int8_product(_matmul, -1, 0, -1)


def _mm(x, w, mode):
    """``x [..., K] @ w [K, N]`` in the arithmetic ``mode`` names."""
    if mode == "bf16":
        return jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if mode == "int8":
        return _int8_matmul(x, w)
    if mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return _matmul(x, w)


def _einsum(spec, a, b, mode):
    """The two attention products, in the arithmetic ``mode`` names."""
    op = functools.partial(jnp.einsum, spec, precision=HIGHEST)
    if mode == "bf16":
        rnd = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return op(rnd(a), rnd(b))
    if mode == "int8":
        return _int8_product(op, -1, -1, -1)(a, b)
    return op(a, b)


def _layer_norm(x, w, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _block(x, blk, heads, mode):
    s, e = x.shape
    hd = e // heads
    y = _layer_norm(x, blk["ln1_w"], blk["ln1_b"])
    qkv = (_mm(y, blk["qkv_w"], mode) + blk["qkv_b"]).reshape(s, heads, 3, hd)
    q, k, v = (qkv[:, :, i] for i in range(3))
    scores = _einsum("qhd,khd->hqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = _einsum("hqk,khd->qhd", probs, v, mode).reshape(s, e)
    x = x + _mm(att, blk["proj_w"], mode) + blk["proj_b"]
    y = _layer_norm(x, blk["ln2_w"], blk["ln2_b"])
    h = jax.nn.gelu(_mm(y, blk["fc1_w"], mode) + blk["fc1_b"], approximate=False)
    return x + _mm(h, blk["fc2_w"], mode) + blk["fc2_b"]


def logits_one(params, tokens, heads, mode="f32"):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward."""
    s = tokens.shape[0]
    x = params["tok_emb"][tokens] + params["pos_emb"][:s]
    body = jax.checkpoint(lambda x, blk: (_block(x, blk, heads, mode), None))
    x, _ = jax.lax.scan(body, x, {k: params[k] for k in BLOCK_KEYS})
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"])
    return _mm(x, params["head_w"], mode) + params["head_b"]


def _row_loss_sum(params, tokens, targets, heads, mode):
    logp = jax.nn.log_softmax(logits_one(params, tokens, heads, mode), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("heads", "mode"))
def loss_and_grads(params, tokens, targets, *, heads, mode="f32"):
    """Token-mean cross entropy of the batch ``[B, S]`` and its gradient,
    one row at a time so that it fits beside nothing else."""
    n_tokens = tokens.shape[0] * tokens.shape[1]
    row = jax.value_and_grad(_row_loss_sum)

    def step(carry, xs):
        loss, grads = row(params, xs[0], xs[1], heads, mode)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grads)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(step, zero, (tokens, targets))
    return loss / n_tokens, jax.tree.map(lambda g: g / n_tokens, grads)


@functools.partial(jax.jit, static_argnames=("heads", "mode"))
def logits_padded(params, tokens, *, heads, mode="f32"):
    return logits_one(params, tokens, heads, mode)


def logits_for(params, tokens, heads, mode="f32", pad_to=256):
    """Logits of one sequence of any length: padded at the END to a
    multiple of ``pad_to`` (causal, so the padding changes no kept row) to
    keep the number of compiled shapes small."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    room = params["pos_emb"].shape[0]
    padded = np.zeros((min(-(-n // pad_to) * pad_to, room),), np.int32)
    padded[:n] = tokens
    return logits_padded(params, jnp.asarray(padded), heads=heads, mode=mode)[:n]


# --------------------------------------------------------------- optimizer

def lr_at(step: int, opt: dict) -> float:
    """The configuration's schedule at the first steps: cosine after a
    linear warm-up (the cosine part is 1 to rounding this early)."""
    sched = opt["lr_schedule"]
    warm = int(sched.get("warmup_iters", 0))
    base, end = float(opt["lr"]), float(sched.get("end_lr", 0.0))
    decay = max(int(sched["total_iters"]) - warm, 1)
    s = min(max(step - warm, 0), decay)
    lr = end + (base - end) * 0.5 * (1.0 + math.cos(math.pi * s / decay))
    if step < warm:
        alpha = step / warm
        f = float(sched.get("warmup_factor", 1.0 / 3))
        lr *= f * (1.0 - alpha) + alpha
    return lr


@jax.jit
def adamw_step(params, mu, nu, grads, t, lr, wd, b1, b2, eps):
    """torch.optim.AdamW's order: decay, moments, bias correction, step,
    with eps outside the square root; decay on every tensor."""
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(p, m, v, g):
        p = p * (1.0 - lr * wd)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        return p - (lr / bc1) * m / (jnp.sqrt(v) / jnp.sqrt(bc2) + eps), m, v

    out = {k: one(params[k], mu[k], nu[k], grads[k]) for k in params}
    return tuple({k: v[i] for k, v in out.items()} for i in range(3))


def first_gradient(moment: dict, params0: dict, opt: dict) -> dict:
    """The first gradient as the optimizer got it, from the program's
    state after ONE step: AdamW's first moment is ``(1 - b1) g``."""
    b1 = float(opt.get("betas", (0.9, 0.999))[0])
    return {k: np.asarray(v) / (1.0 - b1) for k, v in moment.items()}


@jax.jit
def _leaf_norms(tree):
    return {
        k: jnp.sqrt(jnp.sum(
            jnp.square(v).reshape(v.shape[0] if k in BLOCK_KEYS else 1, -1), axis=1
        ))
        for k, v in tree.items()
    }


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf the program holds: one a layer for the stacked
    tensors.  ``{name: float}`` with names ``qkv_w.3``.  Reduced where the
    tree lives (a tree on the device never comes to the host)."""
    out = {}
    norms = jax.device_get(_leaf_norms({k: jnp.asarray(v) for k, v in tree.items()}))
    for key, vals in norms.items():
        if key in BLOCK_KEYS:
            out.update({f"{key}.{i}": float(n) for i, n in enumerate(vals)})
        else:
            out[key] = float(vals[0])
    return out


def train_reference(params0, batches, sizes, opt, mode="f32"):
    """Follow the first ``len(batches)`` optimizer steps.  Returns the loss
    of each step, the per-leaf norms of the first gradient, and the per-leaf
    norms of the parameters' change after all of them."""
    betas = opt.get("betas", (0.9, 0.999))
    params = params0
    mu = jax.tree.map(jnp.zeros_like, params0)
    nu = jax.tree.map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for step, (tokens, targets) in enumerate(batches):
        loss, grads = loss_and_grads(
            params, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32), heads=sizes["H"], mode=mode,
        )
        losses.append(float(loss))
        if step == 0:
            grad_norms = leaf_norms(grads)
        params, mu, nu = adamw_step(
            params, mu, nu, grads, float(step + 1), lr_at(step, opt),
            float(opt["weight_decay"]), float(betas[0]), float(betas[1]),
            float(opt.get("eps", 1e-8)),
        )
    change = jax.tree.map(jnp.subtract, params, params0)
    return {
        "losses": losses, "grad_norms": grad_norms,
        "change_norms": leaf_norms(change),
    }


# ------------------------------------------------- what the drivers call

def from_program_tree(tree, sizes: dict) -> dict:
    return from_checkpoint_tree(tree, sizes["L"])


def prepare_data(seed: int, config: dict, traffic: dict, directory: str) -> dict:
    """The corpus, from the seed: uniform tokens in the flat binary format
    the program's ``tokens`` dataset reads (``<split>.bin`` + ``meta.json``).
    Returns the ``dataset`` section of the program's configuration."""
    import json
    import os

    os.makedirs(directory, exist_ok=True)
    vocab, seq = int(config["vocab_size"]), int(traffic["seq_len"])
    rng = np.random.default_rng(int(seed))
    rows = {"train": int(traffic["n_windows"]), "val": int(traffic["batch_size"])}
    for split, n in rows.items():
        rng.integers(0, vocab, n * seq + 1, dtype=np.uint16).tofile(
            os.path.join(directory, f"{split}.bin")
        )
    with open(os.path.join(directory, "meta.json"), "w") as fp:
        json.dump({"dtype": "uint16", "vocab_size": vocab}, fp)
    return {"name": "tokens", "root": directory, "n_classes": vocab, "seq_len": seq}


def reference_batch(fed, data_cfg: dict):
    """The batch of one checked step for the reference.  Only the INPUT
    rows are taken from what the program fed its step; each must be a
    distinct window of the benchmark's own corpus, and the targets are read
    from that corpus, not from the program."""
    import os

    seq = int(data_cfg["seq_len"])
    corpus = np.fromfile(os.path.join(data_cfg["root"], "train.bin"), np.uint16)
    windows = corpus[: (len(corpus) - 1) // seq * seq].reshape(-1, seq)
    index = {row[:16].tobytes(): i for i, row in enumerate(windows)}
    tokens = np.asarray(fed[0], np.int64)
    found = [index.get(row[:16].astype(np.uint16).tobytes()) for row in tokens]
    if None in found or len(set(found)) != len(found):
        raise ValueError("a fed row is not a distinct window of the corpus")
    for row, i in zip(tokens, found):
        if not np.array_equal(row, windows[i]):
            raise ValueError(f"fed row differs from corpus window {i}")
    targets = np.stack([corpus[i * seq + 1: (i + 1) * seq + 1] for i in found])
    return tokens.astype(np.int32), targets.astype(np.int32)
