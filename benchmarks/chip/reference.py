"""Plain reference of the benchmarked transformers, independent of the program.

One pre-LayerNorm transformer with rotary positions, tanh-GELU MLP, biases
and a tied output embedding: the function the program computes for both
configurations (``causal=False`` is the BERT encoder, ``causal=True`` the
GPT decoder).  Everything is float32 at ``Precision.HIGHEST`` unless
``mode="fp8"``: then both operands of every matrix product are rounded to
float8_e4m3fn with one scale per tensor, which is the control that must come
out as not correct.

Also here: the seeded weights both sides are given, the AdamW step the
training reference follows, and the V-cycle's coalescing operator ("stack"
width pairs (i, i + n/2), "adj" depth pairs (2j, 2j + 1)).  Nothing of the
program is imported.

Layout: ``{"tok": [V, E], "lnf_s", "lnf_b": [E], "layers": {name: [L, ...]}}``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def dims(cfg: Dict) -> Dict:
    """Sizes from a configuration file (HF BERT or GPT-2 key names)."""
    if "hidden_size" in cfg:
        E, L, H = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"]
        F, causal = cfg["intermediate_size"], False
    else:
        E, L, H = cfg["n_embd"], cfg["n_layer"], cfg["n_head"]
        F, causal = cfg["n_inner"] or 4 * cfg["n_embd"], True
    V = cfg["vocab_size"]
    return {"E": E, "L": L, "H": H, "D": E // H, "F": F, "V": V,
            "Vpad": -(-V // 128) * 128, "causal": causal}


# ---------------------------------------------------------------------------
# weights


def layer_shapes(d: Dict) -> Dict[str, Tuple[int, ...]]:
    E, H, D, F, L = d["E"], d["H"], d["D"], d["F"], d["L"]
    return {"ln1_s": (L, E), "ln1_b": (L, E),
            "wq": (L, E, H, D), "wk": (L, E, H, D), "wv": (L, E, H, D),
            "bq": (L, H, D), "bk": (L, H, D), "bv": (L, H, D),
            "wo": (L, H, D, E), "bo": (L, E),
            "ln2_s": (L, E), "ln2_b": (L, E),
            "w_up": (L, E, F), "b_up": (L, F), "w_down": (L, F, E), "b_down": (L, E)}


def init_params(key: jax.Array, d: Dict) -> Dict:
    """Matrices and embeddings N(0, 0.02), biases 0, LayerNorm scales 1; the
    rows of vocabulary padding are 0.  Call under ``jax.jit``."""
    shapes = layer_shapes(d)
    keys = dict(zip(sorted(shapes) + ["tok"], jax.random.split(key, len(shapes) + 1)))
    layers = {}
    for name, shape in shapes.items():
        if name.startswith("w"):
            layers[name] = 0.02 * jax.random.normal(keys[name], shape, jnp.float32)
        elif name.endswith("_s"):
            layers[name] = jnp.ones(shape, jnp.float32)
        else:
            layers[name] = jnp.zeros(shape, jnp.float32)
    tok = 0.02 * jax.random.normal(keys["tok"], (d["Vpad"], d["E"]), jnp.float32)
    tok = jnp.where(jnp.arange(d["Vpad"])[:, None] < d["V"], tok, 0.0)
    return {"tok": tok, "lnf_s": jnp.ones((d["E"],), jnp.float32),
            "lnf_b": jnp.zeros((d["E"],), jnp.float32), "layers": layers}


# ---------------------------------------------------------------------------
# forward


def _q8(x: jax.Array, dtype=jnp.float8_e4m3fn) -> jax.Array:
    """Rounds ``x`` to ``dtype`` under one scale that maps its largest
    magnitude to the format's largest finite value."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _mm8(spec: str, a, b):
    """A product as fp8 training computes it: e4m3 operands forward, the
    incoming gradient rounded to e5m2 backward, both scaled per tensor."""
    prod = lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST)

    @jax.custom_vjp
    def f(a, b):
        return prod(_q8(a), _q8(b))

    def fwd(a, b):
        qa, qb = _q8(a), _q8(b)
        return prod(qa, qb), (qa, qb)

    def bwd(res, dy):
        return jax.vjp(prod, *res)[1](_q8(dy, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _mm(spec: str, a, b, mode: str):
    if mode == "fp8":
        return _mm8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _ln(x, s, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * s + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope(x, pos):
    """x [B, S, H, D]; rotates the two halves of D by position."""
    D = x.shape[-1]
    freq = 1.0 / (10000.0 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[:, :, None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _layer(x, p, pos, causal: bool, mode: str):
    D = p["wq"].shape[-1]
    h = _ln(x, p["ln1_s"], p["ln1_b"])
    q = _rope(_mm("bse,ehd->bshd", h, p["wq"], mode) + p["bq"], pos)
    k = _rope(_mm("bse,ehd->bshd", h, p["wk"], mode) + p["bk"], pos)
    v = _mm("bse,ehd->bshd", h, p["wv"], mode) + p["bv"]
    s = _mm("bshd,bthd->bhst", q, k, mode) / math.sqrt(D)
    if causal:
        S = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = _mm("bhst,bthd->bshd", jax.nn.softmax(s, -1), v, mode)
    x = x + _mm("bshd,hde->bse", a, p["wo"], mode) + p["bo"]
    h = _ln(x, p["ln2_s"], p["ln2_b"])
    u = _gelu(_mm("bse,ef->bsf", h, p["w_up"], mode) + p["b_up"])
    return x + _mm("bsf,fe->bse", u, p["w_down"], mode) + p["b_down"]


def hidden(params, tokens, causal: bool, mode: str = "f32", remat: bool = False):
    """Final-LayerNorm hidden states [B, S, E] of ``tokens`` [B, S]."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    layer = lambda x, p: (_layer(x, p, pos, causal, mode), None)
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, params["tok"][tokens], params["layers"])
    return _ln(x, params["lnf_s"], params["lnf_b"])


def logits(params, h, mode: str = "f32"):
    return _mm("bse,ve->bsv", h, params["tok"], mode)


def mlm_loss_sum(params, batch, causal: bool, mode: str):
    """Sum over labelled positions of the cross-entropy (labels -1 ignored)."""
    lg = logits(params, hidden(params, batch["tokens"], causal, mode, remat=True), mode)
    lab = batch["labels"]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(lab >= 0, nll, 0.0))


# ---------------------------------------------------------------------------
# training: loss and gradient over row blocks, then one AdamW step


def loss_and_grad(params, batch, causal: bool, mode: str, rows: int):
    """Mean loss over the labelled positions of the whole batch and its
    gradient, accumulated over blocks of ``rows`` rows so that it fits."""
    B = batch["tokens"].shape[0]
    n = jnp.maximum(jnp.sum(batch["labels"] >= 0), 1).astype(jnp.float32)
    blocks = jax.tree.map(lambda x: x.reshape(B // rows, rows, *x.shape[1:]), batch)
    vg = jax.value_and_grad(lambda p, b: mlm_loss_sum(p, b, causal, mode) / n)

    def body(acc, b):
        l, g = vg(params, b)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grad), _ = jax.lax.scan(body, zero, blocks)
    return loss, grad


def adamw(params, grad, state, hp: Dict):
    """Global-norm clip, then AdamW with bias correction.  Weight decay
    reaches every leaf but the final LayerNorm, as the program's optimizer
    does (it decays each leaf of two or more dimensions, and per-layer leaves
    are stacked).  Returns (params, state, the clipped gradient)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grad)))
    grad = jax.tree.map(lambda g: g * jnp.minimum(1.0, hp["clip"] / jnp.maximum(gn, 1e-9)), grad)
    t = state["t"] + 1.0
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grad)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grad)
    decay = jax.tree.map(lambda _: hp["wd"], params)
    decay["lnf_s"] = decay["lnf_b"] = 0.0

    def upd(p, m, v, wd):
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"]) + wd * p
        return p - hp["lr"] * step

    params = jax.tree.map(upd, params, m, v, decay)
    return params, {"m": m, "v": v, "t": t}, grad


def adam_state(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# the V-cycle's coalescing operator (paper Alg. 2: "stack" width, "adj" depth)

# per-leaf role of each axis after the layer axis: "in" axes sum the pair
# (i, i + n/2), "out" axes average it, "-" is left alone (head width)
ROLES = {"ln1_s": "o", "ln1_b": "o", "ln2_s": "o", "ln2_b": "o",
         "wq": "io-", "wk": "io-", "wv": "io-", "bq": "o-", "bk": "o-", "bv": "o-",
         "wo": "i-o", "bo": "o", "w_up": "io", "b_up": "o", "w_down": "io", "b_down": "o"}


def _pairs(w, axis: int, role: str, dtype):
    n = w.shape[axis] // 2
    a = jax.lax.slice_in_dim(w, 0, n, axis=axis).astype(dtype)
    b = jax.lax.slice_in_dim(w, n, 2 * n, axis=axis).astype(dtype)
    s = a + b
    return (s * 0.5 if role == "o" else s).astype(jnp.float32)


def coalesce(params, dtype=jnp.float32):
    """Half width (pairs (i, i + n/2)) and half depth (adjacent layers);
    ``dtype`` is the precision the sums are taken in."""
    out = {"tok": _pairs(params["tok"], 1, "o", dtype),
           "lnf_s": _pairs(params["lnf_s"], 0, "o", dtype),
           "lnf_b": _pairs(params["lnf_b"], 0, "o", dtype), "layers": {}}
    for name, w in params["layers"].items():
        for ax, role in enumerate(ROLES[name], start=1):
            if role != "-":
                w = _pairs(w, ax, role, dtype)
        L = w.shape[0]
        w = w.reshape(L // 2, 2, *w.shape[1:]).astype(dtype)
        out["layers"][name] = ((w[:, 0] + w[:, 1]) * 0.5).astype(jnp.float32)
    return out
