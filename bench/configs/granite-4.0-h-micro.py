"""granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro): its weights from
the seed, its plain reference, and the work it requires.

A hybrid decoder as the model's ``config.json`` (model_type
granitemoehybrid) describes it.  Layers of two kinds in the published
order (``layer_types``): Mamba-2 mixers and grouped-query attention, each
followed by a SwiGLU MLP, every branch pre-normed (RMSNorm) and scaled by
``residual_multiplier`` before its residual add.  The token embedding is
multiplied by ``embedding_multiplier``; the tied output head divides the
logits by ``logits_scaling``.

* Attention: 32 query heads over 8 key/value heads of 64, no positional
  encoding (``position_embedding_type: nope``), scores scaled by
  ``attention_multiplier`` (1/64) instead of 1/sqrt(64).
* Mamba-2 mixer: one input projection to (z, x, B, C, dt) with no bias;
  a depthwise causal convolution of width 4 with bias over (x, B, C),
  then SiLU; per head h a scalar decay ``A = -exp(A_log)`` and step
  ``dt = softplus(dt + dt_bias)``; the recurrence over positions,
  ``state <- exp(dt A) state + dt x B^T`` and ``y = state C + D x``, with
  one group of B and C shared by the 64 heads of 64 and a state of 128;
  then ``RMSNorm(y * silu(z))`` and the output projection.

The mixer is computed here as that recurrence, one position after the
other (``lax.scan`` over time), not by the chunked algorithm the
program runs.  Departures from the published model: none besides the
depth the configuration file states.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import refmath as R

BLOCK = 256          # logits rows per block handed to the comparison


def _dims(c: dict):
    m = c["model"]
    kinds = tuple(m["layer_pattern"])
    d_inner = m["ssm_expand"] * m["d_model"]
    return dict(V=m["vocab_size"], D=m["d_model"], H=m["n_heads"],
                KV=m["n_kv_heads"], hd=m["head_dim"], F=m["d_ff"],
                kinds=kinds, n_mamba=kinds.count("mamba"),
                n_attn=kinds.count("attention"), DI=d_inner,
                SH=m["ssm_heads"], P=m["ssm_head_dim"], N=m["ssm_state"],
                K=m["ssm_conv"], conv=d_inner + 2 * m["ssm_state"],
                eps=m["norm_eps"], scale=m["attention_multiplier"],
                emb=m["embedding_multiplier"],
                res=m["residual_multiplier"], logits=m["logits_scaling"])


def weight_shapes(c: dict) -> dict:
    """The served parameter tree: ``embed``, each kind's layers stacked
    (``mamba_layers``, ``attention_layers``), ``final_norm``.  Leaves
    drawn from the Mamba-2 initialisation instead are marked
    ``("mamba", ...)`` (see :func:`make_weights`)."""
    d = _dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    M, A = d["n_mamba"], d["n_attn"]

    def mlp(L):
        return {"w_gate": ("proj", (L, D, F), D),
                "w_up": ("proj", (L, D, F), D),
                "w_down": ("proj", (L, F, D), F)}

    proj = 2 * d["DI"] + 2 * d["N"] + d["SH"]
    return {
        "embed": {"tok": ("embed", (d["V"], D))},
        "mamba_layers": {
            "ln1": {"scale": ("norm", (M, D))},
            "ssm": {"in_proj": ("proj", (M, D, proj), D),
                    "conv_w": ("proj", (M, d["K"], d["conv"]), d["K"]),
                    "conv_b": ("mamba", "conv_b", (M, d["conv"])),
                    "dt_bias": ("mamba", "dt_bias", (M, d["SH"])),
                    "a_log": ("mamba", "a_log", (M, d["SH"])),
                    "d_skip": ("norm", (M, d["SH"])),
                    "norm_scale": ("norm", (M, d["DI"])),
                    "out_proj": ("proj", (M, d["DI"], D), d["DI"])},
            "ln2": {"scale": ("norm", (M, D))},
            "ffn": mlp(M),
        },
        "attention_layers": {
            "ln1": {"scale": ("norm", (A, D))},
            "attn": {"wq": ("proj", (A, D, H, hd), D),
                     "wk": ("proj", (A, D, KV, hd), D),
                     "wv": ("proj", (A, D, KV, hd), D),
                     "wo": ("proj", (A, H, hd, D), H * hd)},
            "ln2": {"scale": ("norm", (A, D))},
            "ffn": mlp(A),
        },
        "final_norm": {"scale": ("norm", (D,))},
    }


def _mamba_leaf(key: jax.Array, which: str, shape) -> jax.Array:
    """Mamba-2's own initialisation: ``dt_bias`` the inverse softplus of
    steps drawn log-uniform in [1e-3, 1e-1], ``A_log`` the log of decay
    rates uniform in [1, 16], the conv bias small."""
    if which == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if which == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def make_weights(key: jax.Array, c: dict) -> dict:
    """The weights from the seed.  The token embedding is drawn at
    0.02 / ``embedding_multiplier``, so that the embedding the layers
    see has the 0.02 scale of the other configurations: drawn at 0.02
    and multiplied by 12 it outweighs every branch of a random model,
    which then predicts its own input token (99% of positions at
    d_model 512) and decodes one token over and over, on which no
    comparison can fail."""
    shapes = weight_shapes(c)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], str)  # noqa
    leaves, tree = jax.tree_util.tree_flatten(shapes, is_leaf=is_leaf)
    ka, kb = jax.random.split(key)
    plain = R.normal_weights(ka, jax.tree_util.tree_unflatten(
        tree, [("norm", leaf[2]) if leaf[0] == "mamba" else leaf
               for leaf in leaves]))
    out = jax.tree_util.tree_leaves(plain)
    keys = jax.random.split(kb, len(leaves))
    for i, leaf in enumerate(leaves):
        if leaf[0] == "mamba":
            out[i] = _mamba_leaf(keys[i], leaf[1], leaf[2])
    w = jax.tree_util.tree_unflatten(tree, out)
    w["embed"]["tok"] = w["embed"]["tok"] / _dims(c)["emb"]
    return w


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------

def _conv(xbc, w, b):
    """Depthwise causal convolution over positions, then SiLU.
    ``xbc (T, C)``, ``w (K, C)``: output t sees inputs t-K+1 .. t."""
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    out = sum(xp[i:i + xbc.shape[0]] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def _recurrence(x, dt, a, b, c):
    """The Mamba-2 recurrence, one position at a time.  ``x (T, H, P)``,
    ``dt (T, H)``, ``a (H,)`` (negative), ``b, c (T, N)``; returns
    ``y (T, H, P)`` without the skip."""
    H, P, N = x.shape[1], x.shape[2], b.shape[1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, b, c))
    return y


def _mamba(xn, L, d, quant):
    """Mamba-2 mixer over the whole sequence ``xn (T, D)``."""
    T, DI, N, SH, P = xn.shape[0], d["DI"], d["N"], d["SH"], d["P"]
    zxbcdt = R.mm(xn, L["in_proj"], quant)
    z = zxbcdt[:, :DI]
    xbc = _conv(zxbcdt[:, DI:DI + d["conv"]], L["conv_w"], L["conv_b"])
    dt = jax.nn.softplus(zxbcdt[:, DI + d["conv"]:] + L["dt_bias"])
    xs = xbc[:, :DI].reshape(T, SH, P)
    b, c = xbc[:, DI:DI + N], xbc[:, DI + N:]
    y = _recurrence(xs, dt, -jnp.exp(L["a_log"]), b, c)
    y = (y + xs * L["d_skip"][None, :, None]).reshape(T, DI)
    g = R.rmsnorm(y * jax.nn.silu(z), L["norm_scale"], d["eps"])
    return R.mm(g, L["out_proj"], quant)


def _attention(xn, L, d, quant, allowed):
    """NoPE grouped-query attention; ``R.attention`` divides the scores
    by sqrt(hd), so the query carries the rest of the published scale."""
    q = R.project(xn, L["wq"], quant) * (d["scale"] * np.sqrt(d["hd"]))
    k = R.project(xn, L["wk"], quant)
    v = R.project(xn, L["wv"], quant)
    return R.unproject(R.attention(q, k, v, allowed), L["wo"], quant)


def _hidden(w, toks, c, quant):
    """Final hidden states (T, D) of the causal forward over ``toks``,
    layer by layer in the published order."""
    d = _dims(c)
    T = toks.shape[0]
    pos = jnp.arange(T)
    allowed = pos[None, :] <= pos[:, None]
    x = w["embed"]["tok"][toks] * d["emb"]
    seen = {"mamba": 0, "attention": 0}
    for kind in d["kinds"]:
        i = seen[kind]
        seen[kind] += 1
        L = jax.tree_util.tree_map(lambda a: a[i], w[kind + "_layers"])
        xn = R.rmsnorm(x, L["ln1"]["scale"], d["eps"])
        if kind == "mamba":
            y = _mamba(xn, L["ssm"], d, quant)
        else:
            y = _attention(xn, L["attn"], d, quant, allowed)
        x = x + d["res"] * y
        x = x + d["res"] * R.swiglu(R.rmsnorm(x, L["ln2"]["scale"],
                                              d["eps"]), L["ffn"], quant)
    return R.rmsnorm(x, w["final_norm"]["scale"], d["eps"])


@functools.lru_cache(maxsize=None)
def _jitted(key: str, quant: bool, block: int):
    c = {"model": json.loads(key)}
    div = _dims(c)["logits"]
    hidden = jax.jit(lambda w, toks: _hidden(w, toks, c, quant))
    head = jax.jit(lambda w, hs, i: R.mm(
        jax.lax.dynamic_slice_in_dim(hs, i, block), w["embed"]["tok"].T,
        quant) / div)
    return hidden, head


def reference_blocks(w: dict, tokens: np.ndarray, first: int, c: dict,
                     pad_to: int, quant: bool = False):
    """Yield ``(p0, logits)``: the reference logits ``(block, V)`` at
    positions ``p0 ..`` covering ``[first, len(tokens))``, ``block`` =
    min(BLOCK, pad_to).  ``tokens`` is padded to ``pad_to`` (a multiple
    of the block) so every sequence shares one compiled program; padding
    lies after every compared position, and neither attention nor the
    recurrence carries anything backwards, so it changes nothing
    compared."""
    block = min(BLOCK, pad_to)
    hidden, head = _jitted(json.dumps(c["model"], sort_keys=True), quant,
                           block)
    T = len(tokens)
    buf = np.zeros((pad_to,), np.int32)
    buf[:T] = tokens
    hs = hidden(w, jnp.asarray(buf))
    for p0 in range(first // block * block, T, block):
        yield p0, head(w, hs, jnp.int32(p0))


# ---------------------------------------------------------------------------
# work required: matrix-product FLOPs (2 per multiply-add); attention over
# causal (query, key) pairs; the Mamba-2 depthwise convolution and its
# state update and read-out (x B^T into the state, state C), 2 per
# multiply-add; bytes of the key/value cache each step reads
# ---------------------------------------------------------------------------

def _mamba_token(d) -> float:
    D, DI, F = d["D"], d["DI"], d["F"]
    proj = 2 * DI + 2 * d["N"] + d["SH"]
    return (2 * D * proj + 2 * d["K"] * d["conv"] + 4 * d["SH"] * d["P"] *
            d["N"] + 2 * DI * D + 6 * D * F)


def _attn_token(d) -> float:
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return 2 * D * (H + 2 * KV) * hd + 2 * H * hd * D + 6 * D * F


def _per_positions(d, p: np.ndarray) -> float:
    """Every layer at positions ``p`` (each attends ``p + 1`` keys)."""
    p = np.asarray(p, np.float64)
    per = d["n_mamba"] * _mamba_token(d) + d["n_attn"] * (
        _attn_token(d) + 4 * d["H"] * d["hd"] * (p + 1))
    return float(np.sum(per))


def decode_flops(c: dict, p: np.ndarray) -> float:
    """Decode steps feeding positions ``p``, with the output head."""
    d = _dims(c)
    return _per_positions(d, p) + len(p) * 2 * d["D"] * d["V"]


def admit_flops(c: dict, prompt_len: int, resident_len: int) -> float:
    """Admission forwarding positions ``[resident_len, prompt_len)``, with
    one row of the output head (the recurrence cannot start mid-prompt,
    so with no prefix sharing ``resident_len`` is 0)."""
    d = _dims(c)
    t = np.arange(resident_len, prompt_len, dtype=np.float64)
    return _per_positions(d, t) + 2 * d["D"] * d["V"]


KV_BYTES = 2          # the cache holds keys and values in bfloat16


def kernel_work(c: dict, kernel: str, p: np.ndarray):
    """(bytes, flops) that ``paged_decode_attention`` requires over
    decode steps feeding positions ``p``, every attention layer: each
    step reads the ``p + 1`` live keys and values of its slot, its query
    and the page-table entries of its live pages, and writes its
    output."""
    if kernel != "paged_decode_attention":
        return None
    d = _dims(c)
    page = c["serving"]["page_size"]
    p = np.asarray(p, np.float64)
    live = p + 1
    kv = 2 * live * d["KV"] * d["hd"] * KV_BYTES
    qo = 2 * d["H"] * d["hd"] * KV_BYTES
    table = 4 * np.ceil(live / page)
    nbytes = d["n_attn"] * np.sum(kv + qo + table)
    flops = d["n_attn"] * np.sum(4 * d["H"] * d["hd"] * live)
    return float(nbytes), float(flops)
