"""smollm-360m (HuggingFaceTB/SmolLM-360M): its weights from the seed,
its plain reference, and the work it requires.

A Llama-architecture decoder as the model card's ``config.json``
describes it: 32 pre-norm layers (RMSNorm), grouped-query attention (15
query heads over 5 key/value heads of 64) with rotary positions (theta
10000, the two halves of each head rotated against each other), SwiGLU
feed-forward of 2560, tied embedding as the output head.  Served tokens
are compared with this model's logits at every position.
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
    return dict(V=m["vocab_size"], D=m["d_model"], H=m["n_heads"],
                KV=m["n_kv_heads"], hd=m["head_dim"], F=m["d_ff"],
                L=m["n_layers"], theta=m["rope_theta"], eps=m["norm_eps"])


def weight_shapes(c: dict) -> dict:
    """The served parameter tree (``embed``, ``layers`` stacked over
    layers, ``final_norm``)."""
    d = _dims(c)
    D, H, KV, hd, F, L = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["L"]
    return {
        "embed": {"tok": ("embed", (d["V"], D))},
        "layers": {
            "ln1": {"scale": ("norm", (L, D))},
            "attn": {"wq": ("proj", (L, D, H, hd), D),
                     "wk": ("proj", (L, D, KV, hd), D),
                     "wv": ("proj", (L, D, KV, hd), D),
                     "wo": ("proj", (L, H, hd, D), H * hd)},
            "ln2": {"scale": ("norm", (L, D))},
            "ffn": {"w_gate": ("proj", (L, D, F), D),
                    "w_up": ("proj", (L, D, F), D),
                    "w_down": ("proj", (L, F, D), F)},
        },
        "final_norm": {"scale": ("norm", (D,))},
    }


def make_weights(key: jax.Array, c: dict) -> dict:
    return R.normal_weights(key, weight_shapes(c))


def _hidden(w, toks, c, quant):
    """Final hidden states (T, D) of the causal forward over ``toks``."""
    d = _dims(c)
    T = toks.shape[0]
    pos = jnp.arange(T)
    allowed = pos[None, :] <= pos[:, None]

    def layer(x, L):
        xn = R.rmsnorm(x, L["ln1"]["scale"], d["eps"])
        q = R.rope(R.project(xn, L["attn"]["wq"], quant), pos, d["theta"])
        k = R.rope(R.project(xn, L["attn"]["wk"], quant), pos, d["theta"])
        v = R.project(xn, L["attn"]["wv"], quant)
        x = x + R.unproject(R.attention(q, k, v, allowed),
                            L["attn"]["wo"], quant)
        x = x + R.swiglu(R.rmsnorm(x, L["ln2"]["scale"], d["eps"]),
                         L["ffn"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, w["embed"]["tok"][toks], w["layers"])
    return R.rmsnorm(x, w["final_norm"]["scale"], d["eps"])


@functools.lru_cache(maxsize=None)
def _jitted(key: str, quant: bool, block: int):
    c = {"model": json.loads(key)}
    hidden = jax.jit(lambda w, toks: _hidden(w, toks, c, quant))
    head = jax.jit(lambda w, hs, i: R.mm(
        jax.lax.dynamic_slice_in_dim(hs, i, block), w["embed"]["tok"].T,
        quant))
    return hidden, head


def reference_blocks(w: dict, tokens: np.ndarray, first: int, c: dict,
                     pad_to: int, quant: bool = False):
    """Yield ``(p0, logits)``: the reference logits ``(block, V)`` at
    positions ``p0 ..`` covering ``[first, len(tokens))``, ``block`` =
    min(BLOCK, pad_to).  ``tokens`` is padded to ``pad_to`` (a multiple
    of the block) so every sequence shares one compiled program; padding
    lies after every compared position, so causality keeps it out."""
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
# causal (query, key) pairs; bytes of the key/value cache each step reads
# ---------------------------------------------------------------------------

def _per_token(d) -> float:
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return 2 * D * (H + 2 * KV) * hd + 2 * H * hd * D + 6 * D * F


def decode_flops(c: dict, p: np.ndarray) -> float:
    """Decode steps feeding positions ``p`` (each attends ``p + 1``
    keys), with the output head."""
    d = _dims(c)
    p = np.asarray(p, np.float64)
    per = d["L"] * (_per_token(d) + 4 * d["H"] * d["hd"] * (p + 1)) + \
        2 * d["D"] * d["V"]
    return float(np.sum(per))


def admit_flops(c: dict, prompt_len: int, resident_len: int) -> float:
    """Admission forwarding positions ``[resident_len, prompt_len)`` (the
    rest is attended from adopted pages), with one row of the output
    head."""
    d = _dims(c)
    t = np.arange(resident_len, prompt_len, dtype=np.float64)
    per = d["L"] * (_per_token(d) + 4 * d["H"] * d["hd"] * (t + 1))
    return float(np.sum(per) + 2 * d["D"] * d["V"])


KV_BYTES = 2          # the cache holds keys and values in bfloat16


def kernel_work(c: dict, kernel: str, p: np.ndarray):
    """(bytes, flops) that ``paged_decode_attention`` requires over
    decode steps feeding positions ``p``, every layer: each step reads
    the ``p + 1`` live keys and values of its slot, its query and the
    page-table entries of its live pages, and writes its output."""
    if kernel != "paged_decode_attention":
        return None
    d = _dims(c)
    page = c["serving"]["page_size"]
    p = np.asarray(p, np.float64)
    live = p + 1
    kv = 2 * live * d["KV"] * d["hd"] * KV_BYTES
    qo = 2 * d["H"] * d["hd"] * KV_BYTES
    table = 4 * np.ceil(live / page)
    nbytes = d["L"] * np.sum(kv + qo + table)
    flops = d["L"] * np.sum(4 * d["H"] * d["hd"] * live)
    return float(nbytes), float(flops)
