"""tconst-41m (arXiv:2509.00202 section 6.2.1): its weights from the
seed, its plain reference, and the work it requires.

The model, as the paper describes it and as ``tconst-41m.json`` sizes
it.  Equivalent depth 8 = 2 blocks of ``h + 2 = 4`` layers; layer ``i``
of a block owns one attention and one SwiGLU parameter set, used by
every path at that depth.  Tokens are read in windows of ``W_og``:
position ``p`` belongs to window ``j = p // W_og``, its history is
positions ``[0, j W_og)`` and its generation window ``[j W_og, p]``.
For each block, given the block's history input ``hist``:

* context path, over the ``W_oh`` history positions before the window
  (the tail): layer 0 compresses (queries: the tail; keys: the whole
  history, causal), layers ``1..h`` self-attend over the tail (causal),
  giving ``C_0 .. C_h``; layer ``h + 1`` restores (queries: the whole
  history; keys: ``C_h`` at the tail's positions, causal) into the next
  block's history input;
* generation path, over the window: every layer self-attends
  causally; layers ``i >= 1`` also attend ``C_{i-1}`` (causal in true
  positions), through the same projections.

Pre-norm residual layers (RMSNorm), rotary positions at true token
positions, tied embedding as the output head.  A query with no key
allowed gives a zero attention output (the restore of history
positions before the tail; a window with no history).  Served tokens
are compared with this model's logits at every position.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import refmath as R


def _dims(c: dict):
    m = c["model"]
    depth = m["tconst"]["h"] + 2
    return dict(V=m["vocab_size"], D=m["d_model"], H=m["n_heads"],
                KV=m["n_kv_heads"], hd=m["head_dim"], F=m["d_ff"],
                nb=m["n_layers"] // depth, depth=depth, h=m["tconst"]["h"],
                W_oh=m["tconst"]["w_oh"], W_og=m["tconst"]["w_og"],
                theta=m["rope_theta"], eps=m["norm_eps"])


def weight_shapes(c: dict) -> dict:
    """The served parameter tree (``embed``, ``blocks`` stacked over
    blocks, ``final_norm``), each leaf as ``refmath.normal_weights``
    takes it."""
    d = _dims(c)
    D, H, KV, hd, F, nb = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["nb"]
    layer = {
        "attn": {"wq": ("proj", (nb, D, H, hd), D),
                 "wk": ("proj", (nb, D, KV, hd), D),
                 "wv": ("proj", (nb, D, KV, hd), D),
                 "wo": ("proj", (nb, H, hd, D), H * hd)},
        "ffn": {"w_gate": ("proj", (nb, D, F), D),
                "w_up": ("proj", (nb, D, F), D),
                "w_down": ("proj", (nb, F, D), F)},
        "ln1": {"scale": ("norm", (nb, D))},
        "ln2": {"scale": ("norm", (nb, D))},
    }
    return {"embed": {"tok": ("embed", (d["V"], D))},
            "blocks": {"layers": [layer] * d["depth"]},
            "final_norm": {"scale": ("norm", (D,))}}


def make_weights(key: jax.Array, c: dict) -> dict:
    return R.normal_weights(key, weight_shapes(c))


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def _attend(L, xq, xkv, qpos, kpos, allowed, theta, quant):
    q = R.rope(R.project(xq, L["attn"]["wq"], quant), qpos, theta)
    k = R.rope(R.project(xkv, L["attn"]["wk"], quant), kpos, theta)
    v = R.project(xkv, L["attn"]["wv"], quant)
    return R.attention(q, k, v, allowed)


def _window_logits(w, toks, j, c, quant):
    """Logits (W_og, V) at the positions of window ``j`` of ``toks``
    (T,), T a multiple of W_og; positions past the sequence are junk."""
    d = _dims(c)
    W, Woh, h, eps, th = d["W_og"], d["W_oh"], d["h"], d["eps"], d["theta"]
    T = toks.shape[0]
    emb = w["embed"]["tok"]
    pos = jnp.arange(T)
    n_hist = j * W
    hist_ok = pos < n_hist
    tpos = n_hist - Woh + jnp.arange(Woh)
    tail_ok = tpos >= 0
    gpos = n_hist + jnp.arange(W)
    hist = emb[toks]
    hg = emb[jax.lax.dynamic_slice_in_dim(toks, n_hist, W)]
    causal = lambda qp, kp: kp[None, :] <= qp[:, None]
    norm = lambda L, n, x: R.rmsnorm(x, L[n]["scale"], eps)
    tail_rope = jnp.maximum(tpos, 0)
    for b in range(d["nb"]):
        Ls = [jax.tree_util.tree_map(lambda a: a[b], L)
              for L in w["blocks"]["layers"]]
        # context path: compress, h tail self-attentions, restore
        L0 = Ls[0]
        tail_x = hist[jnp.clip(tpos, 0, T - 1)]
        cx = tail_x + R.unproject(_attend(
            L0, norm(L0, "ln1", tail_x), norm(L0, "ln1", hist), tail_rope,
            pos, causal(tpos, pos) & hist_ok[None], th, quant),
            L0["attn"]["wo"], quant)
        cx = cx + R.swiglu(norm(L0, "ln2", cx), L0["ffn"], quant)
        C = [cx]
        for i in range(1, h + 1):
            Li = Ls[i]
            xn = norm(Li, "ln1", cx)
            cx = cx + R.unproject(_attend(
                Li, xn, xn, tail_rope, tail_rope,
                causal(tpos, tpos) & tail_ok[None], th, quant),
                Li["attn"]["wo"], quant)
            cx = cx + R.swiglu(norm(Li, "ln2", cx), Li["ffn"], quant)
            C.append(cx)
        Lr = Ls[h + 1]
        r = hist + R.unproject(_attend(
            Lr, norm(Lr, "ln1", hist), norm(Lr, "ln1", cx), pos, tail_rope,
            causal(pos, tpos) & tail_ok[None], th, quant),
            Lr["attn"]["wo"], quant)
        restored = r + R.swiglu(norm(Lr, "ln2", r), Lr["ffn"], quant)
        # generation path over the window
        for i in range(h + 2):
            Li = Ls[i]
            xn = norm(Li, "ln1", hg)
            o = _attend(Li, xn, xn, gpos, gpos, causal(gpos, gpos), th,
                        quant)
            if i >= 1:
                o = o + _attend(Li, xn, norm(Li, "ln1", C[i - 1]), gpos,
                                tail_rope,
                                causal(gpos, tpos) & tail_ok[None], th,
                                quant)
            hg = hg + R.unproject(o, Li["attn"]["wo"], quant)
            hg = hg + R.swiglu(norm(Li, "ln2", hg), Li["ffn"], quant)
        hist = restored
    hg = R.rmsnorm(hg, w["final_norm"]["scale"], eps)
    return R.mm(hg, emb.T, quant)


@functools.lru_cache(maxsize=None)
def _jitted(key: str, quant: bool):
    c = {"model": json.loads(key)}
    return jax.jit(lambda w, toks, j: _window_logits(w, toks, j, c, quant))


def reference_blocks(w: dict, tokens: np.ndarray, first: int, c: dict,
                     pad_to: int, quant: bool = False):
    """Yield ``(p0, logits)``: the reference logits ``(n, V)`` at the
    positions ``p0 .. p0 + n - 1`` that cover ``[first, len(tokens))``,
    one generation window at a time.  ``tokens`` is padded to
    ``pad_to`` (a multiple of W_og) so every sequence shares one
    compiled program."""
    W = _dims(c)["W_og"]
    T = len(tokens)
    buf = np.zeros((pad_to,), np.int32)
    buf[:T] = tokens
    fn = _jitted(_key(c), quant)
    buf = jnp.asarray(buf)
    for j in range(first // W, (T - 1) // W + 1):
        yield j * W, fn(w, buf, jnp.int32(j))


def _key(c: dict) -> str:
    return json.dumps(c["model"], sort_keys=True)


# ---------------------------------------------------------------------------
# work required: matrix-product FLOPs (2 per multiply-add) as the model's
# dataflow needs them; attention counted over causal (query, key) pairs
# ---------------------------------------------------------------------------

def _per_token(d) -> float:
    """Projections and SwiGLU of one token through one layer."""
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return 2 * D * (H + 2 * KV) * hd + 2 * H * hd * D + 6 * D * F


def resync_flops(c: dict, n: np.ndarray) -> np.ndarray:
    """One synchronisation over ``n`` history positions (per row).  The
    context path's tail projections and the cached ``C_h`` keys and
    values count once; restores count for every block but the last,
    whose output nothing reads."""
    d = _dims(c)
    D, H, KV, hd, F, h = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["h"]
    n = np.asarray(n, np.float64)
    wv = np.minimum(n, d["W_oh"])
    pair = 4 * H * hd
    compress = (2 * D * H * hd * wv + 2 * D * 2 * KV * hd * n +
                pair * (wv * n - wv * (wv - 1) / 2) +
                2 * H * hd * D * wv + 6 * D * F * wv)
    tail = h * (_per_token(d) * wv + pair * wv * (wv + 1) / 2)
    ctx_kv = 2 * D * 2 * KV * hd * wv
    restore = (2 * D * H * hd * n + pair * wv * (wv + 1) / 2 +
               2 * H * hd * D * n + 6 * D * F * n)
    per_block = compress + tail + ctx_kv
    return np.where(n > 0, d["nb"] * per_block + (d["nb"] - 1) * restore,
                    0.0)


def _step_flops(d, g: np.ndarray, n_ctx: np.ndarray) -> np.ndarray:
    """Cache-hit work of one token at generation offset ``g`` (it attends
    ``g + 1`` window keys) with ``n_ctx`` valid context slots."""
    pair = 4 * d["H"] * d["hd"]
    per_block = d["depth"] * (_per_token(d) + pair * (g + 1)) + \
        (d["depth"] - 1) * pair * n_ctx
    return d["nb"] * per_block


def decode_flops(c: dict, p: np.ndarray) -> float:
    """Decode steps feeding positions ``p``: the cache-hit step, plus
    the synchronisation over ``p`` positions that precedes a step at a
    window boundary, plus the output head."""
    d = _dims(c)
    W = d["W_og"]
    p = np.asarray(p, np.int64)
    hist = (p // W) * W
    g = p - hist
    head = 2 * d["D"] * d["V"]
    total = _step_flops(d, g, np.minimum(hist, d["W_oh"])) + head
    total = total + np.where(g == 0, resync_flops(c, hist), 0.0)
    return float(np.sum(total))


def admit_flops(c: dict, prompt_len: int, resident_len: int) -> float:
    """Admission of a prompt: synchronise its history part, then its
    window part (1..W_og tokens) through the generation path; one row
    of the output head (the first token)."""
    d = _dims(c)
    W = d["W_og"]
    g0 = (prompt_len - 1) % W + 1
    hist = prompt_len - g0
    n_ctx = min(hist, d["W_oh"])
    pair = 4 * d["H"] * d["hd"]
    window = d["nb"] * (d["depth"] * (_per_token(d) * g0 +
                                      pair * g0 * (g0 + 1) / 2) +
                        (d["depth"] - 1) * pair * g0 * n_ctx)
    return float(resync_flops(c, np.array([hist]))[0] + window +
                 2 * d["D"] * d["V"])


def kernel_work(c: dict, kernel: str, p: np.ndarray):
    """(bytes, flops) a named kernel requires over decode steps feeding
    positions ``p``; None for a kernel this model does not run."""
    return None
