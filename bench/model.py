"""The ranker's weights and its plain reference, independent of the program.

:func:`make_weights` draws a PreTTR BERT ranker's parameters from the seed
in one jitted call on the device, in float32 (the type the program keeps
its parameters in; it casts to its compute dtype at each use).  The tree
follows the program's parameter naming (``backbone`` / ``score_head`` /
``compressor``) so the program can be handed it; nothing here imports the
program.  Biases and norm parameters are drawn too (not left at 0 and 1),
so a path that drops one cannot agree with the reference.

:func:`score_pairs` is the reference: the joint ``[CLS] q [SEP] ; d [SEP]``
forward of the configuration's ranker in float32 with
``precision="highest"`` matmuls, written out layer by layer:

* embeddings: token + learned position + segment;
* pre-norm blocks (LayerNorm, eps from the configuration) with biased
  Q/K/V, an unbiased output projection and a tanh-GELU MLP;
* layers ``0..l-1`` attend within their own segment only (the split mask
  that makes index-time precomputation sound);
* after layer ``l-1`` the document positions go through the compressor
  (``gelu(x W_c + b_c)`` stored as float16, then ``LayerNorm(r W_d + b_d)``);
* layers ``l..n-2`` attend over every valid key; the last layer computes
  only the [CLS] row, which is all the score reads;
* score = ``LayerNorm_final(cls) . w_score``.

``mm_dtype`` rounds both operands of every matmul (and of the attention
products) to that dtype first, with float32 accumulation; ``kv_bits``
quantizes the document keys and values of layer ``l`` per token to that
many bits (absmax, as an int8 K/V index stores them).  With the
configuration's own precisions (bfloat16, int8 K/V) they give the
stated-precision scores the comparison measures rounding noise by; one
step lower (fp8, int4 K/V) they give the controls.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def arch_of(cfg: dict) -> tuple:
    """The hashable architecture tuple the jitted functions specialize on."""
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["max_position_embeddings"],
            cfg["type_vocab_size"], cfg["split_layer"], cfg["compress_dim"],
            float(cfg["layer_norm_eps"]))


def seed_key(seed: int):
    """A PRNG key from any whole seed (more than 32 bits are folded in)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=1)
def _weights(key, arch):
    n, d, _, dff, vocab, max_pos, n_seg, _, e, _ = arch
    ks = iter(jax.random.split(key, 32))

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def small(k, shape, scale=0.02):
        return jax.random.normal(k, shape, jnp.float32) * scale

    def norm(k1, k2, shape):
        return {"scale": 1.0 + small(k1, shape, 0.05),
                "bias": small(k2, shape, 0.05)}

    layers = {
        "attn": {"wq": dense(next(ks), (n, d, d), d),
                 "wk": dense(next(ks), (n, d, d), d),
                 "wv": dense(next(ks), (n, d, d), d),
                 "wo": dense(next(ks), (n, d, d), d),
                 "bq": small(next(ks), (n, d)),
                 "bk": small(next(ks), (n, d)),
                 "bv": small(next(ks), (n, d))},
        "ln1": norm(next(ks), next(ks), (n, d)),
        "ln2": norm(next(ks), next(ks), (n, d)),
        "mlp": {"w_in": dense(next(ks), (n, d, dff), d),
                "b_in": small(next(ks), (n, dff)),
                "w_out": dense(next(ks), (n, dff, d), dff),
                "b_out": small(next(ks), (n, d))},
    }
    w = {"backbone": {"embed": {"tokens": small(next(ks), (vocab, d)),
                                "pos": small(next(ks), (max_pos, d)),
                                "segment": small(next(ks), (n_seg, d))},
                      "layers": layers,
                      "final_norm": norm(next(ks), next(ks), (d,))},
         "score_head": dense(next(ks), (d, 1), d)}
    if e:
        w["compressor"] = {"w_comp": dense(next(ks), (d, e), d),
                           "b_comp": small(next(ks), (e,)),
                           "w_decomp": dense(next(ks), (e, d), e),
                           "b_decomp": small(next(ks), (d,)),
                           "ln": norm(next(ks), next(ks), (d,))}
    return w


def make_weights(seed: int, cfg: dict, device=None):
    """The ranker's float32 weights from ``seed``, made on ``device`` (the
    default device when None) in one jitted call."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _weights(key, arch_of(cfg))


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _round(x, mm_dtype):
    if mm_dtype is None or mm_dtype == jnp.float32:
        return x
    return x.astype(mm_dtype).astype(jnp.float32)


def _quant(x, bits):
    """Per-token absmax quantization to ``bits`` (symmetric), dequantized."""
    top = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-12) / top
    return jnp.clip(jnp.round(x / s), -top, top) * s


@functools.partial(jax.jit, static_argnums=(1, 5, 6))
def _score_block(w, arch, tokens, segs, valid, mm_dtype, kv_bits):
    n, d, h, _, _, _, _, l, e, eps = arch
    dh = d // h
    b, s = tokens.shape

    def mm(x, y):
        return jnp.matmul(_round(x, mm_dtype), _round(y, mm_dtype),
                          precision="highest")

    def ein(spec, x, y):
        return jnp.einsum(spec, _round(x, mm_dtype), _round(y, mm_dtype),
                          precision="highest")

    emb = w["backbone"]["embed"]
    x = (emb["tokens"][tokens] + emb["pos"][jnp.arange(s)][None]
         + emb["segment"][segs])
    lay = w["backbone"]["layers"]
    same_seg = segs[:, :, None] == segs[:, None, :]
    is_doc = (segs == 1)[..., None]

    def attend(lp, hq, hkv, mask, li):
        q = (mm(hq, lp["attn"]["wq"]) + lp["attn"]["bq"])
        k = (mm(hkv, lp["attn"]["wk"]) + lp["attn"]["bk"])
        v = (mm(hkv, lp["attn"]["wv"]) + lp["attn"]["bv"])
        if kv_bits and li == l:
            k = jnp.where(is_doc, _quant(k, kv_bits), k)
            v = jnp.where(is_doc, _quant(v, kv_bits), v)
        sq = hq.shape[1]
        q = q.reshape(b, sq, h, dh)
        k = k.reshape(b, s, h, dh)
        v = v.reshape(b, s, h, dh)
        logits = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        logits = jnp.where(mask[:, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = ein("bhqk,bkhd->bqhd", p, v).reshape(b, sq, d)
        return mm(out, lp["attn"]["wo"])

    def mlp(lp, x):
        hid = _gelu(mm(x, lp["mlp"]["w_in"]) + lp["mlp"]["b_in"])
        return mm(hid, lp["mlp"]["w_out"]) + lp["mlp"]["b_out"]

    for li in range(n - 1):
        lp = jax.tree.map(lambda a: a[li], lay)
        mask = valid[:, None, :] & (same_seg if li < l else True)
        hx = _ln(x, lp["ln1"], eps)
        x = x + attend(lp, hx, hx, mask, li)
        x = x + mlp(lp, _ln(x, lp["ln2"], eps))
        if li == l - 1 and e:
            c = w["compressor"]
            r = _gelu(mm(x, c["w_comp"]) + c["b_comp"])
            r = r.astype(jnp.float16).astype(jnp.float32)
            x_hat = _ln(mm(r, c["w_decomp"]) + c["b_decomp"], c["ln"], eps)
            x = jnp.where(is_doc, x_hat, x)
    lp = jax.tree.map(lambda a: a[n - 1], lay)
    hx = _ln(x, lp["ln1"], eps)
    cls = x[:, :1] + attend(lp, hx[:, :1], hx, valid[:, None, :], n - 1)
    cls = cls + mlp(lp, _ln(cls, lp["ln2"], eps))
    hf = _ln(cls[:, 0], w["backbone"]["final_norm"], eps)
    return mm(hf, w["score_head"])[:, 0]


def pack_pairs(query, docs, max_q: int, max_d: int):
    """Joint inputs for one query against ``docs`` -> tokens, segs, valid
    each ``[len(docs), max_q + max_d]``."""
    from traffic import pack_doc, pack_query

    qt, qv = pack_query(query, max_q)
    packed = [pack_doc(dd, max_d) for dd in docs]
    n = len(docs)
    tokens = np.concatenate([np.repeat(qt[None], n, 0),
                             np.stack([t for t, _ in packed])], 1)
    valid = np.concatenate([np.repeat(qv[None], n, 0),
                            np.stack([v for _, v in packed])], 1)
    segs = np.concatenate([np.zeros((n, max_q), np.int32),
                           np.ones((n, max_d), np.int32)], 1)
    return tokens, segs, valid


def score_pairs(w, cfg: dict, query, docs, *, block: int = 20,
                mm_dtype=None, kv_bits: int = 0) -> np.ndarray:
    """Reference scores of ``query`` against each of ``docs`` (raw token
    ids) -> ``[len(docs)]`` float32, computed ``block`` pairs at a time so
    it fits beside nothing else on the chip."""
    arch = arch_of(cfg)
    tokens, segs, valid = pack_pairs(query, docs, cfg["max_query_len"],
                                     cfg["max_doc_len"])
    n = len(docs)
    pad = (-n) % block
    if pad:
        tokens, segs, valid = (np.concatenate([a, np.repeat(a[-1:], pad, 0)])
                               for a in (tokens, segs, valid))
    out = [np.asarray(_score_block(w, arch, tokens[i:i + block],
                                   segs[i:i + block], valid[i:i + block],
                                   mm_dtype, kv_bits))
           for i in range(0, n + pad, block)]
    return np.concatenate(out)[:n].astype(np.float32)
