"""Generic transformer LM / encoder.

One config covers the assigned LM pool (Mistral-Large, ChatGLM3, Gemma3,
Qwen3-MoE, Granite-MoE), BERT-style encoders (PreTTR's own model, BERT4Rec)
and is the substrate the PreTTR core plugs into.

Design notes
------------
* Parameters are stacked over layers (leading ``[L]`` axis) and the forward
  runs a ``lax.scan`` over layer groups — keeps HLO size (and CPU compile
  time for the 512-device dry-run) independent of depth.
* Per-layer heterogeneity (Gemma3's 5 local : 1 global attention, per-layer
  RoPE bases, PreTTR's split-mask boundary at layer ``l``) rides through the
  scan as traced per-layer scalars, so a single uniform scan body serves all
  architectures.
* ``remat="block"`` checkpoints groups of ``remat_block`` layers: activation
  memory is O(L / remat_block) layer inputs + one group of live activations.
* Decode keeps the KV cache stacked ``[L, B, S, Hkv, Dh]`` and sharded over
  the ``model`` axis on S (flash-decode style: GSPMD emits partial softmax +
  all-reduce).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.context import maybe_shard
from repro.models import backend as B
from repro.models import layers as L
from repro.models import moe as moe_lib

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str = "transformer"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 512
    vocab_size: int = 1024
    head_dim: int | None = None          # defaults to d_model // n_heads
    # --- attention ---
    causal: bool = True
    window_pattern: tuple[int, ...] = (-1,)   # cycled over layers; -1 = global
    window_size: int = 1024                   # width used where pattern > 0
    rope: bool = True
    rope_base: float = 1e4
    rope_base_local: float | None = None      # base for windowed (local) layers
    rope_fraction: float = 1.0                # ChatGLM "2d" RoPE: 0.5
    use_qk_norm: bool = False
    qkv_bias: bool = False
    attn_logit_softcap: float = 0.0
    # --- norms / mlp ---
    norm: str = "rmsnorm"                     # "rmsnorm" | "layernorm"
    gated_mlp: bool = True
    activation: str = "silu"
    use_post_norm: bool = False               # Gemma-style post-block norms
    mlp_bias: bool = False
    # --- embeddings ---
    scale_embeddings: bool = False            # Gemma: x *= sqrt(d)
    learned_pos: int = 0                      # >0: learned positions (BERT)
    segment_vocab: int = 0                    # >0: segment embeddings (BERT)
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # --- execution ---
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # compute backends (repro.models.backend registry):
    attn_impl: str = "blocked"                # "blocked" | "plain" | "pallas"
    compress_impl: str = "plain"              # "plain" | "pallas"
    block_kv: int = 512
    remat: str = "block"                      # "none" | "block"
    remat_block: int = 1                      # layers per scan group
    # residual-stream sharding between layers: "embed" (d_model over TP;
    # partial-sum all-reduces at full width) | "seq" (Megatron-style
    # sequence parallelism: cheaper redistributions) | "none"
    act_shard: str = "embed"
    logits_chunk: int = 0                     # chunk seq for the LM head
    # --- PreTTR hook: first `split_layers` layers mask query<->doc attention
    split_layers: int = 0

    def __post_init__(self):
        # unknown impl names must fail here, not fall through to a default
        # dispatch branch at trace time
        B.validate_config(self.attn_impl, self.compress_impl)

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def layer_windows(self) -> list[int]:
        pat = [w if w <= 0 else self.window_size for w in self.window_pattern]
        return [pat[i % len(pat)] for i in range(self.n_layers)]

    def layer_rope_bases(self) -> list[float]:
        local = self.rope_base_local if self.rope_base_local else self.rope_base
        return [local if w > 0 else self.rope_base for w in self.layer_windows()]

    def num_params(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS in the roofline)."""
        d, dh = self.d_model, self.dh
        attn = d * self.n_heads * dh * 2 + d * self.n_kv_heads * dh * 2
        if self.n_experts:
            ffn = self.n_experts * 3 * d * self.d_ff + d * self.n_experts
        else:
            ffn = (3 if self.gated_mlp else 2) * d * self.d_ff
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + ffn) + emb

    def num_active_params(self) -> int:
        d, dh = self.d_model, self.dh
        attn = d * self.n_heads * dh * 2 + d * self.n_kv_heads * dh * 2
        if self.n_experts:
            ffn = self.top_k * 3 * d * self.d_ff + d * self.n_experts
        else:
            ffn = (3 if self.gated_mlp else 2) * d * self.d_ff
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + ffn) + emb


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: TransformerConfig):
    ks = jax.random.split(key, 8)
    d, dh = cfg.d_model, cfg.dh
    dt = cfg.param_dtype
    attn = {
        "wq": L.dense_init(ks[0], d, cfg.n_heads * dh, dt),
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * dh, dt),
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * dh, dt),
        "wo": L.dense_init(ks[3], cfg.n_heads * dh, d, dt),
    }
    attn_ax = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
               "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.qkv_bias:
        for nm, width in (("bq", cfg.n_heads * dh), ("bk", cfg.n_kv_heads * dh),
                          ("bv", cfg.n_kv_heads * dh)):
            attn[nm] = jnp.zeros((width,), dt)
            attn_ax[nm] = ("heads",) if nm == "bq" else ("kv_heads",)
    if cfg.use_qk_norm:
        attn["q_norm"] = jnp.zeros((dh,), dt)
        attn["k_norm"] = jnp.zeros((dh,), dt)
        attn_ax["q_norm"] = (None,)
        attn_ax["k_norm"] = (None,)

    p = {"attn": attn}
    ax = {"attn": attn_ax}
    p["ln1"], ax["ln1"] = L.init_norm(ks[4], d, cfg.norm, dt)
    p["ln2"], ax["ln2"] = L.init_norm(ks[4], d, cfg.norm, dt)
    if cfg.use_post_norm:
        p["ln1_post"], ax["ln1_post"] = L.init_norm(ks[4], d, cfg.norm, dt)
        p["ln2_post"], ax["ln2_post"] = L.init_norm(ks[4], d, cfg.norm, dt)
    if cfg.n_experts:
        p["moe"], ax["moe"] = moe_lib.init_moe(ks[5], d, cfg.d_ff, cfg.n_experts, dt)
    else:
        p["mlp"], ax["mlp"] = L.init_mlp(ks[5], d, cfg.d_ff, gated=cfg.gated_mlp,
                                         dtype=dt, bias=cfg.mlp_bias)
    return p, ax


def init_params(key, cfg: TransformerConfig):
    """Returns (params, logical_axes). Layer params are stacked [L, ...]."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _init_layer(k, cfg)[0])(layer_keys)
    ax_box = {}

    def _shape_only(k):
        p, ax = _init_layer(k, cfg)
        ax_box["ax"] = ax
        return p

    jax.eval_shape(_shape_only, k_emb)
    layer_ax = jax.tree.map(lambda a: ("layers", *a), ax_box["ax"],
                            is_leaf=lambda x: isinstance(x, tuple))

    params = {"embed": {"tokens": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model,
                                               cfg.param_dtype)},
              "layers": stacked}
    axes = {"embed": {"tokens": ("vocab", "embed")}, "layers": layer_ax}
    if cfg.learned_pos:
        params["embed"]["pos"] = L.embed_init(k_emb, cfg.learned_pos, cfg.d_model,
                                              cfg.param_dtype)
        axes["embed"]["pos"] = (None, "embed")
    if cfg.segment_vocab:
        params["embed"]["segment"] = L.embed_init(k_emb, cfg.segment_vocab,
                                                  cfg.d_model, cfg.param_dtype)
        axes["embed"]["segment"] = (None, "embed")
    params["final_norm"], axes["final_norm"] = L.init_norm(k_head, cfg.d_model,
                                                           cfg.norm, cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                         cfg.param_dtype)
        axes["lm_head"] = ("embed", "vocab")
    return params, axes


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def project_q(p, x, cfg: TransformerConfig, *, positions, rope_base=None):
    """Q projection in model layout ``[B, S, Hq, Dh]`` (bias + qk-norm +
    RoPE applied exactly as inside an attention block)."""
    b, s, _ = x.shape
    dh = cfg.dh
    cd = cfg.compute_dtype
    q = (x @ p["wq"].astype(cd)).reshape(b, s, cfg.n_heads, dh)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd).reshape(cfg.n_heads, dh)
    if cfg.use_qk_norm:
        q = L.rms_norm(q, p["q_norm"])
    if cfg.rope:
        q = L.rope(q, positions,
                   base=cfg.rope_base if rope_base is None else rope_base,
                   fraction=cfg.rope_fraction)
    return q


def project_kv(p, x, cfg: TransformerConfig, *, positions, rope_base=None):
    """K/V projections in model layout ``[B, S, Hkv, Dh]`` — the
    query-invariant half of an attention block.  Shared by ``_attention``
    and PreTTR's index-time layer-``l`` doc K/V precompute
    (``repro.core.prettr.precompute_doc_kv``), so the stored streams are
    computed by the exact ops the query-time join would run."""
    b, s, _ = x.shape
    dh = cfg.dh
    cd = cfg.compute_dtype
    # one matmul for K and V: alone, a narrow (GQA) K or V width picks a
    # CPU dot kernel whose ulps depend on the row count, and the split
    # join projects each segment where the concat oracle projects both
    w = jnp.concatenate([p["wk"], p["wv"]], axis=1).astype(cd)
    kv = (x @ w).reshape(b, s, 2, cfg.n_kv_heads, dh)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cd).reshape(cfg.n_kv_heads, dh)
        v = v + p["bv"].astype(cd).reshape(cfg.n_kv_heads, dh)
    if cfg.use_qk_norm:
        k = L.rms_norm(k, p["k_norm"])
    if cfg.rope:
        k = L.rope(k, positions,
                   base=cfg.rope_base if rope_base is None else rope_base,
                   fraction=cfg.rope_fraction)
    return k, v


def _attention(p, x, cfg: TransformerConfig, *, positions, window, rope_base,
               split_flag, segs, valid, seg_boundary=-1, static_window=None,
               static_split=None, cache=None, cache_pos=None):
    """One attention block, dispatched through the compute-backend registry
    (``repro.models.backend``) selected by ``cfg.attn_impl``.  If
    ``cache=(k,v)`` is given, runs a decode step (x is [B, 1, d]) and
    returns the updated cache."""
    b, s, _ = x.shape
    dh = cfg.dh
    cd = cfg.compute_dtype

    q = project_q(p, x, cfg, positions=positions, rope_base=rope_base)
    k, v = project_kv(p, x, cfg, positions=positions, rope_base=rope_base)
    scale = 1.0 / math.sqrt(dh)

    new_cache = None
    if cache is not None:
        ck, cv = cache
        ck = lax.dynamic_update_slice(ck, k, (0, cache_pos, 0, 0))
        cv = lax.dynamic_update_slice(cv, v, (0, cache_pos, 0, 0))
        new_cache = (ck, cv)
        k_pos = jnp.broadcast_to(jnp.arange(ck.shape[1]), (b, ck.shape[1]))
        out = B.get_impl("decode_attention", cfg.attn_impl)(
            q, ck, cv, cfg=cfg, scale=scale, q_pos=positions, k_pos=k_pos,
            window=window, lengths=positions[:, 0] + 1,
            static_window=static_window)
    else:
        out = B.get_impl("attention", cfg.attn_impl)(
            q, k, v, cfg=cfg, scale=scale, positions=positions,
            window=window, split_flag=split_flag, segs=segs, valid=valid,
            seg_boundary=seg_boundary, static_window=static_window,
            static_split=static_split)
    out = out.reshape(b, s, cfg.n_heads * dh)
    proj = out @ p["wo"].astype(cd)
    return (proj, (k, v)) if cache is None else (proj, new_cache)


def block_tail(lp, cfg: TransformerConfig, x, attn_out):
    """Everything after attention in a transformer block — post-norms,
    residuals, MLP/MoE.  Returns (x, aux_loss).  The single definition of
    the block tail, shared by ``_layer_step`` and PreTTR's split-residual
    join layer (whose fused/legacy bit-exactness depends on them running
    identical ops)."""
    cd = cfg.compute_dtype
    if cfg.use_post_norm:
        attn_out = L.apply_norm(lp["ln1_post"], attn_out, cfg.norm)
    x = x + attn_out
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        b, s, d = h.shape
        moe_p = jax.tree.map(lambda a: a.astype(cd), lp["moe"])
        ff, aux = moe_lib.moe_ffn(moe_p, h.reshape(b * s, d),
                                  top_k=cfg.top_k,
                                  capacity_factor=cfg.capacity_factor)
        ff = ff.reshape(b, s, d)
    else:
        mlp_p = jax.tree.map(lambda a: a.astype(cd), lp["mlp"])
        ff = L.mlp(mlp_p, h, gated=cfg.gated_mlp, activation=cfg.activation)
    if cfg.use_post_norm:
        ff = L.apply_norm(lp["ln2_post"], ff, cfg.norm)
    return x + ff, aux


def _layer_step(lp, x, cfg: TransformerConfig, *, positions, window, rope_base,
                split_flag, segs, valid, seg_boundary=-1, static_window=None,
                static_split=None, cache=None, cache_pos=None):
    """Full transformer block. Returns (x, kv, aux_loss)."""
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    attn_out, kv = _attention(lp["attn"], h, cfg, positions=positions,
                              window=window, rope_base=rope_base,
                              split_flag=split_flag, segs=segs, valid=valid,
                              seg_boundary=seg_boundary,
                              static_window=static_window,
                              static_split=static_split,
                              cache=cache, cache_pos=cache_pos)
    x, aux = block_tail(lp, cfg, x, attn_out)
    return x, kv, aux


# ---------------------------------------------------------------------------
# Layer-scan driver
# ---------------------------------------------------------------------------


def _split_groups(tree, n_groups: int, g: int):
    """[L, ...] stacked tree -> ([n_groups, g, ...], tail=[L%g, ...])."""
    main = jax.tree.map(lambda a: a[: n_groups * g].reshape(n_groups, g, *a.shape[1:]),
                        tree)
    tail = jax.tree.map(lambda a: a[n_groups * g:], tree)
    return main, tail


def _run_layers(params, cfg: TransformerConfig, x, *, positions, segs, valid,
                collect_cache=False, cache=None, cache_pos=None,
                layer_slice: tuple[int, int] | None = None,
                seg_boundary: int = -1):
    """Scan over layer groups. Returns (x, stacked_kv_or_new_cache, aux).

    ``layer_slice=(lo, hi)`` runs only layers [lo, hi) — the PreTTR
    precompute (layers [0, l)) / join (layers [l, n)) split.
    ``seg_boundary`` is the static token index where segment 0 ends (the
    pallas backend's split-mask boundary; -1 = single segment)."""
    lo, hi = layer_slice or (0, cfg.n_layers)
    layer_params = jax.tree.map(lambda a: a[lo:hi], params["layers"])
    n_l = hi - lo
    if n_l == 0:      # empty range (e.g. PreTTR l=0 precompute): no scan —
        return x, None, jnp.zeros((), jnp.float32)   # nothing to trace
    g = max(1, min(cfg.remat_block, n_l))
    n_groups = n_l // g

    static_windows = cfg.layer_windows()[lo:hi]
    static_splits = [i < cfg.split_layers for i in range(cfg.n_layers)][lo:hi]
    # per-layer metadata rides through the scan as traced scalars; when a
    # range is uniform the *static* value is also known here and handed to
    # backends (pallas) that specialize their masks at trace time
    static_window = static_windows[0] if len(set(static_windows)) == 1 else None
    static_split = static_splits[0] if len(set(static_splits)) == 1 else None
    if cfg.attn_impl == "pallas" and (static_window is None
                                      or static_split is None):
        raise ValueError(
            f"attn_impl='pallas' requires a uniform window/split-flag per "
            f"layer range; layers [{lo}, {hi}) mix windows={static_windows} "
            f"splits={static_splits} — run heterogeneous layers via "
            f"separate layer_slice ranges or use attn_impl='blocked'")

    windows = jnp.asarray(static_windows, jnp.int32)
    bases = jnp.asarray(cfg.layer_rope_bases()[lo:hi], jnp.float32)
    splits = jnp.asarray(static_splits, bool)
    meta = (windows, bases, splits)

    def one_layer(lp, x, w, rb, sf, lcache):
        x, kv, a = _layer_step(lp, x, cfg, positions=positions, window=w,
                               rope_base=rb, split_flag=sf, segs=segs,
                               valid=valid, seg_boundary=seg_boundary,
                               static_window=static_window,
                               static_split=static_split,
                               cache=lcache, cache_pos=cache_pos)
        # residual-stream sharding: batch over DP/FSDP plus either d_model
        # (TP) or sequence (Megatron-SP) over the model axis — keeps saved
        # layer inputs (remat checkpoints) 16x smaller either way
        if cfg.act_shard == "seq":
            x = maybe_shard(x, ("batch", "act_seq", None))
        elif cfg.act_shard == "embed":
            x = maybe_shard(x, ("batch", None, "embed_tp"))
        return x, kv, a

    def group_body(carry, xs):
        x, aux = carry
        lp_g, (w_g, rb_g, sf_g), cache_g = xs
        kvs = []
        for i in range(lp_g["ln1"]["scale"].shape[0]):   # static group size
            lp = jax.tree.map(lambda a: a[i], lp_g)
            lcache = None if cache_g is None else tuple(
                jax.tree.map(lambda a: a[i], c) for c in cache_g)
            x, kv, a = one_layer(lp, x, w_g[i], rb_g[i], sf_g[i], lcache)
            aux = aux + a
            kvs.append(kv)
        ys = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs) \
            if (collect_cache or cache is not None) else None
        return (x, aux), ys

    if cfg.remat != "none":
        group_body = jax.checkpoint(group_body, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    main_p, tail_p = _split_groups(layer_params, n_groups, g)
    meta_main = tuple(m[: n_groups * g].reshape(n_groups, g) for m in meta)
    cache_main = cache_tail = None
    if cache is not None:
        cache_main, cache_tail = zip(*(_split_groups(c, n_groups, g) for c in cache))

    (x, aux), ys = lax.scan(group_body, (x, aux0),
                            (main_p, meta_main, cache_main))
    out_kv = None
    if ys is not None:
        out_kv = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)

    # tail (n_layers % remat_block) unrolled
    n_tail = n_l - n_groups * g
    if n_tail:
        tail_kvs = []
        for i in range(n_tail):
            lp = jax.tree.map(lambda a: a[i], tail_p)
            lcache = None if cache is None else tuple(
                jax.tree.map(lambda a: a[i], c) for c in cache_tail)
            x, kv, a = one_layer(lp, x, meta[0][n_groups * g + i],
                                 meta[1][n_groups * g + i],
                                 meta[2][n_groups * g + i], lcache)
            aux = aux + a
            tail_kvs.append(kv)
        if out_kv is not None:
            tail_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *tail_kvs)
            out_kv = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                  out_kv, tail_stack)
    return x, out_kv, aux


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def embed(params, cfg: TransformerConfig, tokens, positions, segs):
    x = params["embed"]["tokens"].astype(cfg.compute_dtype)[tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
    if cfg.learned_pos:
        x = x + params["embed"]["pos"].astype(cfg.compute_dtype)[positions]
    if cfg.segment_vocab and segs is not None:
        x = x + params["embed"]["segment"].astype(cfg.compute_dtype)[segs]
    return x


def forward(params, cfg: TransformerConfig, tokens, *, positions=None,
            segs=None, valid=None, collect_cache=False, seg_boundary=-1):
    """Full-sequence forward. Returns (hidden [B,S,d], kv_cache|None, aux)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed(params, cfg, tokens, positions, segs)
    x, kv, aux = _run_layers(params, cfg, x, positions=positions, segs=segs,
                             valid=valid, collect_cache=collect_cache,
                             seg_boundary=seg_boundary)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    return x, kv, aux


def run_layer_range(params, cfg: TransformerConfig, x, lo: int, hi: int, *,
                    positions, segs=None, valid=None, seg_boundary=-1):
    """Run layers [lo, hi) over already-embedded inputs ``x`` — the public
    hook PreTTR uses for precompute (0..l) and join (l..n).
    ``seg_boundary``: static segment-0 end index for the pallas split mask
    (-1 = single segment / split inactive)."""
    x, _, aux = _run_layers(params, cfg, x, positions=positions, segs=segs,
                            valid=valid, layer_slice=(lo, hi),
                            seg_boundary=seg_boundary)
    return x, aux


def logits(params, cfg: TransformerConfig, hidden):
    head = (params["embed"]["tokens"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.compute_dtype)
    return jnp.einsum("bsd,dv->bsv", hidden, head,
                      preferred_element_type=jnp.float32)


def causal_lm_loss(params, cfg: TransformerConfig, tokens, labels, *,
                   label_mask=None):
    """Next-token cross-entropy, seq-chunked so [B,S,V] logits never fully
    materialize (matters at vocab 262k)."""
    hidden, _, aux = forward(params, cfg, tokens)
    b, s, d = hidden.shape
    chunk = cfg.logits_chunk or s
    if s % chunk:
        chunk = s
    n_chunks = -(-s // chunk)
    head = (params["embed"]["tokens"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.compute_dtype)
    if label_mask is None:
        label_mask = jnp.ones((b, s), jnp.float32)

    hidden = hidden.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    labels_c = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    mask_c = label_mask.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def chunk_loss(carry, xs):
        h, y, m = xs
        lg = jnp.einsum("bsd,dv->bsv", h, head, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - gold) * m), None

    total, _ = lax.scan(jax.checkpoint(chunk_loss), jnp.zeros((), jnp.float32),
                        (hidden, labels_c, mask_c))
    loss = total / jnp.maximum(jnp.sum(label_mask), 1.0)
    return loss + 0.01 * aux / max(cfg.n_layers, 1)


def init_decode_cache(cfg: TransformerConfig, batch: int, max_len: int,
                      dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


DECODE_CACHE_AXES = ("layers", "batch", "kv_seq", None, None)


def decode_step(params, cfg: TransformerConfig, tokens, cache, cache_pos):
    """One decode step. tokens: [B, 1]; cache: (k, v) each [L,B,S,Hkv,Dh];
    cache_pos: scalar current length. Returns (logits [B,1,V], new_cache)."""
    b = tokens.shape[0]
    positions = jnp.full((b, 1), cache_pos, jnp.int32)
    x = embed(params, cfg, tokens, positions, None)
    x, new_cache, _ = _run_layers(params, cfg, x, positions=positions,
                                  segs=None, valid=None,
                                  cache=cache, cache_pos=cache_pos)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    return logits(params, cfg, x), new_cache
