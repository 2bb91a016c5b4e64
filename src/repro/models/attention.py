"""GQA attention: training/prefill (flash-chunked) + paged decode paths.

Decode reads/writes the paged KV pool through ``shard_map``: the pool is
sharded over every mesh axis (the paper's page striping), each shard computes
partial online-softmax stats over the pages it owns, and partials are combined
with collectives — flash-decoding as "concurrent fine-grain reads of a striped
blob".
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.models.modules import dense_init
from repro.parallel.axisinfo import AxisInfo, constrain_batch, page_offset_in_shard



def _constrain_kv(x, axis_info: Optional[AxisInfo]):
    """Cache-bound K/V (B, S, K, hd): batch over DP axes, seq over the model
    axis — pre-aligns the layout with the page-pool striping so the
    prefill->pool reshard is local."""
    if axis_info is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = axis_info.mesh
    n = 1
    for a in axis_info.batch_axes:
        n *= mesh.shape[a]
    tp = mesh.shape[axis_info.model_axis]
    spec = [None, None, None, None]
    if x.shape[0] % n == 0:
        spec[0] = axis_info.batch_axes
    if x.shape[1] % tp == 0:
        spec[1] = axis_info.model_axis
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def attention_init(key, cfg: ModelConfig):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, H, K, hd, dt = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.pdtype()
    params = {
        "wq": dense_init(kq, d, (H, hd), dt),
        "wk": dense_init(kk, d, (K, hd), dt),
        "wv": dense_init(kv, d, (K, hd), dt),
        "wo": dense_init(ko, H * hd, (d,), dt).reshape(H, hd, d),
    }
    axes = {
        "wq": ("embed", "q_heads", "head"),
        "wk": ("embed", "kv_heads", "head"),
        "wv": ("embed", "kv_heads", "head"),
        "wo": ("q_heads", "head", "embed"),
    }
    return params, axes


def qkv(params, x: jnp.ndarray, cfg: ModelConfig, positions: Optional[jnp.ndarray], rope: bool = True):
    """Project + rotate. x: (B, S, d) → q (B,S,H,hd), k/v (B,S,K,hd)."""
    ct = cfg.cdtype()
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(ct))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(ct))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(ct))
    if rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_proj(params, o: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return jnp.einsum("...hk,hkd->...d", o, params["wo"].astype(cfg.cdtype()))


def attention_train(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    kv_src: Optional[jnp.ndarray] = None,
    rope: bool = True,
    return_kv: bool = False,
    axis_info: Optional[AxisInfo] = None,
):
    """Full-sequence attention (training / prefill / encoder / cross).

    ``kv_src`` switches to cross-attention (keys/values from another
    sequence, no RoPE, non-causal).
    """
    if kv_src is not None:
        ct = cfg.cdtype()
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(ct))
        k = jnp.einsum("bsd,dhk->bshk", kv_src, params["wk"].astype(ct))
        v = jnp.einsum("bsd,dhk->bshk", kv_src, params["wv"].astype(ct))
        causal, rope = False, False
    else:
        q, k, v = qkv(params, x, cfg, None, rope=rope)
    kv_cache = None
    if return_kv:
        # the CACHE copy gets the pool-aligned (batch, seq->model) layout.
        # The optimization barrier stops GSPMD from back-propagating the
        # seq-sharding through the QKV einsum into the residual stream
        # (measured without it: 48 GB/dev of f32 residual all-gathers on
        # danube prefill); the reshard then happens exactly once, on the
        # K/V tensors themselves.
        kb, vb = jax.lax.optimization_barrier((k, v))
        kv_cache = (_constrain_kv(kb, axis_info), _constrain_kv(vb, axis_info))
    o = ops.flash_attention(
        q, k, v,
        causal=causal,
        window=cfg.sliding_window if causal else None,
        q_chunk=cfg.attn_chunk,
        impl="pallas" if cfg.use_pallas else "xla",
    )
    out = out_proj(params, o, cfg)
    if return_kv:
        return out, kv_cache
    return out


# ------------------------------- paged decode --------------------------------

CacheLayer = Dict[str, jnp.ndarray]  # pool_k, pool_v, tables, page_pos


def decode_cache_specs(axis_info: Optional[AxisInfo]):
    """(in-)shardings of one cache layer pytree."""
    if axis_info is None:
        return {k: P() for k in ("pool_k", "pool_v", "tables", "page_pos")}
    return {
        "pool_k": P(axis_info.page_axes),
        "pool_v": P(axis_info.page_axes),
        "tables": P(),
        "page_pos": P(),
    }


def attention_decode(
    params,
    x: jnp.ndarray,  # (B, 1, d)
    cache: CacheLayer,
    lengths: jnp.ndarray,  # (B,) tokens already cached (new token position)
    cfg: ModelConfig,
    axis_info: Optional[AxisInfo],
    *,
    update: bool = True,
    rope: bool = True,
) -> Tuple[jnp.ndarray, CacheLayer]:
    """One decode step: append this token's K/V (paper WRITE), then attend over
    the paged pool (paper READ). ``update=False`` gives read-only attention
    (cross-attention over a prefilled pool)."""
    q, k, v = qkv(params, x, cfg, lengths[:, None] if rope else None, rope=rope)
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]  # (B, H/K, hd)

    impl = "pallas" if cfg.use_pallas else "xla"
    window = cfg.sliding_window

    quant = cache["pool_k"].dtype == jnp.int8
    if axis_info is None:
        pool_k, pool_v, page_pos = cache["pool_k"], cache["pool_v"], cache["page_pos"]
        sk, sv = cache.get("scale_k"), cache.get("scale_v")
        if update:
            out = ops.paged_update(
                pool_k, pool_v, cache["tables"], page_pos, lengths, k1, v1,
                scale_k=sk, scale_v=sv,
            )
            if quant:
                pool_k, pool_v, page_pos, sk, sv = out
            else:
                pool_k, pool_v, page_pos = out
        o = ops.paged_attention(
            q1, pool_k, pool_v, cache["tables"], page_pos,
            lengths + (1 if update else 0), scale_k=sk, scale_v=sv,
            window=window, impl=impl,
        )
        new_cache = dict(cache, pool_k=pool_k, pool_v=pool_v, page_pos=page_pos)
        if quant:
            new_cache.update(scale_k=sk, scale_v=sv)
        return out_proj(params, o[:, None], cfg), new_cache

    mesh = axis_info.mesh
    page_axes = axis_info.page_axes
    rep = P()  # replicated within shard_map

    sk = cache.get("scale_k") if quant else jnp.zeros((), jnp.float32)
    sv = cache.get("scale_v") if quant else jnp.zeros((), jnp.float32)

    def local(q1, k1, v1, pool_k, pool_v, sk, sv, tables, page_pos, lengths):
        offset = page_offset_in_shard(page_axes, pool_k.shape[0])
        if not quant:
            sk = sv = None
        if update:
            out = ops.paged_update(
                pool_k, pool_v, tables, page_pos, lengths, k1, v1,
                scale_k=sk, scale_v=sv, page_offset=offset,
            )
            if quant:
                pool_k, pool_v, page_pos_new, sk, sv = out
            else:
                pool_k, pool_v, page_pos_new = out
        else:
            page_pos_new = page_pos
        o = ops.paged_attention(
            q1, pool_k, pool_v, tables, page_pos_new,
            lengths + (1 if update else 0), scale_k=sk, scale_v=sv, window=window,
            page_offset=offset, axis_names=page_axes, impl=impl,
        )
        if not quant:
            sk = sv = jnp.zeros((), jnp.float32)
        # page_pos is replicated: every shard computes the same update
        return o, pool_k, pool_v, sk, sv, page_pos_new

    pool_spec = P(page_axes)
    scale_spec = pool_spec if quant else P()
    o, pool_k, pool_v, sk, sv, page_pos = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(rep, rep, rep, pool_spec, pool_spec, scale_spec, scale_spec, rep, rep, rep),
        out_specs=(rep, pool_spec, pool_spec, scale_spec, scale_spec, rep),
        check_vma=False,
    )(q1, k1, v1, cache["pool_k"], cache["pool_v"], sk, sv,
      cache["tables"], cache["page_pos"], lengths)
    new_cache = dict(cache, pool_k=pool_k, pool_v=pool_v, page_pos=page_pos)
    if quant:
        new_cache.update(scale_k=sk, scale_v=sv)
    return out_proj(params, o[:, None], cfg), new_cache


def init_decode_cache(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    n_layers: int,
    dtype=None,
    pad_pages_to: int = 1,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Allocate an empty paged cache for ``n_layers`` attention layers.

    With a sliding window the per-sequence ring is only ``window/T + 1`` pages
    (rolling buffer); otherwise ``seq_len/T`` pages. ``pad_pages_to`` pads the
    pool's page count for even sharding across ``page_axes``. Returns
    (cache, lengths); each cache leaf is stacked over layers:
    pool_k (L, P, T, K, hd).
    """
    T = cfg.kv_page_tokens
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    if cfg.sliding_window is not None and cfg.sliding_window < seq_len:
        ring = cfg.sliding_window // T + 1
    else:
        ring = max(seq_len // T, 1)
    n_pages = -(-(batch * ring) // pad_pages_to) * pad_pages_to
    K, hd = cfg.n_kv_heads, cfg.head_dim
    tables = jnp.arange(batch * ring, dtype=jnp.int32).reshape(batch, ring)
    page_pos = (jnp.arange(ring, dtype=jnp.int32) * T)[None, :].repeat(batch, 0)
    cache = {
        "pool_k": jnp.zeros((n_layers, n_pages, T, K, hd), dtype),
        "pool_v": jnp.zeros((n_layers, n_pages, T, K, hd), dtype),
        "tables": tables[None].repeat(n_layers, 0),
        "page_pos": page_pos[None].repeat(n_layers, 0),
    }
    if dtype == jnp.int8:  # per-(page, token, kv-head) dequant scales
        cache["scale_k"] = jnp.zeros((n_layers, n_pages, T, K), jnp.float32)
        cache["scale_v"] = jnp.zeros((n_layers, n_pages, T, K), jnp.float32)
    lengths = jnp.zeros((batch,), jnp.int32)
    return cache, lengths
