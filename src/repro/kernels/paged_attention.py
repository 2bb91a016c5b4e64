"""Paged decode attention Pallas TPU kernel — the paper's striped-page READ
fused with attention.

Grid ``(B,)``: one program per sequence. The page table, page positions and
lengths are scalar-prefetched into SMEM, so the kernel knows every page id
before it starts. The pool stays in HBM (``pl.ANY``: it is far too large for
VMEM; this indirection IS the paper's fine-grain remote read). A
``fori_loop`` walks the sequence's table and copies each live page — all
``K`` kv-heads, a ``page_tokens × (K·head_dim)`` slab — from HBM into a
double-buffered VMEM scratch with ``make_async_copy``: the copy of page
``r + 1`` is in flight while page ``r`` is attended. Pages that are out of
this shard's range, or hold no token inside ``[lo, length)``, are neither
copied nor computed.

The wrapper views each page as ``(T, K·D)`` (lane-dense, no tile padding)
and hands the kernel block-diagonal queries ``(H, K·D)``: query head ``h``
holds its vector in the columns of its own kv-head ``h // G`` and zeros
elsewhere. One ``(H, K·D) × (K·D, T)`` matmul then gives every head's
scores, and ``(H, T) × (T, K·D)`` its values; the wrapper keeps each head's
own ``D`` columns. Decode is bound by the page copies, so the ``K``-fold
extra MXU work costs little and keeps every operand 2-D.

The kernel emits *unnormalized* ``(o, m, l)`` so the shard_map wrapper can
split-K combine partial results across pool shards (flash-decoding), exactly
like the XLA path in ``ops._paged_local_xla``.

Ring-buffer (sliding-window) pages are handled through ``page_pos``: a page's
slot-0 absolute position decides token validity, so SWA rolling pools reuse
the same kernel.

Validated against ``ref.paged_attention_ref`` in interpret mode
(tests/test_kernels.py) and compiled for a v5e chip at llama3.2-1b widths
(tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, page_pos_ref, lengths_ref,  # scalar prefetch (SMEM)
            q_ref, pool_k_hbm, pool_v_hbm,  # inputs
            o_ref, m_ref, l_ref,  # outputs (accumulated in place)
            k_buf, v_buf, sems,  # scratch
            *, T: int, R: int, P_loc: int, window: Optional[int], scale: float):
    b = pl.program_id(0)
    length = lengths_ref[b]
    lo = jnp.maximum(0, length - window) if window is not None else 0

    def page(r):
        pid = tables_ref[b * R + r]  # local page id (wrapper pre-subtracts offset)
        base = page_pos_ref[b * R + r]
        live = (pid >= 0) & (pid < P_loc) & (base < length) & (base + T > lo)
        return pid, base, live

    def copies(pid, slot):
        return (
            pltpu.make_async_copy(pool_k_hbm.at[pid], k_buf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(pool_v_hbm.at[pid], v_buf.at[slot], sems.at[1, slot]),
        )

    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    pid0, _, live0 = page(0)

    @pl.when(live0)
    def _first():
        for c in copies(pid0, 0):
            c.start()

    def body(r, _):
        slot = r % 2
        pid, base, live = page(r)
        pid_n, _, live_n = page(jnp.minimum(r + 1, R - 1))

        @pl.when((r + 1 < R) & live_n)
        def _prefetch():
            for c in copies(pid_n, 1 - slot):
                c.start()

        @pl.when(live)
        def _attend():
            for c in copies(pid, slot):
                c.wait()
            q = q_ref[0].astype(jnp.float32)  # (H, K·D) block-diagonal
            kp = k_buf[slot].astype(jnp.float32)  # (T, K·D)
            vp = v_buf[slot].astype(jnp.float32)
            s = lax.dot_general(q, kp, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (H, T)
            pos = base + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = (pos >= lo) & (pos < length)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[0]  # (H, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[0] = l_ref[0] * alpha + p.sum(axis=1, keepdims=True)
            m_ref[0] = m_new
            pv = lax.dot_general(p, vp, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (H, K·D)
            o_ref[0] = o_ref[0] * alpha + pv

        return 0

    lax.fori_loop(0, R, body, 0)


def paged_attention_pallas(
    q: jnp.ndarray,  # (B, H, D)
    pool_k: jnp.ndarray,  # (P_local, T, K, D)
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,  # (B, R) GLOBAL page ids
    page_pos: jnp.ndarray,  # (B, R)
    lengths: jnp.ndarray,  # (B,)
    *,
    window: Optional[int] = None,
    page_offset=0,
    n_pages_total: int = 0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns unnormalized (o (B,H,D) f32, m (B,H) f32, l (B,H) f32)."""
    B, H, D = q.shape
    P_loc, T, K, _ = pool_k.shape
    R = tables.shape[1]
    G = H // K
    KD = K * D
    scale = 1.0 / (D ** 0.5)

    # 1-D scalar-prefetch operands: SMEM pads 2-D arrays along their last dim
    tables_local = (tables.astype(jnp.int32) - page_offset).reshape(B * R)  # negatives -> skipped
    page_pos_flat = page_pos.astype(jnp.int32).reshape(B * R)
    # block-diagonal queries: head h keeps its vector in kv-head h//G's columns
    eye = jnp.eye(K, dtype=q.dtype)
    q_blk = jnp.einsum("bkgd,kj->bkgjd", q.reshape(B, K, G, D), eye).reshape(B, H, KD)

    kernel = functools.partial(
        _kernel, T=T, R=R, P_loc=P_loc, window=window, scale=scale,
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, KD), lambda b, *_: (b, 0, 0)),  # q_blk
                pl.BlockSpec(memory_space=pl.ANY),  # pool_k stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),  # pool_v
            ],
            out_specs=[
                pl.BlockSpec((1, H, KD), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, T, KD), pool_k.dtype),
                pltpu.VMEM((2, T, KD), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, KD), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tables_local, page_pos_flat, lengths.astype(jnp.int32), q_blk,
      pool_k.reshape(P_loc, T, KD), pool_v.reshape(P_loc, T, KD))
    # each head's own kv-head columns
    o = jnp.einsum("bkgjd,kj->bkgd", o.reshape(B, K, G, K, D), eye.astype(o.dtype))
    return o.reshape(B, H, D), m[..., 0], l[..., 0]
