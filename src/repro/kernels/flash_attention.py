"""Flash attention Pallas TPU kernel (causal + sliding-window, GQA).

Tiling: grid ``(B, H, nq, nk)`` — the minor-most ``nk`` axis iterates
sequentially on TPU, so the online-softmax state lives in VMEM scratch across
``nk`` steps and the output tile is emitted on the last one. Q/K/V tiles are
``(block_q|block_k) × head_dim`` VMEM blocks; ``head_dim`` is always the
whole last dimension, so any head width tiles. The MXU sees
``block_q × head_dim × block_k`` matmuls.

Sequence lengths need not divide the block: a sequence longer than its
block is zero-padded up to a block multiple, padded keys are masked and
padded query rows are dropped. A sequence no longer than the block is one
block.

Causal / sliding-window block skipping: fully-masked K blocks are skipped via
``pl.when`` — this is the ~2× causal FLOP saving the XLA chunked path cannot
express.

Validated against ``ref.attention_ref`` in interpret mode on CPU
(tests/test_kernels.py) and compiled for a v5e chip
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


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int], q_start: int,
            kv_len: int, block_q: int, block_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos0 = q_start + iq * block_q
    k_pos0 = ik * block_k
    # block-level skip: block fully in the future, or fully left of the window
    live = True
    if causal:
        live = k_pos0 <= q_pos0 + block_q - 1
    if window is not None:
        live = jnp.logical_and(live, k_pos0 + block_k - 1 > q_pos0 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qp = q_pos0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_pos0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        if kv_len < nk * block_k:  # zero-padded keys
            mask &= kp < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * mask  # mask kills exp(-1e30 - -1e30) == 1
        l_ref[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _fit(n: int, block: int) -> Tuple[int, int]:
    """(block, padded length): one whole-sequence block when ``n`` fits,
    else ``block``-sized blocks over ``n`` rounded up to a block multiple."""
    if n <= block:
        return n, n
    return block, -(-n // block) * block


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, K, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_start: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if H % K:
        raise ValueError(f"{H} query heads do not group over {K} kv heads")
    block_q, Sq_pad = _fit(Sq, block_q)
    block_k, Sk_pad = _fit(Sk, block_k)
    nq, nk = Sq_pad // block_q, Sk_pad // block_k
    scale = 1.0 / (D ** 0.5)

    # layout: (B, heads, seq, D) blocks, seq zero-padded to a block multiple
    def blocks(x, n):
        return jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    qt, kt, vt = blocks(q, Sq_pad), blocks(k, Sk_pad), blocks(v, Sk_pad)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, q_start=q_start,
        kv_len=Sk, block_q=block_q, block_k=block_k, nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h * K // H, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h * K // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
