"""Plain reference of a dense decoder with grouped-query attention, as the
Llama architecture is published: RMSNorm before attention and before the
MLP, rotary positions on q and k (the halves of each head rotated against
each other, ``theta ** (-i / (D/2))``), query head ``h`` reading kv head
``h // (H/K)``, causal softmax attention scaled by ``D ** -0.5``, a SiLU-gated
MLP, a final RMSNorm and the head. With ``tie_word_embeddings`` the head is
the embedding table.

It imports nothing of the program. It computes in float32 at ``highest``
matmul precision, one layer at a time over the stacked weights. The weights
come from :func:`make_weights`, which lays them out as the serving program
takes them: ``embed/table (V, d)``, per layer ``ln1``, ``attn/wq (d, H, D)``,
``wk``/``wv (d, K, D)``, ``wo (H, D, d)``, ``ln2``, ``ffn/wi``/``wg (d, F)``,
``ffn/wo (F, d)``, stacked over layers, and ``ln_f``.

``quant="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 e4m3, weights scaled per output column and
activations per token.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _sizes(cfg: dict):
    return (int(cfg["num_hidden_layers"]), int(cfg["hidden_size"]),
            int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def table_rows(cfg: dict) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 256."""
    v = int(cfg["vocab_size"])
    return -(-v // 256) * 256


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, made on the device by one jitted call."""
    L, d, H, K, D, F, _ = _sizes(cfg)
    Vp = table_rows(cfg)

    def proj(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
                / np.sqrt(fan_in)).astype(dtype)

    def norm(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, F32)).astype(dtype)

    @jax.jit
    def make(key):
        k = jax.random.split(key, 11)
        return {
            # d ** -0.5 keeps the logits of the tied head near unit scale at any width
            "embed": {"table": (jax.random.normal(k[0], (Vp, d), F32) / np.sqrt(d)).astype(dtype)},
            "blocks": {
                "ln1": norm(k[1], (L, d)),
                "attn": {
                    "wq": proj(k[2], (L, d, H, D), d),
                    "wk": proj(k[3], (L, d, K, D), d),
                    "wv": proj(k[4], (L, d, K, D), d),
                    "wo": proj(k[5], (L, H, D, d), H * D),
                },
                "ln2": norm(k[6], (L, d)),
                "ffn": {
                    "wi": proj(k[7], (L, d, F), d),
                    "wg": proj(k[8], (L, d, F), d),
                    "wo": proj(k[9], (L, F, d), F),
                },
            },
            "ln_f": norm(k[10], (d,)),
        }

    return make(jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32))


# ------------------------------- forward pass -------------------------------
def _fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x (S, heads, D): rotate the two halves of each head by position."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(weights, tokens, cfg: dict, quant: Optional[str] = None):
    """Logits ``(S, V)`` in float32 for one sequence of ``S`` tokens."""
    L, d, H, K, D, F, V = _sizes(cfg)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    tied = bool(cfg["tie_word_embeddings"])
    g = H // K

    if quant == "fp8":
        w_in = lambda w, n_in: _fp8(w, tuple(range(n_in)))  # per output column
        act = lambda x: _fp8(x, (-1,))  # per token
    elif quant is None:
        w_in = lambda w, n_in: w.astype(F32)
        act = lambda x: x
    else:
        raise ValueError(f"unknown quant {quant!r}")

    table = weights["embed"]["table"]
    x = table[tokens].astype(F32)  # (S, d)
    S = x.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = act(_rmsnorm(x, p["ln1"], eps))
        q = jnp.einsum("sd,dhk->shk", h, w_in(p["attn"]["wq"], 1))
        k = jnp.einsum("sd,dhk->shk", h, w_in(p["attn"]["wk"], 1))
        v = jnp.einsum("sd,dhk->shk", h, w_in(p["attn"]["wv"], 1))
        q, k = _rope(q, theta), _rope(k, theta)
        qg = q.reshape(S, K, g, D)
        s = jnp.einsum("qkgd,tkd->kgqt", qg, k) * (D ** -0.5)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p_att = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p_att, v).reshape(S, H, D)
        x = x + jnp.einsum("shk,hkd->sd", act(o), w_in(p["attn"]["wo"], 2))
        h = act(_rmsnorm(x, p["ln2"], eps))
        gate = jnp.einsum("sd,df->sf", h, w_in(p["ffn"]["wg"], 1))
        up = jnp.einsum("sd,df->sf", h, w_in(p["ffn"]["wi"], 1))
        x = x + jnp.einsum("sf,fd->sd", act(jax.nn.silu(gate) * up), w_in(p["ffn"]["wo"], 1))
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, weights["blocks"])
        h = act(_rmsnorm(x, weights["ln_f"], eps))
        head = table if tied else weights["lm_head"]
        head = _fp8(head, (1,)) if quant else head.astype(F32)  # (V, d): scale per row
        logits = jnp.einsum("sd,vd->sv", h, head)
    return logits[:, :V]


def gap_fn(cfg: dict, pad_to: int, quant: Optional[str] = None):
    """A jitted ``(weights, tokens, n_prompt, n_served) -> gaps``: for each
    served token, how far the reference's logit of that token lies below the
    reference's best at its position. With ``quant`` set, the token is the
    one the lower-precision forward puts first there (the control). Tokens
    are one sequence padded at its end to ``pad_to``, which causal attention
    keeps away from every position read. Unserved slots read ``-inf``."""

    def gaps(weights, tokens, n_prompt, n_served):
        ref = forward(weights, tokens, cfg)
        live = jnp.arange(pad_to) < n_served
        pos = jnp.where(live, n_prompt - 1 + jnp.arange(pad_to), 0)  # predicts pos + 1
        rows = ref[pos]
        if quant is None:
            nxt = tokens[jnp.where(live, pos + 1, 0)]
        else:
            nxt = forward(weights, tokens, cfg, quant)[pos].argmax(axis=-1)
        got = jnp.take_along_axis(rows, nxt[:, None], axis=-1)[:, 0]
        return jnp.where(live, rows.max(axis=-1) - got, -jnp.inf)

    return jax.jit(gaps)


def served_gaps(fn, weights, prompt: Sequence[int], served: Sequence[int],
                pad_to: int) -> np.ndarray:
    """Run a :func:`gap_fn` over one prompt and the tokens served for it."""
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens over the padded length {pad_to}")
    tokens = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
    out = fn(weights, tokens, jnp.int32(len(prompt)), jnp.int32(len(served)))
    return np.asarray(out)[: len(served)]
