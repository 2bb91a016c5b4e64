"""Top-k MoE with capacity-based gather dispatch under ``shard_map``.

The dispatch is deliberately framed like the paper's storage path: tokens are
"pages", experts are "providers", and the router plus capacity logic is the
provider manager — each token-assignment is placed into a bounded per-expert
slot buffer (load balancing + capacity), computed entirely shard-locally and
combined with one ``psum`` (no global synchronization, mirroring the paper's
single-serialization-point discipline).

Two layouts, chosen by divisibility of ``n_experts`` by the model-axis size:

* **EP** (``E % tp == 0``, e.g. qwen3 128e over 16): each model rank owns
  ``E/tp`` whole experts with full ``d_ff``.
* **expert-TP** (e.g. mixtral 8e over 16): every rank holds all experts with
  ``d_ff/tp`` columns.

Both keep the same local dispatch code; only the expert range / ffn slice
differ. Token→slot routing uses a *gather* formulation (scatter token indices,
then gather rows) so no ``(tokens, k, d)`` intermediate is ever materialized.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.modules import dense_init
from repro.parallel.axisinfo import AxisInfo


def moe_init(key, cfg: ModelConfig):
    kr, k1, kg, k2 = jax.random.split(key, 4)
    d, f, E, dt = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.pdtype()
    params = {
        "router": dense_init(kr, d, (E,), jnp.float32),  # router in fp32
        "w1": jax.vmap(lambda k: dense_init(k, d, (f,), dt))(jax.random.split(k1, E)),
        "wg": jax.vmap(lambda k: dense_init(k, d, (f,), dt))(jax.random.split(kg, E)),
        "w2": jax.vmap(lambda k: dense_init(k, f, (d,), dt))(jax.random.split(k2, E)),
    }
    axes = {
        "router": ("embed", "experts_router"),
        "w1": ("experts", "embed", "moe_ffn"),
        "wg": ("experts", "embed", "moe_ffn"),
        "w2": ("experts", "moe_ffn", "embed"),
    }
    return params, axes


def use_expert_parallel(cfg: ModelConfig, tp: int) -> bool:
    if cfg.n_experts % tp == 0:
        return True
    if cfg.d_ff % tp == 0:
        return False
    raise ValueError(f"neither experts ({cfg.n_experts}) nor d_ff ({cfg.d_ff}) divide tp={tp}")


def _moe_local(
    x: jnp.ndarray,  # (T, d) this shard's tokens
    router: jnp.ndarray,  # (d, E) full router
    w1: jnp.ndarray,  # (E_loc, d, f_loc)
    wg: jnp.ndarray,
    w2: jnp.ndarray,  # (E_loc, f_loc, d)
    cfg: ModelConfig,
    *,
    first_expert,  # first expert id owned by this rank (0 for expert-TP)
    n_local_experts: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shard-local dispatch → expert matmuls → combine. Returns (out, aux)."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ct = cfg.cdtype()

    gates = jnp.einsum("td,de->te", x.astype(jnp.float32), router)  # (T, E)
    probs = jax.nn.softmax(gates, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, k)  # (T, k)
    top_w = jax.nn.softmax(top_w, axis=-1)  # renormalize over selected

    # auxiliary load-balance loss (Switch-style): E * Σ_e f_e · p_e
    counts = jnp.zeros((E,)).at[top_e.reshape(-1)].add(1.0)
    frac = counts / (T * k)
    aux = E * jnp.sum(frac * probs.mean(axis=0))

    # capacity per expert, over this shard's token-assignments
    C = max(int(T * k / E * cfg.capacity_factor), 4)

    flat_e = top_e.reshape(-1)  # (T*k,) expert of each assignment
    # position of each assignment within its expert, via stable sort ranking
    # (avoids a (T·k, E) one-hot cumsum intermediate)
    idx_sorted = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[idx_sorted]
    start = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(flat_e.size, dtype=jnp.int32) - start[e_sorted].astype(jnp.int32)
    pos_in_e = jnp.zeros((flat_e.size,), jnp.int32).at[idx_sorted].set(rank_sorted)

    local_e = flat_e - first_expert
    keep = (pos_in_e < C) & (local_e >= 0) & (local_e < n_local_experts)
    slot = jnp.where(keep, local_e * C + pos_in_e, n_local_experts * C)  # OOB => dropped

    # gather-style dispatch: slot -> source token index
    token_idx = jnp.arange(T * k, dtype=jnp.int32) // k
    slot_token = jnp.full((n_local_experts * C,), T, jnp.int32).at[slot].set(token_idx, mode="drop")
    slot_valid = slot_token < T
    xg = jnp.where(slot_valid[:, None], x[jnp.clip(slot_token, 0, T - 1)], 0.0)
    disp = xg.reshape(n_local_experts, C, d).astype(ct)

    h = jnp.einsum("ecd,edf->ecf", disp, w1.astype(ct))
    g = jnp.einsum("ecd,edf->ecf", disp, wg.astype(ct))
    h = jax.nn.silu(g) * h
    out_slots = jnp.einsum("ecf,efd->ecd", h, w2.astype(ct)).reshape(n_local_experts * C, d)

    # combine: scatter expert outputs back to tokens, weighted by gate prob
    slot_w = jnp.zeros((n_local_experts * C,), jnp.float32).at[slot].set(
        top_w.reshape(-1), mode="drop"
    )
    out = (
        jnp.zeros((T, d), jnp.float32)
        .at[jnp.clip(slot_token, 0, T - 1)]
        .add(out_slots.astype(jnp.float32) * slot_w[:, None] * slot_valid[:, None], mode="drop")
    )
    return out.astype(x.dtype), aux


def moe_ffn(
    params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ModelConfig,
    axis_info: Optional[AxisInfo],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE feed-forward. Returns (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape

    if axis_info is None:
        out, aux = _moe_local(
            x.reshape(B * S, d), params["router"], params["w1"], params["wg"], params["w2"],
            cfg, first_expert=0, n_local_experts=cfg.n_experts,
        )
        return out.reshape(B, S, d), aux

    mesh = axis_info.mesh
    tp = mesh.shape[axis_info.model_axis]
    ep = use_expert_parallel(cfg, tp)
    n_local = cfg.n_experts // tp if ep else cfg.n_experts
    batch_axes = axis_info.batch_axes
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if (B * S) % n_batch:
        batch_axes = ()  # tiny decode batches: replicate tokens, keep EP/TP
    ma = axis_info.model_axis
    w_spec = P(ma, None, None) if ep else P(None, None, ma)
    w2_spec = P(ma, None, None) if ep else P(None, ma, None)

    def local_fn(xf, router, w1, wg, w2):
        first = jax.lax.axis_index(ma) * n_local if ep else 0
        out, aux = _moe_local(
            xf, router, w1, wg, w2, cfg, first_expert=first, n_local_experts=n_local
        )
        out = jax.lax.psum(out, ma)
        # aux is identical across ma ranks (computed from replicated gates);
        # average over the batch shards only.
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    xf = x.reshape(B * S, d)
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(batch_axes, None), P(None, None), w_spec, w_spec, w2_spec),
        out_specs=(P(batch_axes, None), P()),
        check_vma=False,
    )(xf, params["router"], params["w1"], params["wg"], params["w2"])
    return out.reshape(B, S, d), aux
