"""Token-choice top-k MoE with grouped, sort-based, gather-only dispatch.

Tokens are split into G independent dispatch groups (GShard-style; G = batch
by default so groups align with the data shards and every index op stays
shard-local).  Within a group:

  1. router -> top-k experts per token,
  2. a stable argsort of the flat (token,k) expert ids yields each
     assignment's rank within its expert,
  3. the per-expert capacity buffer is built with a GATHER from the sorted
     order (never a scatter — SPMD partitioners turn scatters on sharded
     operands into one-hot matmuls, which is catastrophic at 1M tokens),
  4. a batched expert GEMM 'gecd,edf->gecf' runs all experts,
  5. results gather back to token order and combine with router weights.

Memory is O(T*k*d); assignments beyond capacity are dropped (cf=1.25 train).

Serving routes with :func:`moe_serve` instead: the same router, no
capacity, so a token's output never depends on the other rows of its
dispatch, and only the experts this device holds are computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import substrate
from repro.nn import layers
from repro.parallel import sharding
from repro.parallel.sharding import constrain


def moe_init(key, d_model, d_ff, num_experts, *, num_shared=0, held=0,
             dtype=jnp.float32):
    """The router scores all ``num_experts``; the banks hold ``held`` of
    them (0: all)."""
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d_model)
    n = held or num_experts
    p = {
        "router": layers.normal_init(ks[0], (d_model, num_experts), 0.02,
                                     jnp.float32),
        "wi_gate": layers.normal_init(ks[1], (n, d_model, d_ff), scale,
                                      dtype),
        "wi_up": layers.normal_init(ks[2], (n, d_model, d_ff), scale,
                                    dtype),
        "wo": layers.normal_init(ks[3], (n, d_ff, d_model),
                                 1.0 / jnp.sqrt(d_ff), dtype),
    }
    if num_shared:
        p["shared"] = layers.swiglu_init(ks[4], d_model, d_ff * num_shared,
                                         dtype)
    return p


def _route(p, xf, top_k, backend, interpret):
    """float32 router over all E experts: softmax, top-k, the top-k
    probabilities renormalised to sum to 1.  xf: (..., T, d)."""
    d, E = p["router"].shape
    logits = substrate.gemm(xf.astype(jnp.float32), p["router"],
                            site="moe.router", backend=backend,
                            interpret=interpret,
                            shard=sharding.gemm_shard_ctx(
                                "moe.router", xf.size // d, d, E))
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return probs, top_vals, top_idx


def moe_serve(p, x, *, top_k, first=0, compute_dtype=jnp.bfloat16,
              backend="xla", interpret=None):
    """Serving MoE over the held experts: x (B, S, d) -> (y, routed).

    The banks hold E_h experts, global indices ``first`` .. ``first+E_h-1``;
    the router scores all E.  ``y`` is the held experts' part of the
    layer's output (an expert-parallel deployment sums the parts over its
    devices); ``routed`` (B, S) int32 counts each token's (token, held
    expert) pairs.

    Dropless and per token: a token picks an expert at most once, so each
    held expert gets one row per token of the dispatch (capacity = rows)
    and nothing is dropped; a token's output depends on its own row alone.
    The expert GEMMs run over all E_h x B*S rows; a row whose token did not
    pick the expert is weighted 0 at the combine.
    """
    B, S, d = x.shape
    T = B * S
    Eh = p["wi_gate"].shape[0]
    xf = x.reshape(T, d)
    _, top_vals, top_idx = _route(p, xf, top_k, backend, interpret)
    with jax.named_scope("moe.dispatch"):
        picked = top_idx[:, :, None] == first + jnp.arange(Eh)  # (T,k,Eh)
        # each held expert's combine weight per token: its renormalised
        # probability where the token picked it, else 0
        w = jnp.sum(jnp.where(picked, top_vals[:, :, None], 0.0), axis=1)
        routed = jnp.sum(picked, axis=(1, 2), dtype=jnp.int32)
        he = jnp.broadcast_to(xf.astype(compute_dtype), (1, Eh, T, d))
    esh = sharding.expert_shard_ctx(Eh)
    hg = substrate.expert_gemm(he, p["wi_gate"].astype(compute_dtype),
                               site="moe.wi_gate", backend=backend,
                               shard=esh, interpret=interpret)
    hu = substrate.expert_gemm(he, p["wi_up"].astype(compute_dtype),
                               site="moe.wi_up", backend=backend,
                               shard=esh, interpret=interpret)
    hout = substrate.expert_gemm(jax.nn.silu(hg) * hu,
                                 p["wo"].astype(compute_dtype),
                                 site="moe.wo", backend=backend, shard=esh,
                                 interpret=interpret)      # (1,Eh,T,d)
    with jax.named_scope("moe.combine"):
        y = jnp.sum(hout[0].astype(jnp.float32) * w.T[:, :, None], axis=0)
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + layers.swiglu(p["shared"], x, compute_dtype,
                              backend=backend, interpret=interpret)
    return y.astype(x.dtype), routed.reshape(B, S)


def moe_apply(p, x, *, top_k, capacity_factor=1.25, groups=0,
              compute_dtype=jnp.bfloat16, aux_loss_weight=0.01,
              backend="xla", interpret=None):
    """x: (B, S, d) -> (y, aux_loss).  groups=0 -> one group per sequence."""
    B, S, d = x.shape
    T = B * S
    G = groups or B
    Tg = T // G
    E = p["router"].shape[1]
    if p["wi_gate"].shape[0] != E:
        raise ValueError("capacity routing computes every expert; a layer "
                         "that holds a share of them serves (moe_serve)")
    TK = Tg * top_k
    xf = x.reshape(G, Tg, d)

    probs, top_vals, top_idx = _route(p, xf, top_k, backend, interpret)

    flat_e = top_idx.reshape(G, TK)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)    # (G,TK,E)
    counts = jnp.sum(onehot, axis=1).astype(jnp.int32)       # (G,E)

    # ---- load-balance auxiliary loss (Switch-style), over all tokens ------
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.sum(counts, axis=0).astype(jnp.float32) / (T * top_k)
    aux = aux_loss_weight * E * jnp.sum(me * ce)

    # ---- rank-in-expert via stable sort (all shard-local per group) -------
    order = jnp.argsort(flat_e, axis=-1, stable=True)        # (G,TK)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    starts = jnp.cumsum(counts, axis=-1) - counts            # (G,E) exclusive
    rank_sorted = (jnp.arange(TK, dtype=jnp.int32)[None, :]
                   - jnp.take_along_axis(starts, sorted_e, axis=-1))
    inv_order = jnp.argsort(order, axis=-1, stable=True)
    rank = jnp.take_along_axis(rank_sorted, inv_order, axis=-1)  # (G,TK)

    cap = int(max(1, round(Tg * top_k * capacity_factor / E)))
    keep = rank < cap

    # ---- build capacity buffer by GATHER from the sorted stream -----------
    slot_pos = starts[:, :, None] + jnp.arange(cap)[None, None, :]  # (G,E,cap)
    slot_valid = jnp.arange(cap)[None, None, :] < counts[:, :, None]
    slot_src = jnp.take_along_axis(
        order, jnp.minimum(slot_pos, TK - 1).reshape(G, E * cap),
        axis=-1).reshape(G, E, cap)
    slot_tok = slot_src // top_k                             # (G,E,cap)
    he = jnp.take_along_axis(
        xf.astype(compute_dtype),
        slot_tok.reshape(G, E * cap)[:, :, None], axis=1)
    he = he.reshape(G, E, cap, d) * slot_valid[..., None].astype(compute_dtype)
    he = constrain(he, "moe_buf4")

    # ---- expert GEMMs (substrate-dispatched; xla keeps the fused einsum,
    # arrayflex runs each site's E GEMMs in ONE expert-batched launch;
    # under the mesh the expert axis shards over 'model' when E % tp == 0
    # — the _MOE_EP condition — else dispatch stays replicated) ----------
    esh = sharding.expert_shard_ctx(E)
    wg = p["wi_gate"].astype(compute_dtype)
    wu = p["wi_up"].astype(compute_dtype)
    wo = p["wo"].astype(compute_dtype)
    hg = constrain(substrate.expert_gemm(he, wg, site="moe.wi_gate",
                                         backend=backend, shard=esh,
                                         interpret=interpret), "moe_h4")
    hu = constrain(substrate.expert_gemm(he, wu, site="moe.wi_up",
                                         backend=backend, shard=esh,
                                         interpret=interpret), "moe_h4")
    h = jax.nn.silu(hg) * hu
    hout = constrain(substrate.expert_gemm(h, wo, site="moe.wo",
                                           backend=backend, shard=esh,
                                           interpret=interpret), "moe_buf4")

    # ---- combine back (gather token slots, weight, sum over k) ------------
    dst = jnp.where(keep, flat_e * cap + rank, 0)            # (G,TK)
    y_rep = jnp.take_along_axis(hout.reshape(G, E * cap, d),
                                dst[:, :, None], axis=1)     # (G,TK,d)
    y_rep = y_rep * keep[..., None].astype(compute_dtype)
    w = top_vals.reshape(G, TK, 1).astype(compute_dtype)
    y = jnp.sum((y_rep * w).reshape(G, Tg, top_k, d), axis=2)

    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + layers.swiglu(p["shared"], x.reshape(B, S, d), compute_dtype,
                              backend=backend, interpret=interpret)
    return y.astype(x.dtype), aux


def moe_apply_reference(p, x, *, top_k, compute_dtype=jnp.float32):
    """O(T*E*d*ff) oracle: run every expert on every token, combine top-k.

    Used by tests to validate the dispatch path (with ample capacity the two
    must agree to numerical tolerance).
    """
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    E = p["router"].shape[1]
    g = jnp.einsum("td,edf->tef", xf, p["wi_gate"].astype(compute_dtype))
    u = jnp.einsum("td,edf->tef", xf, p["wi_up"].astype(compute_dtype))
    h = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                   p["wo"].astype(compute_dtype))
    mask = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)     # (T,k,E)
    w = jnp.einsum("tk,tke->te", top_vals, mask)
    y = jnp.einsum("te,ted->td", w, h)
    if "shared" in p:
        y = y + layers.swiglu(p["shared"], xf, compute_dtype)
    return y.reshape(B, S, d).astype(x.dtype)
