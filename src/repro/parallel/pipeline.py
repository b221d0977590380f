"""GPipe-style pipeline parallelism over the 'pod' mesh axis (shard_map).

Inter-pod ICI is the thinnest link in a multi-pod deployment, so the 'pod'
axis runs pipeline stages: each pod holds a contiguous block of layers and
microbatch activations flow pod->pod via collective_permute.  The stage
count is planned by the Eq.(6)/(7)-at-cluster-scale math at the bottom of
this module (see DESIGN.md §Beyond).

``gpipe`` is the generic multi-microbatch schedule: fn is one stage's
forward; stage parameters are sharded over `axis_name` (stage i's params
live on shard i).  ``staged_step`` is the single-microbatch serving
schedule the disaggregated engine pipelines decode/prefill steps with —
one activation flows through the stages while each stage commits its own
slice of the KV cache.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(fn, stage_params, x_micro, *, axis_name: str):
    """Run a P-stage pipeline inside shard_map.

    fn: (params_i, x) -> y, same shape.  stage_params: params of THIS shard's
    stage (shard_map has already split the stage dim).  x_micro: (M, mb, d)
    microbatches (replicated input).  Returns (M, mb, d) outputs (valid on
    every shard after the final broadcast).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x_micro.shape[0]
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(t, carry):
        outputs, recv = carry
        # stage 0 injects microbatch t (clamped; masked below)
        t_in = jnp.minimum(t, M - 1)
        inject = (stage == 0) & (t < M)
        x_in = jnp.where(inject, x_micro[t_in], recv)
        y = fn(stage_params, x_in)
        # the last stage commits its result at tick t to slot t-(P-1)
        out_slot = t - (n_stages - 1)
        valid = (stage == n_stages - 1) & (out_slot >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y.astype(o.dtype), jnp.maximum(out_slot, 0), 0),
            lambda o: o, outputs)
        recv = jax.lax.ppermute(y, axis_name, perm)
        return outputs, recv

    outputs = jnp.zeros_like(x_micro)
    recv = jnp.zeros_like(x_micro[0])
    outputs, _ = jax.lax.fori_loop(0, M + n_stages - 1, tick,
                                   (outputs, recv))
    # broadcast final outputs from the last stage to every shard
    mask = (stage == n_stages - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis_name)


def make_pipelined(fn, mesh, *, axis_name: str = "pod",
                   stage_param_spec=P("pod"), x_spec=P()):
    """shard_map wrapper: stage params stacked on axis 0 (one per pod).

    `stage_param_spec` is a prefix spec applied to every stage-param leaf.
    """
    def inner(stage_params, x_micro):
        sp = jax.tree.map(lambda a: a[0], stage_params)  # this shard's stage
        return gpipe(fn, sp, x_micro, axis_name=axis_name)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(stage_param_spec, x_spec),
                         out_specs=x_spec, check_vma=False)


def staged_step(fn, x0, state, *, axis_name: str = "pod"):
    """Single-microbatch pipeline step inside shard_map (serving path).

    ``fn(x, state) -> (y, new_state)`` is one stage's layer block over this
    shard's slice of the model; ``x0`` the stage-0 input (the embedded
    token chunk, replicated); ``state`` this shard's cache slice.  Runs
    ``P`` ticks: stage ``s`` computes its real output at tick ``t == s``
    from the activation `collective_permute`d in by stage ``s-1`` at the
    previous tick, and commits its cache slice only on that tick — other
    ticks recompute on placeholder zeros so the loop body traces ONCE (one
    kernel launch per GEMM site regardless of depth, and every stage stays
    in lockstep for the permute).  Returns ``(y_last, state)`` where
    ``y_last`` holds the model output on the LAST stage (zeros elsewhere —
    mask and ``psum`` to broadcast) and ``state`` is the committed cache.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(t, carry):
        recv, st, y_last = carry
        x_in = jnp.where(t == 0, x0, recv)
        y, new_st = fn(x_in, st)
        active = stage == t
        st = jax.tree.map(lambda a, b: jnp.where(active, a, b), new_st, st)
        y_last = jnp.where(active & (stage == n_stages - 1), y, y_last)
        recv = jax.lax.ppermute(y, axis_name, perm)
        return recv, st, y_last

    recv0 = jnp.zeros_like(x0)
    _, state, y_last = jax.lax.fori_loop(
        0, n_stages, tick, (recv0, state, jnp.zeros_like(x0)))
    return y_last, state


# ---------------------------------------------------------------------------
# ArrayFlex-at-cluster-scale: pipeline-depth planning with Eq.(6)/(7).
#
# Beyond-paper extension (DESIGN.md §Beyond): the paper's tradeoff — merge
# pipeline stages to cut cycle count at the cost of a slower clock — recurs
# one level up in pipeline-parallel training across pods:
#
#   collapse k pods into one pipeline stage
#     -> fewer stages  P(k) = P/k          (shorter fill/drain "skew"),
#     -> slower "clock" per stage: stage time grows with the per-stage layer
#        count, exactly T_clock(k) = d_base + k*d_inc with
#        d_base = per-microbatch dispatch/collective overhead and
#        d_inc  = per-pod layer compute time.
#
# GPipe latency for M microbatches on P/k stages:
#   T = (M + P/k - 1) * T_stage(k)   — isomorphic to Eq.(6) with T<-M, R,C<-P.
# Setting dT/dk = 0 reproduces Eq.(7) with the same structure; the discrete
# argmin below picks the deployed stage count.


@dataclass(frozen=True)
class PipelineCost:
    n_pods: int                 # P: pods available (max pipeline stages)
    microbatches: int           # M: per-step microbatches
    layer_time_ms: float        # per-pod layer-block compute time
    overhead_ms: float          # per-microbatch stage overhead (dispatch+p2p)


def stage_time_ms(c: PipelineCost, k: int) -> float:
    """T_clock analogue: time of one collapsed stage (k pods' layers)."""
    return c.overhead_ms + k * c.layer_time_ms


def pipeline_latency_ms(c: PipelineCost, k: int) -> float:
    """Eq.(6) analogue: (M + P/k - 1) * T_stage(k)."""
    stages = max(1, c.n_pods // k)
    return (c.microbatches + stages - 1) * stage_time_ms(c, k)


def k_hat(c: PipelineCost) -> float:
    """Eq.(7) analogue (continuous optimum)."""
    if c.microbatches <= 1:
        return float(c.n_pods)
    return math.sqrt(c.n_pods * c.overhead_ms
                     / ((c.microbatches - 1) * c.layer_time_ms))


def best_collapse(c: PipelineCost) -> int:
    ks = [k for k in range(1, c.n_pods + 1) if c.n_pods % k == 0]
    return min(ks, key=lambda k: pipeline_latency_ms(c, k))


def plan(c: PipelineCost) -> dict:
    k = best_collapse(c)
    base = pipeline_latency_ms(c, 1)
    bestt = pipeline_latency_ms(c, k)
    return {
        "k": k, "k_hat": k_hat(c), "stages": c.n_pods // k,
        "latency_ms": bestt, "latency_ms_k1": base,
        "saving": 1.0 - bestt / base,
        "bubble_fraction": (c.n_pods // k - 1)
        / (c.microbatches + c.n_pods // k - 1),
    }
