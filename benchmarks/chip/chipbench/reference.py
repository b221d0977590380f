"""The plain float32 reference's shared part, in jax.numpy alone: the
building blocks most decoders share (RMSNorm, rotary embedding), the
blocking of served rows, and the gap of each served token below the
reference's best logit after the final RMSNorm and the head.

A layer's own equations are its architecture module's ``layer``
(``archs/<name>.py``, named by the configuration file's ``reference`` key),
which states what it follows and its departures.  Every product runs at
``Precision.HIGHEST`` (true float32 on a TPU).

It imports nothing of the program.  It runs layer by layer over blocks of
sequences padded to a few lengths, so that it fits in the chip's memory
once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HI = jax.lax.Precision.HIGHEST


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (N, L, heads, hd), positions 0..L-1, rotate-half pairs."""
    L, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(L)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(lengths, budget):
    """Group row indices, longest first, into blocks of (rows, length): the
    length is the block's longest row rounded up to 256, the row count a
    power of two with rows x length <= budget (a row alone may pass it), so
    that few shapes compile."""
    order = sorted(range(len(lengths)), key=lambda i: -lengths[i])
    out = []
    while order:
        L = -(-max(lengths[order[0]], 1) // 256) * 256
        n = 1
        while 2 * n * L <= budget and n < len(order):
            n *= 2
        out.append((order[:n], n, L))
        order = order[n:]
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _gaps_at(x, rows, pos, tok, scale, head, *, eps):
    """Gap of each token ``tok`` below the best logit at (rows, pos) of the
    block's last hidden states ``x``; inf for a token outside the head."""
    h = rmsnorm(x[rows, pos], scale, eps)
    lg = jnp.einsum("td,dv->tv", h, head, precision=HI)
    ok = (tok >= 0) & (tok < head.shape[1])
    picked = jnp.take_along_axis(
        lg, jnp.clip(tok, 0, head.shape[1] - 1)[:, None], 1)[:, 0]
    return jnp.where(ok, lg.max(axis=1) - picked, jnp.inf)


def served_gaps(arch, dims, seed, served, *, eps, padded_vocab, tied,
                budget=1 << 14, chunk=1024, **layer_kw):
    """For each (prompt, output tokens) pair, the gaps by which its served
    tokens' reference logits lie below the reference's best logit at their
    positions (inf for a token outside the vocabulary): one float64 array
    per pair, in order.

    ``arch`` is the architecture module: its ``LAYER_LEAVES`` and its
    ``layer``, which takes ``eps`` and ``layer_kw`` (the module's
    ``reference_kwargs`` give all of them, with ``tied``).

    Rows run in blocks of about ``budget`` positions; each layer's weights
    are made once and applied to every block, and only the gaps come back
    to the host."""
    if not served:
        return []
    rows = [list(p) + list(o[:-1]) for p, o in served]
    root = weights.root_key(seed)
    top = jax.jit(functools.partial(weights.top_weights, dims=dims,
                                    padded_vocab=padded_vocab,
                                    tied=tied))(root)
    blocks = []
    for idx, n, L in _blocks([len(r) for r in rows], budget):
        tok = np.zeros((n, L), np.int32)
        for j, i in enumerate(idx):
            tok[j, :len(rows[i])] = rows[i]
        blocks.append((idx, top["embed/table"][jnp.asarray(tok)]))
    make = jax.jit(functools.partial(weights.layer_weights, dims=dims,
                                     leaves=arch.LAYER_LEAVES))
    layers = {}
    for li in range(dims["layers"]):
        w = make(root, layer=li)
        for b, (idx, x) in enumerate(blocks):
            n, L = x.shape[:2]
            if (n, L) not in layers:
                q_block = max(64, min(512, (1 << 30) // (
                    n * dims["heads"] * L * 4) // 64 * 64))
                layers[n, L] = jax.jit(functools.partial(
                    arch.layer, dims=dims, eps=eps, q_block=q_block,
                    **layer_kw))
            blocks[b] = (idx, layers[n, L](x, w))
        del w
    v = dims["vocab"]
    head = top["embed/table"][:v].T if tied else top["lm_head/w"][:, :v]
    scale = top["final_norm/scale"]
    out = [None] * len(served)
    for idx, x in blocks:
        r_, p_, t_, own = [], [], [], []
        for j, i in enumerate(idx):
            p, o = served[i]
            r_ += [j] * len(o)
            p_ += range(len(p) - 1, len(p) - 1 + len(o))
            t_ += list(o)
            own += [i] * len(o)
        got = []
        for s in range(0, len(r_), chunk):
            pad = chunk - len(r_[s:s + chunk])
            arr = [np.asarray(a[s:s + chunk] + [0] * pad, np.int32)
                   for a in (r_, p_, t_)]
            got.append(np.asarray(_gaps_at(x, *arr, scale, head, eps=eps),
                                  np.float64)[:chunk - pad])
        got = np.concatenate(got) if got else np.zeros(0)
        own = np.asarray(own)
        for i in idx:
            out[i] = got[own == i]
    return out
