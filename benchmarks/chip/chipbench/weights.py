"""Seeded random weights, made by the benchmark and not by the program.

The same function makes each layer's weights for the program (all layers in
one jitted call on the device, stacked in the program's parameter layout)
and for the plain reference (one layer at a time, after the program's state
is freed).  A leaf's values depend only on (seed, label, layer), so the two
agree without the reference ever reading what the program holds.

Scales follow the usual initialisation (linear weights N(0, 1/fan_in),
embedding N(0, 0.02^2)); biases and norm scales are drawn too, so a path
that dropped a bias or a norm scale would show in the comparison.  Rows of
the padded vocabulary beyond ``vocab_size`` are zero: their logits are 0,
far below the best of the real rows, and the reference has no such rows.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

# label -> (program path inside one stacked block, shape from dims)
LAYER_LEAVES = {
    "ln1/scale": lambda d: (d["hidden"],),
    "attn/wq/w": lambda d: (d["hidden"], d["heads"] * d["head_dim"]),
    "attn/wq/b": lambda d: (d["heads"] * d["head_dim"],),
    "attn/wk/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/wk/b": lambda d: (d["kv_heads"] * d["head_dim"],),
    "attn/wv/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/wv/b": lambda d: (d["kv_heads"] * d["head_dim"],),
    "attn/wo/w": lambda d: (d["heads"] * d["head_dim"], d["hidden"]),
    "ln2/scale": lambda d: (d["hidden"],),
    "mlp/wi_gate/w": lambda d: (d["hidden"], d["ffn"]),
    "mlp/wi_up/w": lambda d: (d["hidden"], d["ffn"]),
    "mlp/wo/w": lambda d: (d["ffn"], d["hidden"]),
}
BIAS_SCALE = 0.2
NORM_SPREAD = 0.1


def root_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def _leaf(key, label: str, shape, valid_rows=None):
    z = jax.random.normal(key, shape, jnp.float32)
    if label.endswith("scale"):
        return 1.0 + NORM_SPREAD * z
    if label.endswith("/b"):
        return BIAS_SCALE * z
    if label == "embed/table":
        w = 0.02 * z
        return jnp.where(jnp.arange(shape[0])[:, None] < valid_rows, w, 0.0)
    if label == "lm_head/w":
        w = z / jnp.sqrt(float(shape[0]))
        return jnp.where(jnp.arange(shape[1])[None, :] < valid_rows, w, 0.0)
    return z / jnp.sqrt(float(shape[0]))


def _key(root, label: str, layer: int):
    return jax.random.fold_in(jax.random.fold_in(root, zlib.crc32(
        label.encode())), layer)


def layer_weights(root, dims: dict, layer):
    """One layer's weights as {label: array}; ``layer`` may be traced."""
    return {lb: _leaf(_key(root, lb, layer), lb, fn(dims))
            for lb, fn in LAYER_LEAVES.items()}


def top_weights(root, dims: dict, padded_vocab: int, tied: bool):
    """Embedding, final norm and (untied) head, vocabulary padded to the
    program's row count with zero rows."""
    v, d = dims["vocab"], dims["hidden"]
    out = {"embed/table": _leaf(_key(root, "embed/table", 0), "embed/table",
                                (padded_vocab, d), v),
           "final_norm/scale": _leaf(_key(root, "final_norm/scale", 0),
                                     "final_norm/scale", (d,))}
    if not tied:
        out["lm_head/w"] = _leaf(_key(root, "lm_head/w", 0), "lm_head/w",
                                 (d, padded_vocab), v)
    return out


def _set(tree: dict, path: str, value):
    *head, last = path.split("/")
    for h in head:
        tree = tree.setdefault(h, {})
    tree[last] = value


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _paths(v, f"{prefix}{k}/")
    elif isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            yield from _paths(v, f"{prefix}{i}/")
    else:
        yield prefix[:-1], tree


def program_params(expected, dims: dict, seed: int, padded_vocab: int,
                   tied: bool):
    """The program's whole parameter tree, made on the device in one jitted
    call.  ``expected`` is the tree of shapes the program's own initialiser
    gives (``jax.eval_shape``); the layout made here must match it leaf for
    leaf, or the program has changed under the benchmark."""
    n_layers = dims["layers"]

    def make(root):
        stacked = jax.vmap(lambda l: layer_weights(root, dims, l))(
            jnp.arange(n_layers))
        tree = {"blocks": [{}]}
        for lb, arr in stacked.items():
            _set(tree["blocks"][0], lb, arr)
        for lb, arr in top_weights(root, dims, padded_vocab, tied).items():
            _set(tree, lb, arr)
        tree["blocks"] = tuple(tree["blocks"])
        return tree

    root = root_key(seed)      # an argument, so one compiled program serves
    got = dict(_paths(jax.eval_shape(make, root)))  # every seed
    want = dict(_paths(expected))
    if set(got) != set(want) or any(
            (got[p].shape, got[p].dtype) != (want[p].shape, want[p].dtype)
            for p in want):
        raise RuntimeError(
            "the program's parameter layout differs from the benchmark's: "
            f"{sorted((p, w.shape, str(w.dtype)) for p, w in want.items())}")
    return jax.jit(make)(root)
