"""Checks of the paged K/V pool's in-place update.

:func:`paged_step_reference` is the paged steps as they were before the
pool stayed in place, kept as the reference the in-place steps must match
bit for bit.  There the stacked pools went through the layer scan as
``xs`` and came back as ``ys``, and each layer wrote its own pool: a decode
step the token's row (``pool.at[phys, slot % page]``), a prefill chunk its
whole gathered view, scattered back over the view's pages.  Each layer's attention and MLP are
the program's own (one layer's pool handed over as a stack of one), so what
is compared is where the pool is read and written.

:func:`check_steps_against_reference` drives both through one prefill and
three decodes over four rows: a prefix page two rows share, block-table
entries reserved lazily (left on page 0 until a decode needs them), and an
idle row; the in-place steps run as the engine launches them, with the pool
donated.  :func:`record_donations` watches an engine's launches, so a test
can see that each pool handed to a paged step was donated and that the
engine holds a live one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.nn import layers
from repro.serving.engine import donate_cache, jit_step


def _row_write(pool, rows, pos, bt):
    """A decode's write of one layer: row ``slot % page`` of its page."""
    page = pool.shape[1]
    slot = jnp.minimum(pos, bt.shape[1] * page - 1)
    phys = jnp.take_along_axis(bt, (slot // page)[:, None], axis=1)[:, 0]
    return pool.at[phys, slot % page].set(rows)


def _view_scatter(pool, rows, pos, lengths, bt):
    """A prefill's write of one layer: the gathered view with the chunk
    put in, scattered back over every page of the view."""
    B, n_pg = bt.shape
    page, C = pool.shape[1], rows.shape[1]
    view = pool[bt].reshape(B, n_pg * page, -1)
    src = jnp.arange(n_pg * page)[None, :] - pos[:, None]
    ok = (src >= 0) & (src < lengths[:, None])
    idx = jnp.clip(src, 0, C - 1)[:, :, None]
    view = jnp.where(ok[:, :, None], jnp.take_along_axis(rows, idx, axis=1),
                     view)
    return pool.at[bt].set(view.reshape(B, n_pg, page, -1))


def paged_step_reference(cfg, params, cache, tokens, pos, lengths, bt):
    """``lm.decode_step_paged`` (``lengths`` None, tokens (B, 1)) or
    ``lm.prefill_step_paged`` with the pools passed through the scan."""
    cd = lm._cdtype(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    C = tokens.shape[1]
    x = layers.embed(params["embed"], tokens, cd)

    def body(x, xs):
        p_block, pools = xs
        new, routed = [], None
        for i in range(lm.period(cfg)):
            one = {k: v[None] for k, v in pools[i].items()}
            if lengths is None:
                x, r, rows = lm._sublayer_decode_paged(p_block[i], cfg, i, x,
                                                       one, 0, pos, bt)
                new.append({k: _row_write(v, rows[k], pos, bt)
                            for k, v in pools[i].items()})
            else:
                x, r, rows = lm._sublayer_prefill_paged(
                    p_block[i], cfg, i, x, one, 0, pos, lengths, bt)
                new.append({k: _view_scatter(v, rows[k], pos, lengths, bt)
                            for k, v in pools[i].items()})
            if r is not None:
                routed = r if routed is None else routed + r
        return x, (tuple(new), routed)

    x, (new_cache, routed) = jax.lax.scan(body, x,
                                          (params["blocks"], cache))
    x = layers.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if lengths is not None:
        last = jnp.clip(lengths - 1, 0, C - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    out = (lm._logits(cfg, params, x, cd)[:, 0], new_cache)
    if routed is None:
        return out
    routed = jnp.sum(routed, axis=0)
    if lengths is not None:
        routed = jnp.where(jnp.arange(C)[None, :] < lengths[:, None],
                           routed, 0)
    return (*out, jnp.sum(routed, axis=1))


def _equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def check_steps_against_reference(cfg, params, seed=0):
    """One prefill, then three decodes, through the engine's donated paged
    steps and through :func:`paged_step_reference`, from the same random
    pool: after each step the logits, ``routed`` (expert layers) and the
    whole pool must be equal bit for bit."""
    page, n_pg, n_pages, C = 4, 6, 32, 8
    rng = np.random.default_rng(seed)
    own = [int(p) for p in rng.permutation(np.arange(1, n_pages))]
    bt = np.zeros((4, n_pg), np.int32)
    bt[0] = own[0:6]
    bt[1] = [own[0]] + own[6:11]     # a cached prefix page rows 0, 1 share
    bt[2, :2] = own[11:13]           # the rest reserved lazily, on page 0
    # row 3 is idle: no pages, no tokens; rows 0 and 1 start past the
    # prefix page, which neither writes
    pos = np.asarray([page, page, 0, 0], np.int32)
    lens = np.asarray([7, 5, 6, 0], np.int32)
    toks = rng.integers(2, cfg.vocab_size, (4, C)).astype(np.int32)
    toks[lens[:, None] <= np.arange(C)[None, :]] = 0

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    pool = tuple({k: jax.random.normal(next(keys), v.shape, v.dtype)
                  for k, v in c.items()}
                 for c in lm.init_paged_cache(cfg, n_pages, page))
    pools = [pool, jax.tree.map(jnp.copy, pool)]
    steps = {
        "prefill": (donate_cache(jit_step(lm.prefill_step_paged, cfg)),
                    jax.jit(functools.partial(paged_step_reference, cfg))),
        "decode": (donate_cache(jit_step(lm.decode_step_paged, cfg)),
                   jax.jit(lambda p, c, t, pos, bt: paged_step_reference(
                       cfg, p, c, t[:, None], pos, None, bt))),
    }
    new, ref = steps["prefill"]
    given = pools[0]
    got = new(params, given, toks, pos, lens, bt)
    want = ref(params, pools[1], toks, pos, lens, bt)
    _equal(got, want)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(given))
    pools = [got[1], want[1]]
    pos = pos + lens
    for _ in range(3):
        if pos[2] // page >= 2 and bt[2, pos[2] // page] == 0:
            bt[2, pos[2] // page] = own[13]   # reserved as the row grows
        tok = np.where(lens > 0, np.argmax(np.asarray(want[0]), -1), 0)
        new, ref = steps["decode"]
        got = new(params, pools[0], tok.astype(np.int32), pos, bt)
        want = ref(params, pools[1], tok.astype(np.int32), pos, bt)
        _equal(got, want)
        pools = [got[1], want[1]]
        pos = pos + (lens > 0)
    assert bt[2, 2] == own[13]


def record_donations(engine):
    """Record ``(step, cache)`` for every launch of ``engine``'s steps;
    returns the list it appends to."""
    given, launch = [], engine._launch

    def recording(step):
        fn = launch(step)

        def call(params, cache, *args):
            given.append((step, cache))
            return fn(params, cache, *args)
        return call
    engine._launch = recording
    return given


def assert_donated(given, *held):
    """Every cache recorded in ``given`` was donated (deleted), and no leaf
    of the caches the engine holds (``held``) is."""
    assert given
    assert all(leaf.is_deleted()
               for _, c in given for leaf in jax.tree.leaves(c))
    assert not any(leaf.is_deleted() for c in held
                   for leaf in jax.tree.leaves(c))
