"""The serving expert layer and q/k norm, on the CPU at a tiny size.

* ``moe.moe_serve`` holds a share of the experts: the parts that the
  shares of every offset give add up to the whole layer's reference;
* its routing is dropless and per token: nothing is dropped, and a token's
  output does not depend on the other rows of its dispatch;
* per-head q/k RMSNorm gives the same logits through ``forward``,
  ``decode_step``, ``prefill_step`` and the paged steps;
* a tiny Qwen3-MoE served through ``ServingEngine`` (paged K/V, batched
  prefill) matches a plain float32 forward's logits and the token-by-token
  prefill's streams, and counts its routed tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm
from repro.nn import layers
from repro.nn import moe as moe_lib
from repro.serving import ServeConfig, ServingEngine
from repro.serving.engine import Request

D, F, E, K = 32, 48, 8, 2


def _moe(key=0):
    return moe_lib.moe_init(jax.random.PRNGKey(key), D, F, E)


def _share(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("wi_gate", "wi_up", "wo")})


@pytest.mark.parametrize("backend", ["xla", "arrayflex"])
def test_held_shares_add_up_to_the_whole_layer(backend):
    """Shares of 2 experts at offsets 0, 2, 4, 6 of 8 (top-2): each routes
    over all 8, computes only its own experts' part, and the parts sum to
    ``moe_apply_reference`` of the whole layer."""
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, D))
    want = moe_lib.moe_apply_reference(p, x, top_k=K)
    parts, routed = [], 0
    for first in (0, 2, 4, 6):
        y, r = moe_lib.moe_serve(_share(p, first, 2), x, top_k=K,
                                 first=first, compute_dtype=jnp.float32,
                                 backend=backend)
        parts.append(y)
        routed = routed + r
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # every token's K choices land in exactly one share
    assert (np.asarray(routed) == K).all()
    # a share alone is not the layer
    assert not np.allclose(np.asarray(parts[0]), np.asarray(want),
                           atol=1e-3)


def test_serving_routing_is_dropless_and_per_token():
    """Every (token, expert) pair is computed however many tokens pick the
    same expert, and each row's output is the same served alone or
    beside others; capacity routing at the same layer drops pairs."""
    p = _moe(2)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16, D))
    y, routed = moe_lib.moe_serve(p, x, top_k=K, compute_dtype=jnp.float32)
    assert int(routed.sum()) == x.shape[0] * x.shape[1] * K
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(moe_lib.moe_apply_reference(p, x, top_k=K)),
        rtol=1e-5, atol=1e-5)
    for b in range(x.shape[0]):
        for s in (0, 7):
            alone, _ = moe_lib.moe_serve(p, x[b:b + 1, s:s + 1], top_k=K,
                                         compute_dtype=jnp.float32)
            np.testing.assert_array_equal(np.asarray(alone[0, 0]),
                                          np.asarray(y[b, s]))
    capped, _ = moe_lib.moe_apply(p, x, top_k=K, capacity_factor=0.25,
                                  compute_dtype=jnp.float32)
    assert not np.allclose(np.asarray(capped), np.asarray(y), atol=1e-3)


def test_capacity_routing_refuses_a_held_share():
    p = _share(_moe(), 2, 4)
    x = jnp.ones((1, 4, D))
    with pytest.raises(ValueError, match="moe_serve"):
        moe_lib.moe_apply(p, x, top_k=K)


def _qk_cfg():
    return reduced(get_config("qwen2-0.5b"), qk_norm=True,
                   compute_dtype="float32")


def test_qk_norm_gives_the_same_logits_on_every_path():
    """Teacher-forced ``decode_step``, one ``prefill_step`` chunk and the
    paged prefill and decode steps reproduce ``forward``'s logits with q/k
    norm on (K/V held in bf16 by the caches, hence the tolerance of the
    dense model's own test), and the norm changes the logits."""
    cfg = _qk_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    assert "q_norm" in params["blocks"][0]["attn"]
    S = 12
    toks = jnp.asarray(np.random.RandomState(0).randint(2, cfg.vocab_size,
                                                        (1, S)), jnp.int32)
    full, _, _ = lm.forward(cfg, params, {"tokens": toks})
    tol = dict(rtol=3e-2, atol=3e-2)
    cache = lm.init_cache(cfg, 1, 16)
    for t in range(S):
        lg, cache = lm.decode_step(cfg, params, cache, toks[:, t],
                                   jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, t]),
                                   **tol)
    lg, _ = lm.prefill_step(cfg, params, lm.init_cache(cfg, 1, 16), toks,
                            jnp.zeros(1, jnp.int32), jnp.full(1, S))
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, -1]),
                               **tol)
    bt = jnp.asarray([[1]], jnp.int32)
    pool = lm.init_paged_cache(cfg, 2, 16)
    lg, pool = lm.prefill_step_paged(cfg, params, pool, toks[:, :S - 1],
                                     jnp.zeros(1, jnp.int32),
                                     jnp.full(1, S - 1), bt)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, -2]),
                               **tol)
    lg, _ = lm.decode_step_paged(cfg, params, pool, toks[:, S - 1],
                                 jnp.full(1, S - 1), bt)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(full[0, -1]),
                               **tol)
    plain = dataclasses.replace(cfg, qk_norm=False)
    no_norm = jax.tree.map(lambda a: a, params)
    for blk in no_norm["blocks"]:
        del blk["attn"]["q_norm"], blk["attn"]["k_norm"]
    other, _, _ = lm.forward(plain, no_norm, {"tokens": toks})
    assert np.abs(np.asarray(other - full)).max() > 0.1


def _moe_cfg():
    """Tiny Qwen3-MoE: 8 experts top-2, this device holding 4 from the
    third; float32 compute."""
    cfg = reduced(get_config("qwen3-moe-30b-a3b"), compute_dtype="float32")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, experts_held=4, expert_first=2))


def _plain_forward(cfg, params, toks):
    """float32 forward in plain jax.numpy: no kernels, no cache, the held
    experts' part of each expert layer, every product at HIGHEST."""
    hi = jax.lax.Precision.HIGHEST
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    eps, m = cfg.rms_eps, cfg.moe
    S = toks.shape[1]

    def norm(x, s):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * s

    def mm(a, b):
        return jnp.einsum("...k,kn->...n", a, b, precision=hi)

    x = params["embed"]["table"][toks]
    pos = jnp.arange(S)[None]
    for li in range(cfg.n_layers):
        w = jax.tree.map(lambda a: a[li], params["blocks"][0])
        a = w["attn"]
        h = norm(x, w["ln1"]["scale"])
        q = norm(mm(h, a["wq"]["w"]).reshape(1, S, H, hd),
                 a["q_norm"]["scale"])
        k = norm(mm(h, a["wk"]["w"]).reshape(1, S, KV, hd),
                 a["k_norm"]["scale"])
        v = mm(h, a["wv"]["w"]).reshape(1, S, KV, hd)
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / hd ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=hi).reshape(1, S, H * hd)
        x = x + mm(o, a["wo"]["w"])
        h = norm(x, w["ln2"]["scale"])
        p = jax.nn.softmax(mm(h, w["moe"]["router"]), -1)
        vals, idx = jax.lax.top_k(p, m.top_k)
        vals = vals / vals.sum(-1, keepdims=True)
        for e in range(m.held):
            wgt = jnp.sum(jnp.where(idx == m.expert_first + e, vals, 0.0), -1)
            g = jax.nn.silu(mm(h, w["moe"]["wi_gate"][e]))
            x = x + wgt[..., None] * mm(g * mm(h, w["moe"]["wi_up"][e]),
                                        w["moe"]["wo"][e])
    x = norm(x, params["final_norm"]["scale"])
    return mm(x, params["lm_head"]["w"])


def test_tiny_qwen3_moe_paged_steps_match_a_plain_forward():
    """The paged prefill of a prompt and the decode of its last token, over
    a float32 pool, give a plain float32 forward's logits; each step counts
    the (token, held expert) pairs of its live tokens."""
    cfg = _moe_cfg()
    assert lm.supports_paged_kv(cfg) and lm.supports_batched_prefill(cfg)
    params = lm.init_params(cfg, jax.random.PRNGKey(4))
    assert params["blocks"][0]["moe"]["wi_gate"].shape[1] == 4
    assert params["blocks"][0]["moe"]["router"].shape[-1] == 8
    S = 10
    toks = jnp.asarray(np.random.RandomState(5).randint(2, cfg.vocab_size,
                                                        (1, S)), jnp.int32)
    want = _plain_forward(cfg, params, toks)
    bt = jnp.asarray([[1], [0]], jnp.int32)
    pool = lm.init_paged_cache(cfg, 2, 16, dtype=jnp.float32)
    chunk = jnp.concatenate([toks[:, :S - 1], jnp.zeros((1, 3), jnp.int32)],
                            axis=1)
    chunk = jnp.concatenate([chunk, chunk])        # row 1 is idle
    lg, pool, routed = lm.prefill_step_paged(
        cfg, params, pool, chunk, jnp.zeros(2, jnp.int32),
        jnp.asarray([S - 1, 0]), bt)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(want[0, -2]),
                               rtol=1e-4, atol=1e-4)
    assert routed.shape == (2,) and int(routed[1]) == 0
    assert 0 < int(routed[0]) <= (S - 1) * cfg.n_layers * cfg.moe.top_k
    lg, _, routed = lm.decode_step_paged(
        cfg, params, pool, jnp.asarray([toks[0, S - 1], 0]),
        jnp.asarray([S - 1, 0]), bt)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(want[0, -1]),
                               rtol=1e-4, atol=1e-4)
    assert routed.shape == (2,)


def test_tiny_qwen3_moe_paged_steps_match_the_per_layer_pool_path():
    """The tiny Qwen3-MoE's paged steps, with the pool donated and updated
    in place, give the logits, ``routed`` and the whole pool, bit for bit,
    of the steps that passed the pool through the layer scan
    (tests/_paged_pool): a prefill and three decodes over a shared
    prefix page, lazily reserved table entries and an idle row."""
    from _paged_pool import check_steps_against_reference
    cfg = _moe_cfg()
    check_steps_against_reference(cfg, lm.init_params(cfg,
                                                      jax.random.PRNGKey(7)))


def test_tiny_qwen3_moe_serves_paged_as_token_prefill_does():
    """Through ``ServingEngine``: paged K/V with batched prefill, the dense
    batched path and token-by-token prefill give the same greedy streams;
    the paged engine counts routed pairs and expert rows."""
    cfg = _moe_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(6))
    prompts = [[5, 6, 7, 8, 9, 10, 11], [12, 13, 14], [15] * 20, [16, 17]]

    def run(**kw):
        eng = ServingEngine(cfg, params, ServeConfig(max_batch=2, max_seq=32,
                                                     prefill_chunk=8, **kw))
        reqs = [Request(prompt=p, max_new_tokens=6, rid=i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        assert all(r.outcome == "ok" for r in reqs)
        return [r.out_tokens for r in reqs], eng

    paged, eng = run(kv_pages=12, page_size=8, prefix_cache=True)
    assert eng.prefill_mode == "batched" and eng.paged
    assert run(prefill_mode="token")[0] == paged
    assert run(prefill_mode="batched")[0] == paged
    st = eng.stats
    live = st["prefill_tokens"] + st["decode_tokens"]
    # each live token picks top_k of 8 experts in each layer; 4 are held
    assert 0 < st["moe_assignments"] <= live * cfg.n_layers * cfg.moe.top_k
    rows = (st["prefill_dispatches"] * 2 * 8 + st["decode_dispatches"] * 2)
    assert st["moe_expert_rows"] == rows * cfg.n_layers * 4
