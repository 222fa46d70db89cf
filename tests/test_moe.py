"""MoE dispatch properties (unit + hypothesis; the hypothesis test skips
itself via pytest.importorskip when the dev-only dep is absent): no pair
dropped, Nemotron-H routing, relu² experts, and expert-parallel shares
that add up to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import MoEConfig
from repro.models.moe import init_moe, moe_apply, load_balance_loss, router_topk


def _setup(E=4, K=2, D=16, F=32, scoring="softmax", seed=0, **kw):
    cfg = MoEConfig(num_experts=E, top_k=K, d_ff_expert=F, **kw)
    params = init_moe(jax.random.PRNGKey(seed), D, cfg)
    return cfg, params


def _dense_moe(params, xf, cfg, act="silu", scoring="softmax"):
    """Every token through each of its chosen held experts, one at a
    time, in numpy: the definition the dispatch must meet."""
    bias = params.get("router_bias")
    w, ids, _ = router_topk(xf @ params["router"], cfg.top_k, scoring,
                            bias, cfg.routed_scaling)
    out = np.zeros(xf.shape, np.float32)
    for n in range(xf.shape[0]):
        for j in range(cfg.top_k):
            e = int(ids[n, j]) - cfg.expert_offset
            if not 0 <= e < cfg.held:
                continue
            up = xf[n] @ params["w_up"][e]
            if act == "silu":
                h = jax.nn.silu(xf[n] @ params["w_gate"][e]) * up
            else:
                h = jnp.square(jax.nn.relu(up))
            out[n] += float(w[n, j]) * np.asarray(h @ params["w_down"][e])
    return out


def test_moe_output_shape_and_finite():
    cfg, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, aux, stats = jax.jit(lambda p, x: moe_apply(p, x, cfg))(params, x)
    assert y.shape == x.shape
    assert np.all(np.isfinite(np.asarray(y)))
    assert np.isfinite(float(aux))
    assert float(stats[0]) == 2 * 8 * 2  # every pair, all experts held


def test_no_drop_moe_matches_dense_per_expert_computation_under_skew():
    """A router that sends most tokens to expert 0 (the old capacity of
    1.25 x N K / E would have dropped most of them): every pair is
    computed, as the per-token evaluation computes it."""
    cfg, params = _setup(E=4, K=2)
    params["router"] = params["router"].at[:, 0].add(3.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 16)) + 0.5
    y, _, stats = moe_apply(params, x, cfg)
    xf = x.reshape(-1, 16)
    _, ids, _ = router_topk(xf @ params["router"], 2)
    assert int(np.sum(np.asarray(ids) == 0)) > 1.25 * 24 * 2 / 4
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 16)),
                               _dense_moe(params, xf, cfg),
                               rtol=2e-3, atol=2e-3)
    assert float(stats[1]) == np.max(np.bincount(np.asarray(ids).ravel()))


def test_moe_gradient_matches_dense_per_expert_computation():
    cfg, params = _setup(E=4, K=2)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 16))

    def loss(p, fn):
        return jnp.sum(jnp.square(fn(p)))

    g = jax.grad(loss)(params, lambda p: moe_apply(p, x, cfg)[0])

    def dense(p):
        xf = x.reshape(-1, 16)
        w, ids, _ = router_topk(xf @ p["router"], 2)
        y = 0.0
        for e in range(4):
            gate = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
            h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
            y = y + gate[:, None] * (h @ p["w_down"][e])
        return y

    g_ref = jax.grad(loss)(params, dense)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_router_sigmoid_bias_normalise_and_scale():
    """Nemotron-H / DeepSeek-v3 routing: the bias moves the choice only;
    the chosen experts' sigmoid scores are renormalised, then scaled."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (10, 8)) * 2
    bias = jnp.zeros((8,)).at[5].set(10.0)  # expert 5 always chosen
    w, ids, _ = router_topk(logits, 3, "sigmoid", bias, 2.5)
    assert np.all(np.any(np.asarray(ids) == 5, axis=-1))
    scores = jax.nn.sigmoid(logits)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(w),
                               2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-5)


def test_relu2_experts_are_ungated():
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16)
    params = init_moe(jax.random.PRNGKey(1), 8, cfg, act="relu2")
    assert "w_gate" not in params
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 10, 8))
    y, _, _ = moe_apply(params, x, cfg, act="relu2")
    np.testing.assert_allclose(
        np.asarray(y.reshape(-1, 8)),
        _dense_moe(params, x.reshape(-1, 8), cfg, act="relu2"),
        rtol=2e-3, atol=2e-4)


def test_expert_shares_sum_to_the_uncut_layer():
    """16 devices hold 2 of 32 experts each: the held-expert parts of the
    16 shares, with the shared expert counted once, add up to the layer
    that holds all 32, and their rows to every token's 6 choices."""
    E, K, D, shares = 32, 6, 16, 16
    full = MoEConfig(num_experts=E, top_k=K, d_ff_expert=24,
                     num_shared_experts=1, d_ff_shared=40, router_bias=True,
                     routed_scaling=2.5)
    params = init_moe(jax.random.PRNGKey(0), D, full, act="relu2")
    params["router_bias"] = jax.random.normal(jax.random.PRNGKey(3), (E,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, D))
    y_full, _, _ = moe_apply(params, x, full, act="relu2", scoring="sigmoid")
    per = E // shares
    total, rows = 0.0, 0.0
    for s in range(shares):
        cfg = dataclasses.replace(full, experts_held=per, expert_offset=s * per)
        p = dict(params, w_up=params["w_up"][s * per:(s + 1) * per],
                 w_down=params["w_down"][s * per:(s + 1) * per])
        if s:  # every device computes the shared expert alike
            p["shared"] = jax.tree.map(jnp.zeros_like, params["shared"])
        y, _, stats = moe_apply(p, x, cfg, act="relu2", scoring="sigmoid")
        total = total + y
        rows += float(stats[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               rtol=1e-4, atol=1e-5)
    assert rows == 2 * 9 * K


def test_a_share_of_the_experts_gives_the_routing_no_gradient():
    """The routing weights' gradient needs every chosen expert's output:
    the whole layer trains the router and the layers below through them,
    a share that holds some of the experts does neither."""
    full = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                     num_shared_experts=1, router_bias=True,
                     router_aux_weight=0.0)
    params = init_moe(jax.random.PRNGKey(0), 8, full, act="relu2")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 8))
    share = dataclasses.replace(full, experts_held=4)
    p_share = dict(params, w_up=params["w_up"][:4],
                   w_down=params["w_down"][:4])

    def grads(p, cfg, routed_only):
        def loss(p, x):
            y = moe_apply(p, x, cfg, act="relu2", scoring="sigmoid")[0]
            return jnp.sum(jnp.square(y))
        if routed_only:  # the shared expert's own path to x left out
            p = dict(p, shared=jax.tree.map(jnp.zeros_like, p["shared"]))
        return jax.grad(loss, argnums=(0, 1))(p, x)

    g_full, _ = grads(params, full, False)
    g_share, _ = grads(p_share, share, False)
    assert float(jnp.abs(g_full["router"]).max()) > 0
    assert float(jnp.abs(g_share["router"]).max()) == 0
    assert float(jnp.abs(g_share["w_up"]).max()) > 0
    # with the shared expert zeroed, x's gradient comes through the held
    # experts' inputs alone, as a stop on the weights leaves it
    _, gx_share = grads(p_share, share, True)
    w, ids, _ = router_topk(x.reshape(-1, 8) @ params["router"], 2,
                            "sigmoid", params["router_bias"])
    w = np.where(np.asarray(ids) < 4, np.asarray(w), 0.0)

    def held_only(x):
        xf = x.reshape(-1, 8)
        y = 0.0
        for e in range(4):
            gate = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
            h = jnp.square(jax.nn.relu(xf @ params["w_up"][e]))
            y = y + gate[:, None] * (h @ params["w_down"][e])
        return jnp.sum(jnp.square(y))

    np.testing.assert_allclose(np.asarray(gx_share),
                               np.asarray(jax.grad(held_only)(x)),
                               rtol=2e-3, atol=2e-4)


def test_router_sigmoid_weights_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(0), (10, 8)) * 2
    w, ids, probs = router_topk(logits, 3, scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


def test_load_balance_loss_uniform_is_one():
    """Perfectly uniform routing gives loss = 1 (E · Σ (1/E)·(1/E) · E)."""
    E = 8
    N = 800
    probs = jnp.full((N, E), 1.0 / E)
    ids = jnp.stack([jnp.arange(N) % E, (jnp.arange(N) + 1) % E], -1)
    lb = load_balance_loss(probs, ids, E)
    np.testing.assert_allclose(float(lb), 1.0, rtol=1e-5)


def test_moe_dispatch_invariants():
    """Property: outputs finite; aux in [0, weight·E]; shape preserved;
    every (token, choice) pair is dispatched."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        E=st.sampled_from([2, 4, 8]),
        K=st.integers(1, 2),
        T=st.integers(2, 24),
        seed=st.integers(0, 5),
    )
    def check(E, K, T, seed):
        cfg = MoEConfig(num_experts=E, top_k=min(K, E), d_ff_expert=8,
                        router_aux_weight=0.01)
        params = init_moe(jax.random.PRNGKey(seed), 8, cfg)
        x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, T, 8))
        y, aux, stats = moe_apply(params, x, cfg)
        assert float(stats[0]) == T * cfg.top_k
        assert y.shape == x.shape
        assert np.all(np.isfinite(np.asarray(y)))
        assert 0.0 <= float(aux) <= 0.01 * E * cfg.top_k * 4

    check()


def test_shared_expert_added():
    cfg = MoEConfig(num_experts=2, top_k=1, d_ff_expert=8,
                    num_shared_experts=1)
    params = init_moe(jax.random.PRNGKey(0), 8, cfg)
    assert "shared" in params
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 8))
    y_with, _, _ = moe_apply(params, x, cfg)
    p2 = dict(params)
    p2["shared"] = jax.tree.map(jnp.zeros_like, params["shared"])
    y_zero_shared, _, _ = moe_apply(p2, x, cfg)
    assert float(jnp.sum(jnp.abs(y_with - y_zero_shared))) > 1e-4
