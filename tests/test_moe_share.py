"""The dropless expert layer that holds a share of the experts
(``moe_expert_share`` / ``ExpertShare``): the shares' routed parts, with
the shared expert counted once, add up to the whole layer as the plain
reference (chipbench/references/kimi_linear.py) computes it uncut, and
no pair is dropped however skewed the router."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu.ops import moe

ref = importlib.import_module("chipbench.references.kimi_linear")

D, F, E, K, SCALE = 32, 24, 16, 4, 2.446
CFG = dict(num_experts_per_token=K, moe_renormalize=True,
           routed_scaling_factor=SCALE, num_experts_total=E)


def weights(skew=0.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda k, *s: 0.2 * jax.random.normal(k, s)
    # skew: the selection bias makes every token choose expert 3
    bias = (0.01 * jax.random.normal(ks[1], (E,))).at[3].add(skew)
    return dict(router=n(ks[0], E, D), bias=bias,
                gate=n(ks[2], E, D, F), up=n(ks[3], E, D, F),
                down=n(ks[4], E, F, D), shared_gate=n(ks[5], F, D),
                shared_up=n(ks[6], F, D), shared_down=n(ks[7], D, F),
                x=jax.random.normal(ks[8], (2, 40, D)))


def reference_layer(w, x, first, held):
    """The reference's expert layer holding experts first..first+held-1."""
    cfg = dict(CFG, held_experts_first=first, num_experts=held)
    sl = slice(first, first + held)
    named = {"router": w["router"], "experts_gate": w["gate"][sl],
             "experts_up": w["up"][sl], "experts_down": w["down"][sl],
             "shared_gate": w["shared_gate"], "shared_up": w["shared_up"],
             "shared_down": w["shared_down"]}
    return ref._expert_layer(cfg, named, "", x, w["bias"], "highest")


def share(w, x, first, held):
    sl = slice(first, first + held)
    return moe.moe_expert_share(
        x, w["router"], w["bias"], w["gate"][sl], w["up"][sl], w["down"][sl],
        held_first=first, top_k=K, scale=SCALE)


@pytest.mark.parametrize("tile", [4096, 48],
                         ids=["wide_tiles", "narrow_tiles"])
@pytest.mark.parametrize("skew", [0.0, 4.0], ids=["even", "skewed"])
@pytest.mark.parametrize("shares", [1, 4], ids=["whole", "four_shares"])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, shares, skew,
                                              tile):
    monkeypatch.setattr(moe, "PAIRS_PER_TILE", tile)
    w = weights(skew)
    x, held = w["x"], E // shares
    total = ref._swiglu("highest", x, w["shared_gate"], w["shared_up"],
                        w["shared_down"])                   # counted once
    pairs = 0
    for first in range(0, E, held):
        y, load = share(w, x, first, held)
        total = total + y
        load = np.asarray(load)
        # no pair is dropped: what is not held here fell elsewhere
        assert load.sum() == x.shape[0] * x.shape[1] * K
        pairs += load[:-1].sum()
    assert pairs == x.shape[0] * x.shape[1] * K
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(reference_layer(w, x, 0, E)),
        rtol=1e-5, atol=2e-6)
    if skew:
        _, load = share(w, x, 0, held)
        assert load[3] == x.shape[0] * x.shape[1]          # every token


@pytest.mark.parametrize("shares,total,k", [(8, 128, 8)],
                         ids=["eight_shares_of_16_of_128"])
def test_softmax_routed_shares_add_up_to_the_uncut_layer(shares, total, k):
    """The softmax-scored layer with no shared expert and no selection
    bias (Keye-VL's: 128 experts, 8 a token, renormalised): eight
    ranks' shares of 16 experts sum to what the plain reference
    (chipbench/references/keye_vl.py) computes with all 128 held."""
    keye = importlib.import_module("chipbench.references.keye_vl")
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    n = lambda key, *s: 0.2 * jax.random.normal(key, s)
    w = {"router": n(ks[0], total, D), "experts_gate": n(ks[1], total, D, F),
         "experts_up": n(ks[2], total, D, F),
         "experts_down": n(ks[3], total, F, D)}
    x = jax.random.normal(ks[4], (2, 40, D))
    held = total // shares
    summed, pairs = 0.0, 0
    for first in range(0, total, held):
        sl = slice(first, first + held)
        y, load = moe.moe_expert_share(
            x, w["router"], jnp.zeros((total,)), w["experts_gate"][sl],
            w["experts_up"][sl], w["experts_down"][sl], held_first=first,
            top_k=k, score="softmax")
        summed = summed + y
        pairs += int(np.asarray(load)[:-1].sum())
    assert pairs == x.shape[0] * x.shape[1] * k         # none dropped
    cfg = dict(num_experts=total, held_experts_first=0,
               num_experts_per_tok=k, norm_topk_prob=True)
    np.testing.assert_allclose(
        np.asarray(summed),
        np.asarray(keye._expert_layer(cfg, w, "", x, "highest")),
        rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("eps", [1e-6, 0.25], ids=["published", "coarse"])
def test_four_shares_of_8_sigmoid_experts_add_up_to_the_uncut_layer(eps):
    """LFM2's layer (32 experts, 4 a token, sigmoid scores, a selection
    bias for the choice only, renormalised with ``renorm_eps`` added to
    the chosen scores' sum, scale 1, no shared expert): four ranks'
    shares of 8 experts sum to what the plain reference
    (chipbench/references/lfm2_moe.py) computes with all 32 held.  The
    coarse case shows the argument is read: at 0.25 it moves the result
    by a tenth."""
    lfm2 = importlib.import_module("chipbench.references.lfm2_moe")
    total, shares, k = 32, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    n = lambda key, *s: 0.2 * jax.random.normal(key, s)
    w = {"router": n(ks[0], total, D), "experts_gate": n(ks[1], total, D, F),
         "experts_up": n(ks[2], total, D, F),
         "experts_down": n(ks[3], total, F, D)}
    bias = 0.3 * jax.random.normal(ks[5], (total,))
    x = jax.random.normal(ks[4], (2, 40, D))
    held = total // shares

    def summed(eps):
        out, pairs = 0.0, 0
        for first in range(0, total, held):
            sl = slice(first, first + held)
            y, load = moe.moe_expert_share(
                x, w["router"], bias, w["experts_gate"][sl],
                w["experts_up"][sl], w["experts_down"][sl],
                held_first=first, top_k=k, scale=1, renorm_eps=eps)
            out = out + y
            pairs += int(np.asarray(load)[:-1].sum())
        assert pairs == x.shape[0] * x.shape[1] * k         # none dropped
        return np.asarray(out)

    cfg = dict(num_experts=total, held_experts_first=0,
               num_experts_per_tok=k, norm_topk_prob=True,
               routed_scaling_factor=1)
    saved = lfm2.RENORM_EPS
    lfm2.RENORM_EPS = eps
    try:
        want = np.asarray(lfm2._expert_layer(cfg, w, "", x, bias, "highest"))
    finally:
        lfm2.RENORM_EPS = saved
    np.testing.assert_allclose(summed(eps), want, rtol=1e-5, atol=2e-6)
    if eps > 1e-3:
        plain = summed(0.0)
        assert 0.05 < np.abs(plain - want).max() / np.abs(want).max() < 0.3


@pytest.mark.parametrize("tile", [4096, 48],
                         ids=["wide_tiles", "narrow_tiles"])
def test_gradients_of_a_share_match_the_reference(monkeypatch, tile):
    monkeypatch.setattr(moe, "PAIRS_PER_TILE", tile)
    w = weights(skew=1.0, seed=1)
    first, held = 4, 4

    def routed(fn):
        def loss(x, gate, router):
            ww = dict(w, gate=w["gate"].at[first:first + held].set(gate),
                      router=router)
            return jnp.sum(jnp.square(fn(ww, x)))
        return jax.grad(loss, argnums=(0, 1, 2))(
            w["x"], w["gate"][first:first + held], w["router"])

    got = routed(lambda ww, x: share(ww, x, first, held)[0])
    want = routed(lambda ww, x: reference_layer(ww, x, first, held)
                  - ref._swiglu("highest", x, ww["shared_gate"],
                                ww["shared_up"], ww["shared_down"]))
    for g, r, name in zip(got, want, ("x", "experts_gate", "router")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(r))),
                                   err_msg=name)


@pytest.mark.parametrize("pairs,held,total,want", [
    (8192 * 8, 8, 256, 4096),    # 2,048 expected: half of one tile
    (8192 * 4, 8, 64, 6144),     # 4,096 expected: two thirds of one tile
    (2 * 8192 * 4, 8, 64, 5120),        # 8,192: two tiles, 1.6 filled
    (3 * 8192 * 4, 8, 64, 5120),        # 12,288: three tiles, 2.4 filled
    (32 * 8192 * 8, 8, 256, 6144),      # 65,536: eleven tiles
    (4096 * 8, 8, 256, 3072),    # a shorter sequence takes a smaller tile
    (320, 4, 16, 320),           # fewer pairs than a tile: toy sizes
], ids=["kimi_linear_cell", "glm_cell", "two_sequences", "three_sequences",
        "thirty_two_sequences", "half_a_sequence", "toy"])
def test_the_even_load_never_ends_at_a_tiles_edge(pairs, held, total, want):
    expected = pairs * held / total
    rows = moe._tile_rows(pairs, expected)
    assert rows == want and rows <= 1.5 * moe.PAIRS_PER_TILE
    if rows < pairs:
        # half a PAIRS_PER_TILE of room in the last tile the load reaches
        tiles = -(-expected // rows)
        assert tiles * rows - expected >= moe.PAIRS_PER_TILE // 2
        assert expected - (tiles - 1) * rows > 0


def test_the_selection_bias_chooses_and_takes_no_gradient():
    w = weights()
    bias = w["bias"].at[5].add(10.0)            # everyone now chooses 5
    _, load = moe.moe_expert_share(
        w["x"], w["router"], bias, w["gate"][4:8], w["up"][4:8],
        w["down"][4:8], held_first=4, top_k=K, scale=SCALE)
    assert load[1] == w["x"].shape[0] * w["x"].shape[1]
    grad = jax.grad(lambda b: jnp.sum(moe.moe_expert_share(
        w["x"], w["router"], b, w["gate"][4:8], w["up"][4:8],
        w["down"][4:8], held_first=4, top_k=K, scale=SCALE)[0]))(bias)
    assert not np.asarray(grad).any()


def test_expert_share_block_counts_its_load_and_rejects_a_bad_share():
    from mxtpu.models.kimi_linear import expert_loads
    from mxtpu.models.parts import ExpertShare

    layer = ExpertShare(D, F, E, K, held=(4, 4), routed_scale=SCALE,
                        prefix="probe_moe_")
    layer.initialize(mx.init.Xavier())
    x = mx.nd.array(np.asarray(weights()["x"]))
    y = layer(x)
    assert y.shape == x.shape
    load = expert_loads()["probe_moe"]
    assert sum(load["held"]) + load["elsewhere"] == 2 * 40 * K
    layer(x)
    again = expert_loads()["probe_moe"]
    assert again["held"] == load["held"]                # the newest pass
    assert again["held_sum"] == [2.0 * n for n in load["held"]]
    from mxtpu.observability.metrics import default_registry
    snap = default_registry().snapshot()
    assert snap["moe.probe_moe.elsewhere"] == load["elsewhere"]
    assert not [p for p in layer.collect_params().values()
                if p.name.endswith(("load", "load_sum", "select_bias"))
                and p.grad_req != "null"]
    with pytest.raises(ValueError, match="not within"):
        ExpertShare(D, F, E, K, held=(14, 4))
