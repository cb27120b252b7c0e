"""Tests for the Pallas flash-attention kernel (interpret mode on CPU)."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu.ops.pallas import flash_attention
from mxtpu.ops.pallas.flash_attention import _dense_attention


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 128, 16
    return tuple(jnp.array(rng.randn(B, H, T, D).astype("float32"))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(qkv, causal):
    q, k, v = qkv
    out = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64)
    ref = _dense_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_flash_gradients(qkv):
    q, k, v = qkv
    g = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, q_block=64, kv_block=64).sum())(q)
    gref = jax.grad(lambda q: _dense_attention(
        q, k, v, 1.0 / np.sqrt(q.shape[-1]), True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), rtol=1e-4,
                               atol=1e-5)


def test_flash_unpadded_length(qkv):
    q, k, v = (a[:, :, :100] for a in qkv)
    out = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    ref = _dense_attention(q, k, v, 1.0 / np.sqrt(16), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_flash_op_taped(qkv):
    q, k, v = qkv
    qn = mx.nd.array(np.asarray(q))
    qn.attach_grad()
    with mx.autograd.record():
        out = mx.nd.flash_attention(qn, mx.nd.array(np.asarray(k)),
                                    mx.nd.array(np.asarray(v)), causal=True)
        out.sum().backward()
    assert float(np.abs(qn.grad.asnumpy()).sum()) > 0


def test_mha_uses_flash_matches_dense():
    """MultiHeadAttention flash path vs dense path parity."""
    from mxtpu import models
    np.random.seed(0)
    x = mx.nd.array(np.random.randn(2, 32, 16).astype("float32"))
    mha = models.MultiHeadAttention(16, 4, causal=True, use_flash=True)
    mha.initialize()
    out_flash = mha(x).asnumpy()
    mha._use_flash = False
    out_dense = mha(x).asnumpy()
    np.testing.assert_allclose(out_flash, out_dense, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_all_grads_match_dense(qkv, causal):
    """Full dq/dk/dv from the Pallas backward kernel vs dense autodiff
    (round-3 verdict item 5; a non-trivial cotangent exercises delta)."""
    q, k, v = qkv
    rng = np.random.RandomState(7)
    ct = jnp.asarray(rng.randn(*q.shape).astype("float32"))

    def loss(fn):
        def f(q_, k_, v_):
            return (fn(q_, k_, v_) * ct).sum()
        return f

    flash = loss(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, q_block=64, kv_block=64))
    dense = loss(lambda a, b, c: _dense_attention(
        a, b, c, 1.0 / np.sqrt(q.shape[-1]), causal))
    g_flash = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_pallas_backward_unpadded_length(qkv):
    """T not a multiple of the block size: padded rows/keys must
    contribute zero gradient."""
    q, k, v = (a[:, :, :100] for a in qkv)
    g_flash = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, causal=True, q_block=64, kv_block=64).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda a, b, c: _dense_attention(
        a, b, c, 1.0 / np.sqrt(16), True).sum(), argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_pallas_backward_bf16(qkv):
    """bf16 numerics within 1e-2 of the fp32 dense reference."""
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    qf, kf, vf = qkv
    g_flash = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, causal=True, q_block=64, kv_block=64).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda a, b, c: _dense_attention(
        a, b, c, 1.0 / np.sqrt(16), True).sum(),
        argnums=(0, 1, 2))(qf, kf, vf)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf, dtype="float32"),
                                   np.asarray(gd), rtol=1e-1, atol=1e-2,
                                   err_msg=name)


def _tiles_of(monkeypatch, tile):
    """Both kernels held to tiles of at most ``tile`` rows, so that a
    short sequence is walked in several trips."""
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "TILE", tile)
    return fa


def _vjp_both(q, k, v, ct, causal, **blocks):
    """(dq, dk, dv) of the fused backward and of the dense reference
    under the same cotangent."""
    _, flash = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, **blocks), q, k, v)
    _, dense = jax.vjp(lambda a, b, c: _dense_attention(
        a, b, c, 1.0 / np.sqrt(q.shape[-1]), causal), q, k, v)
    return flash(ct), dense(ct)


@pytest.mark.parametrize("tile", [128, 512], ids=["tile128", "tile512"])
@pytest.mark.parametrize("T,causal,blocks", [
    # q_block != kv_block, T a multiple of neither: Tq = 192, Tk = 256
    (150, True, dict(q_block=64, kv_block=128)),
    (150, False, dict(q_block=128, kv_block=64)),
    # three kv blocks: dq accumulates across three grid steps
    (384, True, dict(q_block=128, kv_block=128)),
    (384, False, dict(q_block=128, kv_block=128)),
    # padded keys in the last of three kv blocks, two q blocks
    (300, True, dict(q_block=256, kv_block=128)),
], ids=["causal_q64_k128_T150", "q128_k64_T150", "causal_3kv_T384",
        "3kv_T384", "causal_q256_k128_T300"])
def test_fused_backward_matches_dense_vjp(monkeypatch, tile, T, causal,
                                          blocks):
    """One kernel gives dq, dk and dv: against jax.vjp of the dense
    reference, at the caller's tiles (TILE = 128: the kv-block grid
    axis has several steps and dq is carried across them) and at the
    widened ones."""
    _tiles_of(monkeypatch, tile)
    rng = np.random.RandomState(T + tile)
    q, k, v, ct = (jnp.asarray(rng.randn(2, 2, T, 16).astype("float32"))
                   for _ in range(4))
    got, want = _vjp_both(q, k, v, ct, causal, **blocks)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_is_repeatable_bit_for_bit(monkeypatch, causal):
    """dq's accumulator is zeroed at the first kv block of every head,
    not left from the head or the call before: the same inputs give the
    same bits, in one call across equal heads and from call to call."""
    _tiles_of(monkeypatch, 128)
    rng = np.random.RandomState(11)
    one = [rng.randn(1, 1, 384, 16).astype("float32") for _ in range(4)]
    # three equal heads: head 2's dq must not hold head 1's sum
    q, k, v, ct = (jnp.asarray(np.tile(a, (1, 3, 1, 1))) for a in one)
    first, _ = _vjp_both(q, k, v, ct, causal, q_block=128, kv_block=128)
    again, _ = _vjp_both(q, k, v, ct, causal, q_block=128, kv_block=128)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    dq = np.asarray(first[0])
    np.testing.assert_array_equal(dq[:, 0], dq[:, 1])
    np.testing.assert_array_equal(dq[:, 0], dq[:, 2])


@pytest.mark.parametrize("block,padded,want", [
    (128, 512, 512), (128, 2048, 512), (128, 640, 128), (128, 768, 384),
    (64, 192, 192), (100, 100, 100), (1024, 2048, 1024), (256, 512, 512),
    (128, 8192, 512), (16, 16, 16), (128, 1024, 512), (64, 640, 320),
    (256, 1280, 256), (128, 1536, 512), (32, 160, 160), (128, 256, 256),
    (64, 704, 64), (128, 1664, 128),
])
def test_a_tile_is_the_widest_run_of_whole_blocks(block, padded, want):
    """Both kernels walk the widest run of whole blocks up to TILE rows
    that tiles the padded length: the caller's blocks stay the granule
    of the padding (eleven or thirteen blocks leave one block a tile)."""
    from mxtpu.ops.pallas.flash_attention import _tile

    assert _tile(block, padded) == want
    assert padded % want == 0 and want % block == 0


# ------------------------------- the forward's wide tiles (TILE), PR 34

WIDE_CASES = [
    # T a multiple of neither block: Tq = 192, Tk = 256
    (150, 16, 16, True, dict(q_block=64, kv_block=128)),
    (150, 16, 16, False, dict(q_block=128, kv_block=64)),
    # three tiles of 128 a side (or one of 384), padded keys in the last
    (300, 16, 16, True, dict(q_block=128, kv_block=128)),
    (300, 16, 16, False, dict(q_block=128, kv_block=128)),
    # q tiles of 256 over key tiles of 128 and the other way round
    (300, 16, 16, True, dict(q_block=256, kv_block=64)),
    (384, 16, 16, True, dict(q_block=64, kv_block=128)),
    # values narrower than keys, and both at 256, at a short T
    (160, 192, 128, True, dict(q_block=32, kv_block=32)),
    (160, 256, 256, True, dict(q_block=32, kv_block=32)),
]
WIDE_IDS = ["causal_q64_k128_T150", "q128_k64_T150", "causal_T300", "T300",
            "causal_q256_k64_T300", "causal_q64_k128_T384",
            "causal_D192_Dv128_T160", "causal_D256_Dv256_T160"]


@pytest.mark.parametrize("tile", [128, 512], ids=["tile128", "tile512"])
@pytest.mark.parametrize("T,D,Dv,causal,blocks", WIDE_CASES, ids=WIDE_IDS)
def test_wide_forward_tiles_match_dense(monkeypatch, tile, T, D, Dv, causal,
                                        blocks):
    """Output and all three gradients against the dense reference, at
    the tiles the module chooses (512: one trip, or few) and held to 128
    (several trips a tile of queries: the walk below the diagonal, the
    diagonal, the padded last tile)."""
    _tiles_of(monkeypatch, tile)
    rng = np.random.RandomState(T + D + tile)
    q, k = (jnp.asarray(rng.randn(1, 2, T, D).astype("float32"))
            for _ in range(2))
    v, ct = (jnp.asarray(rng.randn(1, 2, T, Dv).astype("float32"))
             for _ in range(2))
    scale = 1.0 / np.sqrt(D)
    out, flash = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, **blocks), q, k, v)
    want, dense = jax.vjp(lambda a, b, c: _dense_attention(
        a, b, c, scale, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for g, w, name in zip(flash(ct), dense(ct), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,blocks", [
    (150, dict(q_block=64, kv_block=128)),
    (384, dict(q_block=128, kv_block=128)),
    (300, dict(q_block=128, kv_block=64)),
], ids=["T150_q64_k128", "T384", "T300_q128_k64"])
def test_forward_lse_is_the_reference_logsumexp(monkeypatch, T, blocks,
                                                causal):
    """The residual the backward reads: a row of lanes a tile of
    queries, equal to the logsumexp of the masked, scaled scores."""
    fa = _tiles_of(monkeypatch, 128)
    rng = np.random.RandomState(T)
    q, k, v = (jnp.asarray(rng.randn(2, 2, T, 16).astype("float32"))
               for _ in range(3))
    qb, kb = min(blocks["q_block"], T), min(blocks["kv_block"], T)
    _, lse = fa._flash_fwd(q, k, v, 0.25, causal, qb, kb, True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * 0.25
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), jnp.bool_)), s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1)
    Tq = -(-T // qb) * qb
    assert lse.shape == (4, Tq // fa._tile(qb, Tq), fa._tile(qb, Tq))
    np.testing.assert_allclose(
        np.asarray(lse).reshape(2, 2, Tq)[:, :, :T], np.asarray(want),
        rtol=1e-5, atol=1e-5)


def test_tiles_below_on_and_above_the_diagonal(monkeypatch):
    """Three tiles of 128 a side, causal: the middle tile of queries
    walks one tile below the diagonal, the one on it, and skips one
    (above).  The walk is held to the answer of the same call as ONE
    tile of 384, and the skipped tile is never read: its keys and values
    are NaN, which a masked visit would carry into the sum."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 384, 16).astype("float32"))
               for _ in range(3))
    blocks = dict(causal=True, q_block=128, kv_block=128)
    _tiles_of(monkeypatch, 512)
    one_tile = flash_attention(q, k, v, **blocks)
    _tiles_of(monkeypatch, 128)
    tiled = flash_attention(q, k, v, **blocks)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(one_tile),
                               rtol=1e-5, atol=1e-6)
    ref = _dense_attention(q, k, v, 0.25, True)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    nan = jnp.full((1, 2, 128, 16), jnp.nan, jnp.float32)
    k_bad = jnp.concatenate([k[:, :, :256], nan], axis=2)
    v_bad = jnp.concatenate([v[:, :, :256], nan], axis=2)
    poisoned = np.asarray(flash_attention(q, k_bad, v_bad, **blocks))
    np.testing.assert_array_equal(poisoned[:, :, :256],
                                  np.asarray(tiled)[:, :, :256])
    assert np.isnan(poisoned[:, :, 256:]).all()


@pytest.mark.parametrize("T,causal,blocks,tiles", [
    (640, True, {}, (128, 128)),             # five tiles: the diagonal whole
    (640, False, {}, (128, 128)),
    (1000, True, {}, (512, 512)),            # two: the diagonal in 4 steps
    (600, True, dict(q_block=64, kv_block=128), (320, 128)),
    (600, False, dict(q_block=128, kv_block=64), (128, 320)),
], ids=["causal_T640", "T640", "causal_T1000", "causal_q64_k128_T600",
        "q128_k64_T600"])
def test_the_module_s_own_tiles_in_several_trips(T, causal, blocks, tiles):
    """No constant held down: lengths at which TILE = 512 itself leaves
    several trips a tile of queries (five blocks a side stay five tiles;
    1,024 padded rows are two tiles of 512; tiles of 320 queries over
    tiles of 128 keys).  Output, lse and all three gradients against the
    dense reference; the calls test_kernel_check.py only traces at
    T = 150 and T = 640 run here."""
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    rng = np.random.RandomState(T)
    q, k, v, ct = (jnp.asarray(rng.randn(1, 2, T, 16).astype("float32"))
                   for _ in range(4))
    qb, kb = blocks.get("q_block", 128), blocks.get("kv_block", 128)
    Tq, Tk = -(-T // qb) * qb, -(-T // kb) * kb
    assert (fa._tile(qb, Tq), fa._tile(kb, Tk)) == tiles
    out, flash = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, **blocks), q, k, v)
    want, dense = jax.vjp(lambda a, b, c: _dense_attention(
        a, b, c, 0.25, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for g, w, name in zip(flash(ct), dense(ct), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    _, lse = fa._flash_fwd(q, k, v, 0.25, causal, qb, kb, True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * 0.25
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), jnp.bool_)), s, -jnp.inf)
    assert lse.shape == (2, Tq // tiles[0], tiles[0])
    np.testing.assert_allclose(
        np.asarray(lse).reshape(1, 2, Tq)[:, :, :T],
        np.asarray(jax.nn.logsumexp(s, axis=-1)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("diag", [128, 256], ids=["diag128", "diag256"])
def test_the_diagonal_tile_is_walked_in_key_steps(monkeypatch, diag):
    """Two square tiles of 256, causal: the second walks one tile
    unmasked and then its diagonal tile in steps of ``diag`` keys, each
    against the queries from its own first on (two steps of 128, or the
    whole tile at once): values and gradients of the dense reference."""
    fa = _tiles_of(monkeypatch, 256)
    monkeypatch.setattr(fa, "FWD_DIAG", diag)
    rng = np.random.RandomState(diag)
    q, k, v, ct = (jnp.asarray(rng.randn(1, 2, 500, 16).astype("float32"))
                   for _ in range(4))
    out, flash = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=True), q, k, v)
    want, dense = jax.vjp(lambda a, b, c: _dense_attention(
        a, b, c, 0.25, True), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for g, w, name in zip(flash(ct), dense(ct), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("tile", [128, 512], ids=["tile128", "tile512"])
@pytest.mark.parametrize("causal", [False, True])
def test_wide_forward_tiles_bf16(monkeypatch, qkv, tile, causal):
    """bf16 inputs (``hi_prec=False``: the matrix unit's default
    precision) within 2e-2 of the float32 dense reference."""
    _tiles_of(monkeypatch, tile)
    q, k, v = (jnp.concatenate([a, a[:, :, :64]], axis=2) for a in qkv)
    out = flash_attention(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                          causal=causal, q_block=64, kv_block=64)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q, k, v, 0.25, causal)
    np.testing.assert_allclose(np.asarray(out, dtype="float32"),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_pallas_backward_mixed_block_sizes(qkv):
    """q_block != kv_block pads Tq and Tk differently; the backward
    kernel must iterate the Q-side padded length, not the K-side."""
    q, k, v = (a[:, :, :150] for a in qkv)  # pads to Tq=192 vs Tk=256... 
    g_flash = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, causal=True, q_block=64, kv_block=128).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda a, b, c: _dense_attention(
        a, b, c, 1.0 / np.sqrt(16), True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("dp,tp", [(2, 2), (4, 1), (1, 2), (2, 3)])
def test_flash_shard_mapped_under_a_sharding_scope(qkv, dp, tp):
    """Inside a sharded program the kernel splits itself over the
    scope's batch and heads axes (GSPMD cannot partition a Mosaic
    kernel): same values and gradients as the unpartitioned call, and
    an axis whose shard count does not divide stays whole (B=2 over
    dp=4, H=2 over tp=3)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxtpu.ops.pallas.partition import head_sharding_scope
    from mxtpu.parallel import make_mesh

    mesh = make_mesh(dp=dp, tp=tp, devices=jax.devices()[:dp * tp])

    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, q_block=64,
                                kv_block=64) ** 2).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(*qkv)

    @jax.jit
    def scoped(q, k, v):
        with head_sharding_scope(mesh, "tp", "dp"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    B, H = qkv[0].shape[:2]
    spec = P("dp" if B % dp == 0 else None, "tp" if H % tp == 0 else None)
    got = scoped(*(jax.device_put(a, NamedSharding(mesh.jax_mesh, spec))
                   for a in qkv))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


# ---- values narrower than keys (latent attention: keys 192, values 128)

@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("T,D,Dv,causal,blocks", [
    (256, 192, 128, True, {}),                          # the new cell's heads
    (150, 192, 128, True, dict(q_block=64, kv_block=128)),
    (200, 48, 16, False, {}),
    (256, 64, 64, False, {}),                           # BERT's: one width
    (256, 256, 256, True, {}),          # rotary latent attention: 192 + 64
    (300, 256, 256, True, dict(q_block=128, kv_block=64)),
], ids=["causal_D192_Dv128_T256", "causal_D192_Dv128_T150_q64_k128",
        "D48_Dv16_T200", "D64_Dv64_T256", "causal_D256_Dv256_T256",
        "causal_D256_Dv256_T300_q128_k64"])
def test_flash_with_a_value_width_of_its_own(direction, T, D, Dv, causal,
                                             blocks):
    rng = np.random.RandomState(T + D)
    q, k = (jnp.asarray(rng.randn(1, 2, T, D).astype("float32"))
            for _ in range(2))
    v, ct = (jnp.asarray(rng.randn(1, 2, T, Dv).astype("float32"))
             for _ in range(2))
    scale = 1.0 / np.sqrt(D)
    if direction == "forward":
        out = flash_attention(q, k, v, causal=causal, **blocks)
        assert out.shape == (1, 2, T, Dv)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_dense_attention(q, k, v, scale, causal)),
            rtol=1e-4, atol=1e-5)
        return
    _, flash = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, **blocks), q, k, v)
    _, dense = jax.vjp(lambda a, b, c: _dense_attention(
        a, b, c, scale, causal), q, k, v)
    for g, w, name in zip(flash(ct), dense(ct), ("dq", "dk", "dv")):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_a_long_head_asks_for_its_vmem_and_a_short_one_for_nothing():
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    # BERT's call: the compiler's own limit, as before
    assert fa._compiler_params(fa._fwd_vmem(512, 512, 512, 64, 64,
                                            "float32")) is None
    assert fa._compiler_params(fa._bwd_vmem(512, 512, 64, 64,
                                            "float32")) is None
    # 8,192 keys of 192: K and V whole in VMEM are 12 MiB, twice buffered
    asked = fa._compiler_params(fa._fwd_vmem(512, 512, 8192, 192, 128,
                                             "float32"))
    assert 24 * 2 ** 20 < asked.vmem_limit_bytes < 128 * 2 ** 20
    back = fa._compiler_params(fa._bwd_vmem(8192, 512, 192, 128, "float32"))
    assert asked.vmem_limit_bytes < back.vmem_limit_bytes < 128 * 2 ** 20
    # keys and values of 256: 16 MiB of K and V a head, 8 MiB each of Q,
    # dO, dQ and its accumulator; still inside the chip's 128 MiB
    wide = fa._compiler_params(fa._fwd_vmem(512, 512, 8192, 256, 256,
                                            "float32"))
    wide_back = fa._compiler_params(fa._bwd_vmem(8192, 512, 256, 256,
                                                 "float32"))
    assert asked.vmem_limit_bytes < wide.vmem_limit_bytes \
        < wide_back.vmem_limit_bytes < 128 * 2 ** 20


# ---------------------------- under a unit of recomputation (ops/remat)

@pytest.mark.parametrize("causal", [False, True])
def test_a_unit_keeps_out_and_lse_and_runs_the_forward_once(qkv, causal):
    """A checkpoint with the units' policy keeps what ``fa_fwd`` marked:
    the forward kernel is not in the backward pass a second time, q, k
    and v are formed again, and no gradient moves by a bit."""
    import collections
    import re
    from mxtpu.ops import remat

    q, k, v = qkv
    B, H, T, D = q.shape
    w = jnp.array(np.random.RandomState(1).randn(D, 4).astype("float32"))

    def unit(q, k, v, w):
        q, k, v = (jnp.tanh(a) for a in (q, k, v))  # a projection's stead
        out = flash_attention(q, k, v, causal=causal, q_block=64,
                              kv_block=64)
        return jnp.tanh(out @ w).sum()

    def under(policy):
        grad = jax.grad(jax.checkpoint(unit, policy=policy),
                        argnums=(0, 1, 2, 3))
        remat.reset()
        text = str(jax.make_jaxpr(grad)(q, k, v, w))
        return (collections.Counter(re.findall(r"name=(flash_\w+)", text)),
                text.count("tanh"), remat.counts(), grad(q, k, v, w))

    kernels, tanhs, counts, grads = under(remat.policy)
    alone, tanhs_alone, nothing, want = under(None)
    assert kernels == {"flash_attention_fwd": 1, "flash_attention_bwd": 1}
    assert alone == {"flash_attention_fwd": 2, "flash_attention_bwd": 1}
    assert tanhs == tanhs_alone             # q, k, v are formed again
    assert counts == {"kept_outputs": 2,
                      "kept_bytes": B * H * T * D * 4 + B * H * T * 4}
    assert nothing == {"kept_outputs": 0, "kept_bytes": 0}
    for a, b in zip(grads, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_outside_a_checkpoint_the_marks_leave_the_program_alone(qkv):
    grad = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, q_block=64, kv_block=64).sum(),
        argnums=(0, 1, 2)))
    assert "mxtpu_kept" not in grad.lower(*qkv).compile().as_text()


def test_a_sharded_unit_keeps_its_shards_of_out_and_lse():
    """Under a sharded trainer the kernel sits in a ``shard_map``: the
    units' policy reaches the marks through it, and counts a shard."""
    import re
    from mxtpu import gluon
    from mxtpu.models import transformer
    from mxtpu.observability.metrics import get_registry
    from mxtpu.parallel import SPMDTrainer, make_mesh

    B, H, T, layers = 4, 2, 16, 2
    mx.random.seed(0)
    net = transformer.TransformerLM(64, units=32, hidden_size=64,
                                    num_layers=layers, num_heads=H)
    net.initialize(mx.init.Xavier())
    trainer = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(axis=-1),
                          "sgd", make_mesh(dp=2, devices=jax.devices()[:2]),
                          optimizer_params={"learning_rate": 0.1},
                          remat=True)
    tokens = mx.nd.array(np.random.RandomState(0).randint(0, 64, (B, T)),
                         dtype="int32")
    jitted, args = trainer.step_program(tokens, tokens)
    text = str(jax.make_jaxpr(jitted)(*args))
    assert "shard_map" in text
    assert len(re.findall(r"name=flash_attention_fwd", text)) == layers
    snap = get_registry().snapshot()
    shard = B // 2 * H * T * (32 // H + 1) * 4     # out and lse, float32
    assert (snap["remat.kept_outputs"], snap["remat.kept_bytes"]) == (
        2 * layers, layers * shard)
