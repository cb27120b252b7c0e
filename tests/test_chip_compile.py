"""The Pallas kernels of the two main paths compile for a TPU v5e.

The sandbox has no chip but it has the chip's compiler: a topology that
is described, not attached, is enough for ``jit(f).lower(shapes)
.compile()`` to raise exactly what the chip would.  Interpret mode on
the CPU cannot: before PR 22 every kernel here passed its parity suite
and four of them were refused by Mosaic.

Geometries are the real ones — BERT-base training (batch 32, 12 heads
of 64, seq 128 in bfloat16 and the benchmark cell's seq 512 in float32),
Kimi-Linear's cell (32 heads at 8,192 positions: latent attention with
keys of 192 and values of 128, KDA's state kernels at 128),
GLM-4.7-Flash's cell (20 heads at 8,192 positions, keys and values of
256, and its whole training step, for the compiler's memory bound),
LFM2's cell (32 heads of 64 at 8,192 positions, and its whole step) and
Llama-3-8B serving (32 Q / 8 KV heads of 128).
Nothing runs, so results are covered by the interpret-mode suites
(test_flash_attention / test_paged_attention_pallas /
test_prefill_attention_pallas / test_sharded_paged_kernel).

One process may load the TPU's library, so the topology is described
inside a fixture (never at import) and every compile happens in this
file, in the test's own process.
"""

import importlib

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from mxtpu.ops.pallas.partition import head_sharding_scope
from mxtpu.parallel.mesh import make_mesh

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
kda = importlib.import_module("mxtpu.ops.pallas.kda")
pa = importlib.import_module("mxtpu.ops.pallas.paged_attention")
pf = importlib.import_module("mxtpu.ops.pallas.prefill_attention")

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8

# Llama-3-8B attention geometry and a serving-sized page pool
KV, REP, D = 8, 4, 128
SLOTS, PAGES, TABLE = 8, 129, 64
#: smallest legal block_size per cache dtype (K002 sublane tile)
BLOCK = {BF16: 16, F32: 8, I8: 32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_chip(topo, monkeypatch):
    """The kernels' public wrappers pick interpret mode from
    ``jax.default_backend()``, which is the CPU here: answer for the
    chip the shapes are placed on, so the wrapper's own non-interpret
    path (geometry guard included) is what gets compiled."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return SingleDeviceSharding(topo.devices[0])


def _compiles_with_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("geometry,dtype,causal", [
    ((32, 12, 128, 64), BF16, False),   # BERT-base, batch 32 x seq 128
    ((1, 32, 2048, 128), BF16, True),   # Llama-3-8B full forward, T=2048
    # the benchmark's cell: BERT-base pre-training, 32 x 512, float32
    ((32, 12, 512, 64), F32, False),
], ids=["bert_base", "llama_3_8b", "bert_base_seq512_f32"])
def test_flash_attention(on_chip, geometry, dtype, causal, backward):
    q = _shape(geometry, dtype, on_chip)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = jax.jit(fn).lower(q, q, q).compile().as_text()
    assert "flash_attention_fwd" in text
    # the forward walks tiles (512 rows where the length allows)
    # and hands lse over along lanes, a row a tile
    B, H, T, _ = geometry
    tile = fa._tile(min(128, T), T)
    assert tile == min(T, 512)
    assert "f32[%d,%d,%d]" % (B * H, T // tile, tile) in text
    # one backward kernel, and neither of the two it replaced
    assert ("flash_attention_bwd" in text) == backward
    assert "flash_attention_dq" not in text
    assert "flash_attention_dkv" not in text


def test_flash_attention_with_narrower_values_at_8k(on_chip):
    """Latent attention of the Kimi-Linear cell: one row of 32 heads,
    8,192 positions, keys of 192 and values of 128, float32, causal.
    The heads' K and V (forward) and Q, dO and dQ (backward) stay whole
    in VMEM, which the calls ask the compiler for."""
    q = _shape((1, 32, 8192, 192), F32, on_chip)
    v = _shape((1, 32, 8192, 128), F32, on_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile().as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "f32[32,16,512]" in text         # lse: 16 tiles of 512 a head


def test_flash_attention_with_keys_and_values_of_256_at_8k(on_chip):
    """Rotary latent attention of the GLM-4.7-Flash cell: one row of 20
    heads, 8,192 positions, keys of 192 + 64 and values of 256, float32,
    causal: whole heads in VMEM still (the forward, at its tiles of 512,
    asks for 64 MiB, the backward for 94 of the chip's 128)."""
    q = _shape((1, 20, 8192, 256), F32, on_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "f32[20,16,512]" in text         # lse: 16 tiles of 512 a head
    assert fa._vmem_limit(fa._fwd_vmem(512, 512, 8192, 256, 256, "float32")) \
        < fa._vmem_limit(fa._bwd_vmem(8192, 512, 256, 256, "float32")) \
        < 128 * 2 ** 20


def test_the_whole_glm_step_fits_the_chip(on_chip, record_property):
    """``SPMDTrainer``'s step of the GLM-4.7-Flash cell at its own
    sizes (706.5 M trained parameters, one sequence of 8,192, Adam,
    recomputation per unit, the two cross-entropies through the head in
    blocks of rows) compiles for the described chip inside its memory:
    8.48 GB of weights and Adam state beside the compiler's bound on
    everything else.  With both sets of logits whole the bound is
    16.3 GB of the 16.9 the chip gives (PERF.md, PR 33)."""
    import mxtpu as mx
    from chipbench import harness, models
    from mxtpu.models.glm4_moe_lite import glm4_moe_lite_from_config
    from mxtpu.parallel import SPMDTrainer

    cfg = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
    net = glm4_moe_lite_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["n_routed_experts"]),
        num_experts_total=cfg["num_experts_total"], return_logits=False)
    net.initialize(mx.init.Zero())
    trainer = SPMDTrainer(
        net, net.loss(cfg["mtp_weight"]), cfg["train"]["optimizer"],
        models.one_chip_mesh(jax.devices()[:1]),
        optimizer_params={"learning_rate": cfg["train"]["learning_rate"]},
        remat=cfg["train"]["remat"])
    trainer._stage_params()             # no eager forward: shapes are known
    step = trainer._make_step_fns()[0]
    like = lambda a: _shape(a.shape, a.dtype, on_chip)
    scalar, tokens = _shape((), F32, on_chip), _shape((1, 8192), jnp.int32,
                                                      on_chip)
    args = [tuple(like(p.data()._data) for p in trainer._diff_params),
            tuple(like(p.data()._data) for p in trainer._aux_params),
            jax.tree_util.tree_map(like, tuple(trainer._opt_states)),
            scalar, scalar, tokens, tokens,
            _shape((2,), jnp.uint32, on_chip)]
    assert sum(a.size for a in args[0]) == cfg["trained_parameters"]
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("flash_attention_fwd") >= 6
    assert text.count("flash_attention_bwd") >= 6
    m = compiled.memory_analysis()
    bound = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    record_property("glm_step_code_bytes", m.generated_code_size_in_bytes)
    record_property("glm_step_memory_bound_bytes", bound)
    print("GLM step: code %.3f GB, arguments %.3f GB, temporaries %.3f GB, "
          "bound %.3f GB" % (m.generated_code_size_in_bytes / 1e9,
                             m.argument_size_in_bytes / 1e9,
                             m.temp_size_in_bytes / 1e9, bound / 1e9))
    # weights and Adam's two moments, 12 B a parameter, and little else
    assert 0 <= m.argument_size_in_bytes \
        - 12 * cfg["trained_parameters"] < 2 ** 20
    assert bound < 15.9e9               # the chip gives 16.909 GB


def test_indexed_attention_at_the_keye_cells_geometry(on_chip):
    """Keye-VL-2.0's attention layer in its cell: one row of 32 query
    and 4 key heads of 128 at 8,192 positions whose keys an indexer of
    16 heads of 64 selects (the 2,048 best of each row), forward and
    backward, float32.  The flash kernels take a Q tile's column
    (8,192 x 512) or a K/V block's row (512 x 8,192) of the selection's
    scores beside the head; the indexer's three kernels walk tiles of
    512, a tile's heads a grid step, the backward's key gradient
    (64 x 8,192) resident for the call; the thresholds' kernel holds a
    query tile's whole column of keys."""
    dsa = importlib.import_module("mxtpu.ops.dsa")
    T = 8192
    shapes = [_shape(s, F32, on_chip) for s in (
        (1, 32, T, 128), (1, 4, T, 128), (1, 4, T, 128), (1, 16, T, 64),
        (1, T, 64), (1, 16, T))]

    def loss(*a):
        o, kl, _ = dsa.indexed_attention(*a, top_k=2048)
        return o.sum() + kl.sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *shapes).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd",
                 "indexer_scores_fwd", "indexer_scores_bwd_q_k",
                 "indexer_probs", "indexer_threshold"):
        assert name in text, name
    # the thresholds come from the kernel (a tile's column of 8,192 x 512
    # scores twice buffered and its turned copy: 76 MiB asked for): no
    # loop of XLA's carries the scores' unsigned bits
    assert "u32[1,8192,8192]" not in text
    # the (T, T) arrays are whole — scores, mean probabilities, what the
    # loss forms of them — and nothing with a heads axis is
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert fa._vmem_limit(fa._bwd_vmem(T, 512, 128, 128, "float32")
                          + 2 * fa._padded(512, T, "float32")) \
        < 128 * 2 ** 20


def test_the_whole_keye_step_fits_the_chip(on_chip, record_property):
    """``SPMDTrainer``'s step of the Keye-VL cell at its own sizes
    (659.2 M trained parameters, six layers, one sequence of 8,192,
    Adam, recomputation per unit, the cross-entropy through the head in
    blocks of rows, the indexers' loss) compiles for the described chip
    inside its memory."""
    import mxtpu as mx
    from chipbench import harness, models
    from mxtpu.models.keye_vl import keye_vl_from_config
    from mxtpu.ops import remat
    from mxtpu.parallel import SPMDTrainer

    cfg = harness.load_json(harness.HERE, "configs",
                            "keye-vl-2.0-30b-a3b.json")
    net = keye_vl_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_total=cfg["num_experts_total"], return_logits=False)
    net.initialize(mx.init.Zero())
    trainer = SPMDTrainer(
        net, net.loss(cfg["index_loss_weight"]), cfg["train"]["optimizer"],
        models.one_chip_mesh(jax.devices()[:1]),
        optimizer_params={"learning_rate": cfg["train"]["learning_rate"]},
        remat=cfg["train"]["remat"])
    trainer._stage_params()             # no eager forward: shapes are known
    step = trainer._make_step_fns()[0]
    like = lambda a: _shape(a.shape, a.dtype, on_chip)
    scalar, tokens = _shape((), F32, on_chip), _shape((1, 8192), jnp.int32,
                                                      on_chip)
    args = [tuple(like(p.data()._data) for p in trainer._diff_params),
            tuple(like(p.data()._data) for p in trainer._aux_params),
            jax.tree_util.tree_map(like, tuple(trainer._opt_states)),
            scalar, scalar, tokens, tokens,
            _shape((2,), jnp.uint32, on_chip)]
    assert sum(a.size for a in args[0]) == cfg["trained_parameters"]
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd",
                 "indexer_scores_fwd", "indexer_scores_bwd_q_k",
                 "indexer_probs", "indexer_threshold"):
        assert text.count(name) >= 6, name
    assert "u32[1,8192,8192]" not in text
    # a unit keeps its attention's output, logsumexp and thresholds, and
    # the indexer's queries, key and weights, so that the scores it forms
    # again are the first's to the bit; the (T, T) scores and mean
    # probabilities are formed again (kept, the mean probabilities cost
    # 3.06 GB of the bound and, on the chip, four times the gap of the
    # indexer's gradient: PERF.md, PR 35)
    kept = remat.counts()
    assert kept["kept_outputs"] == 36
    assert kept["kept_bytes"] == 6 * 4 * (
        32 * 8192 * 128 + 32 * 8192 + 8192
        + 16 * 8192 * 64 + 8192 * 64 + 16 * 8192)
    m = compiled.memory_analysis()
    bound = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    record_property("keye_step_code_bytes", m.generated_code_size_in_bytes)
    record_property("keye_step_memory_bound_bytes", bound)
    print("Keye step: code %.3f GB, arguments %.3f GB, temporaries %.3f GB, "
          "bound %.3f GB" % (m.generated_code_size_in_bytes / 1e9,
                             m.argument_size_in_bytes / 1e9,
                             m.temp_size_in_bytes / 1e9, bound / 1e9))
    assert 0 <= m.argument_size_in_bytes \
        - 12 * cfg["trained_parameters"] < 2 ** 20
    assert bound < 13e9                 # the chip gives 16.909 GB


def test_flash_attention_at_narrow_heads_at_8k(on_chip):
    """Grouped-query attention of the LFM2 cell as the kernel sees it:
    one row of 32 query heads (the 8 key heads repeated to them), 8,192
    positions, heads of 64, float32, causal — BERT's head width at the
    8k cells' length, a geometry no other cell has."""
    q = _shape((1, 32, 8192, 64), F32, on_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "f32[32,16,512]" in text         # lse: 16 tiles of 512 a head
    assert fa._vmem_limit(fa._bwd_vmem(8192, 512, 64, 64, "float32")) \
        < 128 * 2 ** 20


def test_the_whole_lfm2_step_fits_the_chip(on_chip, record_property):
    """``SPMDTrainer``'s step of the LFM2 cell at its own sizes (507.8 M
    trained parameters, four convolution layers and one of attention,
    four expert layers, one sequence of 8,192, Adam, recomputation per
    unit, the cross-entropy through the shared embedding in blocks of
    rows) compiles for the described chip inside its memory."""
    import mxtpu as mx
    from chipbench import harness, models
    from mxtpu.models.lfm2_moe import lfm2_moe_from_config
    from mxtpu.ops import remat
    from mxtpu.parallel import SPMDTrainer

    cfg = harness.load_json(harness.HERE, "configs", "lfm2-8b-a1b.json")
    net = lfm2_moe_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_total=cfg["num_experts_total"], return_logits=False)
    net.initialize(mx.init.Zero())
    trainer = SPMDTrainer(
        net, net.loss(), cfg["train"]["optimizer"],
        models.one_chip_mesh(jax.devices()[:1]),
        optimizer_params={"learning_rate": cfg["train"]["learning_rate"]},
        remat=cfg["train"]["remat"])
    trainer._stage_params()             # no eager forward: shapes are known
    step = trainer._make_step_fns()[0]
    like = lambda a: _shape(a.shape, a.dtype, on_chip)
    scalar, tokens = _shape((), F32, on_chip), _shape((1, 8192), jnp.int32,
                                                      on_chip)
    args = [tuple(like(p.data()._data) for p in trainer._diff_params),
            tuple(like(p.data()._data) for p in trainer._aux_params),
            jax.tree_util.tree_map(like, tuple(trainer._opt_states)),
            scalar, scalar, tokens, tokens,
            _shape((2,), jnp.uint32, on_chip)]
    assert sum(a.size for a in args[0]) == cfg["trained_parameters"]
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "ragged-dot" in text
    # by ``_tile_rows`` an even load of 8,192 held pairs takes two tiles
    # of 5,120 rows a layer
    assert "f32[5120,1792]" in text and "f32[5120,2048]" in text
    # the attention unit keeps flash's output and logsumexp, the other
    # nine units their input alone
    kept = remat.counts()
    assert kept["kept_outputs"] == 2
    assert kept["kept_bytes"] == 4 * (32 * 8192 * 64 + 32 * 8192)
    m = compiled.memory_analysis()
    bound = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    record_property("lfm2_step_code_bytes", m.generated_code_size_in_bytes)
    record_property("lfm2_step_memory_bound_bytes", bound)
    print("LFM2 step: code %.3f GB, arguments %.3f GB, temporaries %.3f GB, "
          "bound %.3f GB" % (m.generated_code_size_in_bytes / 1e9,
                             m.argument_size_in_bytes / 1e9,
                             m.temp_size_in_bytes / 1e9, bound / 1e9))
    assert 0 <= m.argument_size_in_bytes \
        - 12 * cfg["trained_parameters"] < 2 ** 20
    assert bound < 13e9                 # the chip gives 16.909 GB


@pytest.mark.parametrize("T", [8192, 96], ids=["cell", "toy"])
def test_kda_kernels(on_chip, T, heads=4, K=128):
    """KDA's kernels, forward and backward, at one call of the cell (4
    heads at a time x 128 chunks of 64 x 128): the chunks' operands (the
    forward, the forward that also writes the inverses, the hand-written
    backward that reads them) and the pass that carries the state."""
    rows = _shape((1, T, heads, K), F32, on_chip)
    beta = _shape((1, T, heads), F32, on_chip)
    text = jax.jit(jax.grad(
        lambda *a: kda._recurrence(*a, chunk=kda.CHUNK).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(
        rows, rows, rows, rows, beta).compile().as_text()
    for name in (kda.CHUNK_FWD_NAME, kda.CHUNK_FWD_INVERSE_NAME,
                 kda.CHUNK_BWD_NAME, kda.FWD_NAME, kda.FWD_STATES_NAME,
                 kda.BWD_NAME):
        assert name in text
    for cached in (kda._make_state_pass, kda._make_chunk_operands):
        cached.cache_clear()


def _decode_shapes(cache_dtype, W, tree, place):
    bs = BLOCK[cache_dtype]
    heads4, heads3, repl = place
    shapes = dict(
        q=_shape((SLOTS, KV * REP, W, D), BF16, heads4),
        pool_k=_shape((PAGES, KV, bs, D), cache_dtype, heads4),
        pool_v=_shape((PAGES, KV, bs, D), cache_dtype, heads4),
        tables=_shape((SLOTS, TABLE), jnp.int32, repl),
        pos=_shape((SLOTS,), jnp.int32, repl))
    if cache_dtype == I8:
        shapes["k_scales"] = _shape((PAGES, KV, bs), F32, heads3)
        shapes["v_scales"] = _shape((PAGES, KV, bs), F32, heads3)
    if tree:
        shapes["anc"] = _shape((SLOTS, W), jnp.int32, repl)
    return shapes


@pytest.mark.parametrize("W,tree", [(1, False), (4, False), (8, True)],
                         ids=["decode_W1", "verify_W4", "tree_W8"])
@pytest.mark.parametrize("cache_dtype", [BF16, F32, I8],
                         ids=["bf16", "f32", "int8"])
def test_paged_decode(on_chip, cache_dtype, W, tree):
    shapes = _decode_shapes(cache_dtype, W, tree, (on_chip,) * 3)
    _compiles_with_kernel(
        lambda kw: pa.paged_decode_attention(**kw), shapes)


@pytest.mark.parametrize("cache_dtype", [BF16, I8], ids=["bf16", "int8"])
def test_paged_prefill(on_chip, cache_dtype, T=512):
    bs = BLOCK[cache_dtype]
    shapes = dict(
        q=_shape((1, KV * REP, T, D), BF16, on_chip),
        pool_k=_shape((PAGES, KV, bs, D), cache_dtype, on_chip),
        pool_v=_shape((PAGES, KV, bs, D), cache_dtype, on_chip),
        table=_shape((TABLE,), jnp.int32, on_chip),
        start_pos=_shape((), jnp.int32, on_chip))
    if cache_dtype == I8:
        shapes["k_scales"] = _shape((PAGES, KV, bs), F32, on_chip)
        shapes["v_scales"] = _shape((PAGES, KV, bs), F32, on_chip)
    _compiles_with_kernel(
        lambda kw: pf.paged_prefill_attention(**kw), shapes)


@pytest.mark.parametrize("cache_dtype", [BF16, I8], ids=["bf16", "int8"])
def test_paged_decode_partitioned_over_four_chips(topo, on_chip,
                                                  cache_dtype):
    """tp=4: under the decoder's head_sharding_scope the kernel is
    shard_mapped over the cache's heads axis — two KV heads a chip."""
    mesh = make_mesh(tp=4, devices=list(topo.devices))
    place = tuple(NamedSharding(mesh.jax_mesh, spec) for spec in
                  (P(None, "tp", None, None), P(None, "tp", None), P()))
    shapes = _decode_shapes(cache_dtype, 1, False, place)

    def fn(kw):
        with head_sharding_scope(mesh, "tp"):
            return pa.paged_decode_attention(**kw)

    before = pa.invocation_count()
    _compiles_with_kernel(fn, shapes)
    assert pa.invocation_count() == before + 1


def test_flash_attention_partitioned_over_four_chips(topo, on_chip):
    """A sharded training step (dp=2 x tp=2, BERT-base): GSPMD cannot
    partition a Mosaic kernel, so under the trainer's scope the kernel
    shard_maps itself over batch and heads — eight rows and six heads a
    chip, forward and backward."""
    mesh = make_mesh(dp=2, tp=2, devices=list(topo.devices))
    q = _shape((32, 12, 128, 64), BF16,
               NamedSharding(mesh.jax_mesh, P("dp", "tp", None, None)))

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(F32).sum()

    def scoped(q, k, v):
        with head_sharding_scope(mesh, "tp", "dp"):
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _compiles_with_kernel(scoped, q, q, q)
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(jax.grad(loss)).lower(q, q, q).compile()
