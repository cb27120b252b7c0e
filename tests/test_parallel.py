"""Tests for mxtpu.parallel (SPMD trainer, ring attention, collectives,
dist kvstore) on the virtual 8-device CPU mesh (SURVEY §4 fixture 5)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon, models
from mxtpu.gluon import nn
from mxtpu.parallel import (make_mesh, DeviceMesh, SPMDTrainer,
                            ShardingRules, PartitionSpec as P,
                            ring_attention, collectives)


def test_mesh_construction():
    mesh = make_mesh(dp=2, tp=2, sp=2)
    assert mesh.size("dp") == 2 and mesh.size("tp") == 2
    assert mesh.num_devices == 8
    assert repr(mesh)
    with pytest.raises(ValueError):
        DeviceMesh(dp=16)
    # default: all devices to dp
    assert make_mesh().size("dp") == len(jax.devices())


def test_sharding_rules():
    mesh = make_mesh(tp=2, dp=4)
    rules = ShardingRules([(r"weight$", P("tp", None))])
    assert rules.spec_for("dense0_weight", 2) == P("tp", None)
    assert rules.spec_for("dense0_bias", 1) == P()
    sh = rules.sharding_for("dense0_weight", 2, mesh)
    x = jax.device_put(jnp.zeros((8, 4)), sh)
    assert len(x.devices()) >= 2


def test_ring_attention_matches_dense():
    mesh = make_mesh(dp=2, sp=4)
    B, H, T, D = 2, 3, 16, 8
    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(B, H, T, D).astype("float32"))
    k = jnp.array(rng.randn(B, H, T, D).astype("float32"))
    v = jnp.array(rng.randn(B, H, T, D).astype("float32"))

    def dense(q, k, v, causal):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if causal:
            s = s + np.triu(np.full((T, T), -np.inf), 1)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    for causal in (False, True):
        out = ring_attention.ring_self_attention(q, k, v, mesh,
                                                 causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense(q, k, v, causal)),
                                   rtol=1e-4, atol=1e-5)


def test_spmd_trainer_dp_matches_single_device():
    """Grad sync correctness: dp=8 training must track dp=1 numerically."""
    np.random.seed(0)
    X = np.random.randn(16, 8).astype("float32")
    y = (np.random.rand(16) * 3).astype("int32")

    def run(mesh):
        np.random.seed(42)
        mx.random.seed(42)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
        net.initialize(force_reinit=True)
        tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         mesh, None, {"learning_rate": 0.1})
        return [float(tr.step(mx.nd.array(X), mx.nd.array(y)).asnumpy())
                for _ in range(5)]

    l8 = run(make_mesh(dp=8))
    l1 = run(make_mesh(dp=1))
    np.testing.assert_allclose(l8, l1, rtol=1e-4, atol=1e-5)


def test_spmd_trainer_tp_convergence():
    np.random.seed(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
    net.initialize()
    rules = ShardingRules([(r"dense0_weight", P("tp", None)),
                           (r"dense0_bias", P("tp")),
                           (r"dense1_weight", P(None, "tp"))])
    mesh = make_mesh(dp=2, tp=4)
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     mesh, rules, {"learning_rate": 0.01})
    X = np.random.randn(16, 8).astype("float32")
    y = (np.random.rand(16) * 4).astype("int32")
    losses = [float(tr.step(mx.nd.array(X), mx.nd.array(y)).asnumpy())
              for _ in range(40)]
    assert losses[-1] < 0.2 * losses[0]


def test_spmd_trainer_lower_step_consumes_nothing():
    """lower_step hands out the program the next step() would run —
    without running it, donating a buffer, drawing from the RNG ring or
    advancing the step count: the steps after it are the steps that
    would have happened anyway."""
    def run(look):
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dropout(0.5),
                nn.Dense(4))
        net.initialize()
        tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         "adam", make_mesh(dp=2),
                         optimizer_params={"learning_rate": 0.01})
        rng = np.random.RandomState(0)
        X = mx.nd.array(rng.randn(8, 8).astype("float32"))
        y = mx.nd.array((rng.rand(8) * 4).astype("int32"))
        out = [float(tr.step(X, y).asnumpy())]
        if look:
            text = tr.lower_step(X, y).as_text()
            assert "stablehlo" in text and "sharding" in text
        return out + [float(tr.step(X, y).asnumpy()) for _ in range(3)]

    assert run(look=True) == run(look=False)


def test_spmd_step_compiles_once_with_bf16_sgd_momentum():
    """The f32 learning rate promotes a bf16 momentum to f32 inside the
    update rule.  The state must START in that dtype: found on the
    chip, where ResNet-50's step program compiled twice (step 2 saw new
    state dtypes) — invisible to the compile ledger, whose key is the
    batch signature."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.cast("bfloat16")
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     make_mesh(dp=2), optimizer_params={
                         "learning_rate": 0.1, "momentum": 0.9})
    rng = np.random.RandomState(0)
    X = mx.nd.array(rng.randn(8, 8), dtype="bfloat16")
    y = mx.nd.array((rng.rand(8) * 4).astype("int32"))
    dtypes = []
    for _ in range(3):
        tr.step(X, y).asnumpy()
        dtypes.append([str(s.dtype) for s in
                       jax.tree_util.tree_leaves(tr._opt_states)])
    assert dtypes[0] == dtypes[1] == dtypes[2]
    (program,) = tr._jit_cache.values()
    assert program._cache_size() == 1


@pytest.mark.parametrize("rules,batch_spec,heads,batch", [
    ([(r"weight$", P("tp", None))], P("dp"), ("tp",), ("dp",)),
    ([(r"weight$", P(("tp", "ep"), None)), (r"bias$", P("dp"))],
     P(("dp", "sp")), ("tp", "ep"), ("dp", "sp")),
    ([], P("dp"), (), ("dp",)),
], ids=["megatron_tp", "two_model_axes", "data_parallel_only"])
def test_spmd_trainer_scopes_kernels_by_its_rules(rules, batch_spec,
                                                  heads, batch):
    """The scope the Pallas attention kernels partition themselves by
    (ops/pallas/partition.py) comes from the trainer's own layout: heads
    over the axes its rules shard parameters over, rows over the axes of
    its batch_spec — a batch axis is never a heads axis."""
    from mxtpu.ops.pallas.partition import _axes

    rules = ShardingRules(rules)
    assert set(heads) <= set(rules.axes())
    seen = []

    class Probe(nn.Dense):
        def hybrid_forward(self, F, x, **params):
            from mxtpu.ops.pallas.partition import _SCOPE
            seen.extend(_SCOPE[-1:])    # the staging forward has none
            return super().hybrid_forward(F, x, **params)

    mesh = make_mesh(dp=2, tp=2, sp=2)
    net = Probe(4, in_units=8)
    net.initialize()
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd", mesh, rules=rules,
                     batch_spec=batch_spec, label_spec=batch_spec)
    tr.step(mx.nd.ones((8, 8)), mx.nd.ones((8, 4))).asnumpy()
    scope = seen[-1]
    assert (scope.axes, scope.shards) == _axes(mesh, heads)
    assert (scope.batch_axes, scope.batch_shards) == _axes(mesh, batch)


def test_spmd_transformer_lm_full_parallel():
    """The flagship path: dp x tp x sp with ring attention, loss drops."""
    np.random.seed(0)
    mesh = make_mesh(dp=2, tp=2, sp=2)
    lm = models.llama_tiny(mesh=mesh)
    lm.initialize()

    class LMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            return self._ce(
                logits[:, :-1].reshape((-1, logits.shape[-1])),
                labels[:, 1:].reshape((-1,)))

    tr = SPMDTrainer(lm, LMLoss(), "adam", mesh,
                     models.transformer_lm_sharding_rules(),
                     {"learning_rate": 3e-3},
                     batch_spec=P("dp", "sp"), label_spec=P("dp", "sp"))
    X = mx.nd.array(np.random.randint(0, 256, (8, 16)), dtype="int32")
    losses = [float(tr.step(X, X).asnumpy()) for _ in range(25)]
    assert losses[-1] < 0.6 * losses[0]


def test_collectives_eager():
    a = [jnp.ones((4,)) * i for i in range(3)]
    out = collectives.all_reduce_arrays([a])
    np.testing.assert_allclose(np.asarray(out[0]), np.full(4, 3.0))
    assert collectives.all_reduce_across_processes(jnp.ones(3)).shape == (3,)


def test_dist_kvstore_single_process():
    kv = mx.kv.create("dist_tpu_sync")
    assert kv.rank == 0 and kv.num_workers == 1
    kv.init("w", mx.nd.ones((4,)))
    grads = [mx.nd.ones((4,)) * 2, mx.nd.ones((4,)) * 3]
    kv.push("w", grads)
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(4, 5.0))


@pytest.mark.slow
def test_bert_forward_and_sharded_training():
    np.random.seed(0)
    mesh = make_mesh(dp=4, tp=2)
    bert = models.BERTModel(vocab_size=64, units=32, hidden_size=64,
                            num_layers=2, num_heads=4, max_length=32)
    bert.initialize()
    tok = mx.nd.array(np.random.randint(0, 64, (4, 12)), dtype="int32")
    seq, pooled, mlm = bert(tok)
    assert seq.shape == (4, 12, 32)
    assert pooled.shape == (4, 32)
    assert mlm.shape == (4, 12, 64)

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, out, labels):
            mlm = out[2] if isinstance(out, tuple) else out
            return self._ce(mlm.reshape((-1, mlm.shape[-1])),
                            labels.reshape((-1,)))

    tr = SPMDTrainer(bert, MLMLoss(), "adam", mesh,
                     models.bert_sharding_rules(), {"learning_rate": 1e-3})
    losses = [float(tr.step(tok, tok).asnumpy()) for _ in range(15)]
    assert losses[-1] < losses[0]


def test_graft_entry_dryrun():
    import __graft_entry__
    __graft_entry__.dryrun_multichip(8)


def test_spmd_adam_matches_imperative_trainer():
    """_step_t bias correction on device must track the imperative Adam
    path (host-side coef folding in Adam.update) step for step."""
    np.random.seed(3)
    X = np.random.randn(16, 6).astype("float32")
    y = (np.random.rand(16) * 3).astype("int32")

    def build():
        np.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(12, activation="relu"), nn.Dense(3))
        net.initialize(force_reinit=True)
        return net

    # imperative: gluon.Trainer + autograd
    net_a = build()
    tr_a = gluon.Trainer(net_a.collect_params(), "adam",
                         {"learning_rate": 0.01})
    from mxtpu import autograd
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(4):
        with autograd.record():
            loss = loss_fn(net_a(mx.nd.array(X)), mx.nd.array(y))
        loss.backward()
        tr_a.step(16)

    # SPMD: one compiled step, t traced on device
    net_b = build()
    tr_b = SPMDTrainer(net_b, loss_fn, "adam", make_mesh(dp=1), None,
                       {"learning_rate": 0.01})
    for _ in range(4):
        tr_b.step(mx.nd.array(X), mx.nd.array(y))

    pa = {p.name: p.data().asnumpy() for p in
          net_a.collect_params().values()}
    pb = {p.name: p.data().asnumpy() for p in
          net_b.collect_params().values()}
    # names differ by block prefix counters; compare by sorted order
    for (na, va), (nb, vb) in zip(sorted(pa.items()), sorted(pb.items())):
        np.testing.assert_allclose(va, vb, rtol=2e-4, atol=2e-5)


def test_spmd_trainer_accepts_lamb():
    """LAMB exposes the pure interface via _step_t (t traced); previously
    the guard rejected it because it lacks a plain _step."""
    np.random.seed(5)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "lamb",
                     make_mesh(dp=2), None, {"learning_rate": 0.02})
    X = np.random.randn(8, 5).astype("float32")
    y = (np.random.rand(8) * 3).astype("int32")
    losses = [float(tr.step(mx.nd.array(X), mx.nd.array(y)).asnumpy())
              for _ in range(25)]
    assert losses[-1] < losses[0]


def test_spmd_trainer_global_norm_clip():
    """clip_gradient_norm fused into the compiled step == manual global
    clip + plain SGD, verified against hand-computed gradients."""
    import jax

    import mxtpu as mx
    from mxtpu import gluon, nd
    from mxtpu.parallel import make_mesh, SPMDTrainer, PartitionSpec as P

    rng = np.random.RandomState(61)
    X = nd.array(rng.randn(8, 4).astype("f"))
    y = nd.array(rng.randn(8, 1).astype("f"))

    def build():
        mx.random.seed(77)
        net = gluon.nn.Dense(1, in_units=4, use_bias=True)
        net.initialize()
        return net

    clip, lr = 0.05, 0.5

    def by_suffix(params):
        # block name counters differ between the two nets
        # (dense0_/dense1_): key on the stable parameter suffix
        return {n.rsplit("_", 1)[-1]: p for n, p in params.items()}

    net = build()
    w0 = {n: p.data().asnumpy() for n, p in
          by_suffix(net.collect_params()).items()}
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd", make_mesh(dp=1),
                     optimizer_params={"learning_rate": lr},
                     batch_spec=P(), label_spec=P(),
                     clip_gradient_norm=clip)
    tr.step(X, y).asnumpy()
    got = {n: p.data().asnumpy() for n, p in
           by_suffix(net.collect_params()).items()}

    # manual: grads of mean(L2Loss) wrt params, global-norm clipped
    ref = build()
    from mxtpu import autograd
    params = by_suffix(ref.collect_params())
    with autograd.record():
        L = gluon.loss.L2Loss()(ref(X), y).mean()
    L.backward()
    grads = {n: p.grad().asnumpy() for n, p in params.items()}
    gnorm = np.sqrt(sum((g ** 2).sum() for g in grads.values()))
    assert gnorm > clip  # the clip is actually active in this setup
    scale = min(1.0, clip / (gnorm + 1e-6))
    for n, p in params.items():
        expect = w0[n] - lr * grads[n] * scale
        np.testing.assert_allclose(got[n], expect, rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("reader", ["grad", "list_grad", "gluon_trainer",
                                    "zero_grad", "cast"])
def test_eager_gradients_survive_the_spmd_trainers_release(reader):
    """``SPMDTrainer`` gives up the parameters' eager gradient buffers
    when it stages (``Parameter.release_grad``).  An eager backward pass
    on the same net afterwards writes its gradients on the data: whoever
    asks next (``grad``, ``list_grad``, ``gluon.Trainer``'s dense list,
    ``zero_grad``) gets those, not fresh zeros."""
    from mxtpu import autograd
    np.random.seed(5)
    X = mx.nd.array(np.random.randn(16, 6).astype("float32"))
    y = mx.nd.array((np.random.rand(16) * 3).astype("int32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def build():
        np.random.seed(11)
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(12, activation="relu", in_units=6),
                nn.Dense(3, in_units=12))
        net.initialize(force_reinit=True)
        return net

    def backward(net):
        with autograd.record():
            loss = loss_fn(net(X), y)
        loss.backward()

    plain = build()
    backward(plain)
    want = [p.grad().asnumpy() for p in plain.collect_params().values()]
    assert all(np.abs(g).max() > 0 for g in want)

    net = build()
    trainer = SPMDTrainer(net, loss_fn, "sgd", make_mesh(dp=1), None,
                          {"learning_rate": 0.0})
    trainer.step(X, y)                  # stages, releases; moves nothing
    params = list(net.collect_params().values())
    assert all(p._grad is None for p in params)
    if reader == "cast":                # released AND re-made data
        for p in params:
            p.cast("float32")
    backward(net)
    if reader in ("grad", "cast"):
        got = [p.grad().asnumpy() for p in params]
    elif reader == "list_grad":
        got = [p.list_grad()[0].asnumpy() for p in params]
    elif reader == "gluon_trainer":
        before = [p.data().asnumpy() for p in params]
        gluon.Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 1.0}).step(1)
        got = [b - p.data().asnumpy() for b, p in zip(before, params)]
    else:
        net.collect_params().zero_grad()
        got = [p.grad().asnumpy() for p in params]
        want = [np.zeros_like(g) for g in want]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def _toy_step_text(model):
    """The StableHLO of ``SPMDTrainer``'s step over the toy configuration
    of one of the benchmark's accepted models, built as its runner
    builds it."""
    import importlib
    from chipbench import harness, models as bench_models, models_glm, \
        models_lm

    config, reference, seq = {
        "bert": ("bert-tiny.json", "bert", 128),
        "kimi_linear": ("kimi-linear-tiny.json", "kimi_linear", 40),
        "glm4_moe_lite": ("glm-tiny.json", "glm4_moe_lite", 40)}[model]
    cfg = harness.load_json(harness.HERE, "tests", "configs", config)
    ref = importlib.import_module("chipbench.references." + reference)
    tokens = mx.nd.array(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, seq), dtype=np.int32), dtype="int32")
    train = dict(dtype="float32", optimizer="adam", learning_rate=1e-3,
                 remat=True, seq=seq)
    weights, devices = ref.init_weights(cfg, 5), jax.devices()[:1]
    if model == "bert":
        trainer, _ = bench_models.bert_trainer(cfg, train, weights, devices)
    else:
        build = models_lm.kimi_linear_trainer if model == "kimi_linear" \
            else models_glm.glm4_moe_lite_trainer
        trainer, _ = build(cfg, train, weights, ref.selection_bias(cfg),
                           devices)
    return trainer.lower_step(tokens, tokens).as_text()


@pytest.mark.parametrize("model,whiles,sorts,parent", [
    ("bert", 8, 0,
     "abbbdbbad2fd8722270ee4cc17e7195ca1721408b970548380c89a5530bd2286"),
    ("kimi_linear", 40, 2,
     "76a28a798b4d188d5af899ba18833729f506766c72ec7e65a39deef7fec8a29e"),
    ("glm4_moe_lite", 26, 2,
     "cf34519cf450ba52af90b33f02c408ec8e65435467912060cc3483f5181a3583"),
])
def test_the_accepted_models_steps_lower_as_before_the_kept_keys(
        model, whiles, sorts, parent):
    """A set of kept keys in ``flash_attention``, a softmax score in the
    expert layer and the indexer are paths the three accepted cells'
    models do not take: their toy steps lower to the StableHLO they
    lowered to before those paths were there.

    First what a reader can see: no unsigned-integer value (only the
    selection's counting loop forms any), and the loops and sorts the
    step had.  Then the whole text, by its sha256 at commit 85e0e7b; to
    take it again: ``git archive 85e0e7b | tar -x -C <dir>``, copy this
    function and ``_toy_step_text`` into a test there, print
    ``hashlib.sha256(text.encode()).hexdigest()``.  The text is JAX's
    as much as ours: a change that means to alter these programs, or a
    new JAX, takes new digests at its own parent and says so."""
    import hashlib
    import re

    text = _toy_step_text(model)
    assert "ui32" not in text
    assert len(re.findall(r"stablehlo\.while", text)) == whiles
    assert len(re.findall(r"stablehlo\.sort", text)) == sorts
    assert hashlib.sha256(text.encode()).hexdigest() == parent, \
        "the step's StableHLO is not the parent's (%d characters)" % len(text)
