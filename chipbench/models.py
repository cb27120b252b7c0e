"""The system under test, built through its normal entry points.

Copies of ``chip_smoke.py``'s constructors (the yardstick may not import
a file later PRs may edit), changed in one way: the weights are not the
program's own initialisation but the arrays the benchmark made from
``--seed`` (``references/<name>.init_weights``), loaded by walking the
model's blocks, so that the program and its plain reference start from
the same numbers and neither takes anything from the other.
"""

import jax
import jax.numpy as jnp


def one_chip_mesh(devices):
    from mxtpu.parallel import make_mesh

    return make_mesh(dp=1, devices=list(devices[:1]))


# -------------------------------------------------------------------- BERT

def bert_for_mlm(cfg, seq):
    """BERT with the MLM head as the training output, and its loss —
    ``chip_smoke.bert_for_mlm``'s construction at ``cfg``'s sizes."""
    from mxtpu import gluon
    from mxtpu.gluon import HybridBlock
    from mxtpu.models import transformer

    class BertForMLM(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.bert = transformer.BERTModel(
                    vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
                    hidden_size=cfg["intermediate_size"],
                    num_layers=cfg["num_hidden_layers"],
                    num_heads=cfg["num_attention_heads"],
                    max_length=seq, dropout=0.0)

        def hybrid_forward(self, F, tokens):
            _seq, _pooled, mlm = self.bert(tokens)
            return mlm

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, mlm, labels):
            return self._ce(mlm.reshape((-1, mlm.shape[-1])),
                            labels.reshape((-1,)))

    return BertForMLM(), MLMLoss()


def bert_params(net):
    """{reference weight name: the program's Parameter}.  The program's
    segment embedding and pooler have no counterpart: they do not enter
    the MLM loss and stay zero."""
    bert = net.bert
    named = {
        "word_embed": bert.word_embed.weight,
        "position_embed": bert.position_embed.weight,
        "embed_ln.gamma": bert.embed_ln.gamma,
        "embed_ln.beta": bert.embed_ln.beta,
        "mlm.weight": bert.mlm_decoder.weight,
        "mlm.bias": bert.mlm_decoder.bias,
    }
    for i, layer in enumerate(bert.encoder.layers):
        p = "layer%d." % i
        for name, block in (("qkv", layer.attn.qkv),
                            ("out", layer.attn.out_proj),
                            ("ffn1", layer.ffn1), ("ffn2", layer.ffn2)):
            named[p + name + ".weight"] = block.weight
            named[p + name + ".bias"] = block.bias
        for name, block in (("ln1", layer.ln1), ("ln2", layer.ln2)):
            named[p + name + ".gamma"] = block.gamma
            named[p + name + ".beta"] = block.beta
    return named


def bert_trainer(cfg, train, weights, devices):
    """``SPMDTrainer`` over BERT as ``chip_smoke.bert_trainer`` builds it
    (dp=1 mesh on one device, Adam), in ``train["dtype"]``, holding
    ``weights``.  Returns (trainer, {name: Parameter})."""
    import mxtpu as mx
    from mxtpu.ndarray import NDArray
    from mxtpu.parallel import SPMDTrainer

    net, loss = bert_for_mlm(cfg, train["seq"])
    net.initialize(mx.init.Zero())
    if train["dtype"] != "float32":
        net.cast(train["dtype"])
    named = bert_params(net)
    if set(named) != set(weights):
        raise ValueError("weights and parameters differ in %r"
                         % sorted(set(named) ^ set(weights)))
    for name, param in named.items():
        # a copy: the trainer donates its parameters' buffers
        param.set_data(NDArray(jnp.array(weights[name], dtype=train["dtype"],
                                         copy=True)))
    trainer = SPMDTrainer(net, loss, train["optimizer"],
                          one_chip_mesh(devices),
                          optimizer_params={
                              "learning_rate": train["learning_rate"]})
    return trainer, named


def trainer_state(trainer, named):
    """What the comparison reads of a trainer, by reference name: the
    parameters and Adam's (mean, var) as the program holds them now."""
    index = {id(p): i for i, p in enumerate(trainer._diff_params)}
    params = {n: p.data()._data for n, p in named.items()}
    mean = {n: trainer._opt_states[index[id(p)]][0]
            for n, p in named.items()}
    return params, mean


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def change_norms(after, before):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        after[k].astype(jnp.float32) - before[k].astype(jnp.float32))))
        for k in after}


# ----------------------------------------------------------------- decoder

def decoder_params(lm):
    """{reference weight name: the program's Parameter} of a
    ``TransformerLM``."""
    named = {"embed": lm.embed.weight, "norm": lm.norm.weight,
             "lm_head": lm.lm_head.weight}
    for i, layer in enumerate(lm.layers):
        p = "layer%d." % i
        named.update({
            p + "attn_norm": layer.attn_norm.weight,
            p + "qkv": layer.attn.qkv.weight,
            p + "out": layer.attn.out_proj.weight,
            p + "ffn_norm": layer.ffn_norm.weight,
            p + "gate": layer.gate_proj.weight,
            p + "up": layer.up_proj.weight,
            p + "down": layer.down_proj.weight})
    return named


def decoder_server(cfg, serve, make_leaves, devices):
    """``Gateway([PagedContinuousBatchingEngine(TransformerLM)])`` as
    ``chip_smoke.llama`` / ``server_phase`` build them, at ``cfg``'s sizes
    and ``serve``'s engine settings, holding the weights that
    ``make_leaves(names)`` makes (called once per layer, so that the
    model is never held twice).  Returns (gateway, engine)."""
    import mxtpu as mx
    from mxtpu.models import transformer
    from mxtpu.models.transformer import transformer_lm_sharding_rules
    from mxtpu.ndarray import NDArray
    from mxtpu.parallel import PagedContinuousBatchingEngine
    from mxtpu.serving import Gateway

    lm = transformer.TransformerLM(
        cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"])
    lm.collect_params().setattr("grad_req", "null")   # serving: no grads
    lm.cast(serve["dtype"])         # before initialize: never an f32 copy
    named = decoder_params(lm)
    groups = {}
    for name in named:
        groups.setdefault(name.split(".")[0], []).append(name)
    for names in groups.values():       # the ends, then layer by layer
        made = make_leaves(names)
        for name in names:
            named[name].initialize(mx.init.Zero())
            named[name].set_data(NDArray(made.pop(name)))
    lm(mx.nd.zeros((1, 16), dtype="int32"))     # resolve deferred shapes
    engine = PagedContinuousBatchingEngine(
        lm, one_chip_mesh(devices), transformer_lm_sharding_rules(),
        **serve["engine"])
    return Gateway([engine]), engine
