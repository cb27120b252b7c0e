"""Language models under test, built through the system's normal entry
points and holding the arrays the benchmark made from ``--seed`` (as
``models.py`` does for the configurations it serves; that file is not
edited, this one stands beside it).
"""

import jax.numpy as jnp

from chipbench import models


def lm_loss():
    """Cross-entropy over every position of (B, T, V) logits."""
    from mxtpu import gluon

    class LMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            return self._ce(logits.reshape((-1, logits.shape[-1])),
                            labels.reshape((-1,)))

    return LMLoss()


def kimi_linear_params(net):
    """{reference weight name: the program's Parameter} of a
    ``KimiLinearLM``: every trained parameter, and nothing else (the
    frozen selection bias and the load counters are not weights)."""
    named = {"embed": net.embed.weight, "norm": net.norm.weight,
             "lm_head": net.lm_head.weight}
    for i, (mixer, ffn) in enumerate(net.layer_kinds):
        p = "layer%d." % i
        mix, ff = net.decoder_layer(i)
        named[p + "mix_norm"] = mix.norm.weight
        named[p + "ffn_norm"] = ff.norm.weight
        m, f = mix.inner, ff.inner
        if mixer == "kda":
            named.update({
                p + "q": m.q_proj.weight, p + "k": m.k_proj.weight,
                p + "v": m.v_proj.weight, p + "q_conv": m.q_conv,
                p + "k_conv": m.k_conv, p + "v_conv": m.v_conv,
                p + "f_down": m.f_down.weight, p + "f_up": m.f_up.weight,
                p + "A_log": m.a_log, p + "dt_bias": m.dt_bias,
                p + "beta": m.beta_proj.weight,
                p + "g_down": m.g_down.weight, p + "g_up": m.g_up.weight,
                p + "g_up_bias": m.g_up.bias,
                p + "o_norm": m.o_norm.weight, p + "out": m.out_proj.weight})
        else:
            named.update({
                p + "q": m.q_proj.weight, p + "dkv": m.dkv_proj.weight,
                p + "kv_norm": m.kv_norm.weight,
                p + "ukv": m.ukv_proj.weight, p + "out": m.out_proj.weight})
        if ffn == "dense":
            named.update({p + "gate": f.gate_proj.weight,
                          p + "up": f.up_proj.weight,
                          p + "down": f.down_proj.weight})
        else:
            named.update({
                p + "router": f.router.weight,
                p + "experts_gate": f.experts_gate,
                p + "experts_up": f.experts_up,
                p + "experts_down": f.experts_down,
                p + "shared_gate": f.shared.gate_proj.weight,
                p + "shared_up": f.shared.up_proj.weight,
                p + "shared_down": f.shared.down_proj.weight})
    return named


def kimi_linear_lm(cfg, weights, selection_bias, dtype="float32"):
    """``KimiLinearLM`` at ``cfg``'s sizes, this share's experts held,
    holding ``weights`` and the frozen ``selection_bias`` ({layer:
    array}).  Returns (net, {name: Parameter})."""
    import mxtpu as mx
    from mxtpu.models.kimi_linear import kimi_linear_from_config
    from mxtpu.ndarray import NDArray

    net = kimi_linear_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_total=cfg["num_experts_total"],
        kda_gate_rank=cfg["assumed_sizes"]["kda_gate_rank"])
    net.initialize(mx.init.Zero())
    if dtype != "float32":
        net.cast(dtype)
    named = kimi_linear_params(net)
    if set(named) != set(weights):
        raise ValueError("weights and parameters differ in %r"
                         % sorted(set(named) ^ set(weights)))
    for name, param in named.items():
        # a copy: the trainer donates its parameters' buffers
        param.set_data(NDArray(jnp.array(weights[name], dtype=dtype,
                                         copy=True)))
    for i, bias in selection_bias.items():
        net.decoder_layer(i)[1].inner.select_bias.set_data(
            NDArray(jnp.array(bias, dtype=dtype, copy=True)))
    return net, named


def kimi_linear_trainer(cfg, train, weights, selection_bias, devices):
    """``SPMDTrainer`` over the model as a user builds it (dp=1 mesh on
    one device, Adam, recomputation per unit as ``train["remat"]`` says),
    in ``train["dtype"]``.  Returns (trainer, {name: Parameter})."""
    from mxtpu.parallel import SPMDTrainer

    net, named = kimi_linear_lm(cfg, weights, selection_bias,
                                train["dtype"])
    trainer = SPMDTrainer(net, lm_loss(), train["optimizer"],
                          models.one_chip_mesh(devices),
                          optimizer_params={
                              "learning_rate": train["learning_rate"]},
                          remat=train["remat"])
    return trainer, named
