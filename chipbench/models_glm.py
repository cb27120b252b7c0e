"""GLM-4.7-Flash decoders under test, built through the system's normal
entry points and holding the arrays the benchmark made from ``--seed``
(as ``models_lm.py`` does for Kimi-Linear; neither that file nor
``models.py`` is edited, this one stands beside them).
"""

import jax.numpy as jnp

from chipbench import models


def _attention_params(named, p, block):
    m = block.inner
    named.update({
        p + "mix_norm": block.norm.weight, p + "q_a": m.q_a_proj.weight,
        p + "q_norm": m.q_norm.weight, p + "q_b": m.q_b_proj.weight,
        p + "dkv": m.dkv_proj.weight, p + "kv_norm": m.kv_norm.weight,
        p + "ukv": m.ukv_proj.weight, p + "out": m.out_proj.weight})


def _expert_params(named, p, block):
    f = block.inner
    named.update({
        p + "ffn_norm": block.norm.weight, p + "router": f.router.weight,
        p + "experts_gate": f.experts_gate, p + "experts_up": f.experts_up,
        p + "experts_down": f.experts_down,
        p + "shared_gate": f.shared.gate_proj.weight,
        p + "shared_up": f.shared.up_proj.weight,
        p + "shared_down": f.shared.down_proj.weight})


def glm4_moe_lite_params(net):
    """{reference weight name: the program's Parameter} of a
    ``Glm4MoeLiteLM``: every trained parameter, and nothing else (the
    frozen selection bias and the counters are not weights)."""
    named = {"embed": net.embed.weight, "norm": net.norm.weight,
             "lm_head": net.lm_head.weight}
    for i in range(net.num_layers):
        p = "layer%d." % i
        mix, ff = net.decoder_layer(i)
        _attention_params(named, p, mix)
        if i < net.num_dense:
            named.update({p + "ffn_norm": ff.norm.weight,
                          p + "gate": ff.inner.gate_proj.weight,
                          p + "up": ff.inner.up_proj.weight,
                          p + "down": ff.inner.down_proj.weight})
        else:
            _expert_params(named, p, ff)
    if net.mtp is not None:
        named.update({"mtp.enorm": net.mtp.enorm.weight,
                      "mtp.hnorm": net.mtp.hnorm.weight,
                      "mtp.eh_proj": net.mtp.eh_proj.weight,
                      "mtp.norm": net.mtp.norm.weight})
        _attention_params(named, "mtp.", net.mtp.mix)
        _expert_params(named, "mtp.", net.mtp.ffn)
    return named


def glm4_moe_lite_lm(cfg, weights, selection_bias, dtype="float32",
                     return_logits=True):
    """``Glm4MoeLiteLM`` at ``cfg``'s sizes, this share's experts held,
    holding ``weights`` and the frozen ``selection_bias`` ({layer index:
    array}, the prediction module's under ``num_hidden_layers``).
    Returns (net, {name: Parameter})."""
    import mxtpu as mx
    from mxtpu.models.glm4_moe_lite import glm4_moe_lite_from_config
    from mxtpu.ndarray import NDArray

    net = glm4_moe_lite_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["n_routed_experts"]),
        num_experts_total=cfg["num_experts_total"],
        return_logits=return_logits)
    net.initialize(mx.init.Zero())
    if dtype != "float32":
        net.cast(dtype)
    named = glm4_moe_lite_params(net)
    if set(named) != set(weights):
        raise ValueError("weights and parameters differ in %r"
                         % sorted(set(named) ^ set(weights)))
    for name, param in named.items():
        # a copy: the trainer donates its parameters' buffers
        param.set_data(NDArray(jnp.array(weights[name], dtype=dtype,
                                         copy=True)))
    for i, bias in selection_bias.items():
        ffn = net.mtp.ffn if i == net.num_layers else net.decoder_layer(i)[1]
        ffn.inner.select_bias.set_data(
            NDArray(jnp.array(bias, dtype=dtype, copy=True)))
    return net, named


def glm4_moe_lite_trainer(cfg, train, weights, selection_bias, devices):
    """``SPMDTrainer`` over the model as a user builds it (dp=1 mesh on
    one device, Adam, the model's own two-term loss at the
    configuration's weight taken through the head in blocks of rows,
    recomputation per unit as ``train["remat"]`` says), in
    ``train["dtype"]``.  Returns (trainer, {name: Parameter})."""
    from mxtpu.parallel import SPMDTrainer

    net, named = glm4_moe_lite_lm(cfg, weights, selection_bias,
                                  train["dtype"], return_logits=False)
    trainer = SPMDTrainer(net, net.loss(cfg["mtp_weight"]),
                          train["optimizer"], models.one_chip_mesh(devices),
                          optimizer_params={
                              "learning_rate": train["learning_rate"]},
                          remat=train["remat"])
    return trainer, named
