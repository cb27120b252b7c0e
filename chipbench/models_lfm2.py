"""LFM2 expert decoders under test, built through the system's normal
entry points and holding the arrays the benchmark made from ``--seed``
(as ``models_lm.py``, ``models_glm.py`` and ``models_keye.py`` do for
theirs; none is edited, this one stands beside them).
"""

import jax.numpy as jnp

from chipbench import models


def lfm2_moe_params(net):
    """{reference weight name: the program's Parameter} of an
    ``Lfm2MoeLM``: every trained parameter, and nothing else (the frozen
    selection bias and the counters are not weights; the head is the
    embedding)."""
    named = {"embed": net.embed.weight, "norm": net.norm.weight}
    for i, kind in enumerate(net.layer_types):
        p = "layer%d." % i
        mix, ff = net.decoder_layer(i)
        m, f = mix.inner, ff.inner
        named.update({p + "mix_norm": mix.norm.weight,
                      p + "ffn_norm": ff.norm.weight})
        if kind == "conv":
            named.update({p + "conv_in": m.in_proj.weight,
                          p + "conv_filter": m.conv,
                          p + "conv_out": m.out_proj.weight})
        else:
            named.update({
                p + "q": m.q_proj.weight, p + "k": m.k_proj.weight,
                p + "v": m.v_proj.weight, p + "q_norm": m.q_norm.weight,
                p + "k_norm": m.k_norm.weight, p + "out": m.out_proj.weight})
        if i < net.num_dense:
            named.update({p + "gate": f.gate_proj.weight,
                          p + "up": f.up_proj.weight,
                          p + "down": f.down_proj.weight})
        else:
            named.update({p + "router": f.router.weight,
                          p + "experts_gate": f.experts_gate,
                          p + "experts_up": f.experts_up,
                          p + "experts_down": f.experts_down})
    return named


def lfm2_moe_lm(cfg, weights, selection_bias, dtype="float32",
                return_logits=True):
    """``Lfm2MoeLM`` at ``cfg``'s sizes, this share's experts held,
    holding ``weights`` and the frozen ``selection_bias`` ({layer index:
    array}).  Returns (net, {name: Parameter})."""
    import mxtpu as mx
    from mxtpu.models.lfm2_moe import lfm2_moe_from_config
    from mxtpu.ndarray import NDArray

    net = lfm2_moe_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_total=cfg["num_experts_total"],
        return_logits=return_logits)
    net.initialize(mx.init.Zero())
    if dtype != "float32":
        net.cast(dtype)
    named = lfm2_moe_params(net)
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


def lfm2_moe_trainer(cfg, train, weights, selection_bias, devices):
    """``SPMDTrainer`` over the model as a user builds it (dp=1 mesh on
    one device, Adam, the model's own loss taken through the shared
    embedding in blocks of rows, recomputation per unit as
    ``train["remat"]`` says), in ``train["dtype"]``.  Returns (trainer,
    {name: Parameter})."""
    from mxtpu.parallel import SPMDTrainer

    net, named = lfm2_moe_lm(cfg, weights, selection_bias, train["dtype"],
                             return_logits=False)
    trainer = SPMDTrainer(net, net.loss(), train["optimizer"],
                          models.one_chip_mesh(devices),
                          optimizer_params={
                              "learning_rate": train["learning_rate"]},
                          remat=train["remat"])
    return trainer, named
