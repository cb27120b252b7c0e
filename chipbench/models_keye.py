"""Keye-VL-2.0 language models under test, built through the system's
normal entry points and holding the arrays the benchmark made from
``--seed`` (as ``models_lm.py`` and ``models_glm.py`` do for theirs;
neither is edited, this one stands beside them).
"""

import jax.numpy as jnp

from chipbench import models


def keye_vl_params(net):
    """{reference weight name: the program's Parameter} of a
    ``KeyeVLTextLM``: every trained parameter, and nothing else (the
    counters are not weights)."""
    named = {"embed": net.embed.weight, "norm": net.norm.weight,
             "lm_head": net.lm_head.weight}
    for i in range(net.num_layers):
        p = "layer%d." % i
        mix, ffn = net.decoder_layer(i)
        m, f = mix.inner, ffn.inner
        named.update({
            p + "mix_norm": mix.norm.weight, p + "q": m.q_proj.weight,
            p + "k": m.k_proj.weight, p + "v": m.v_proj.weight,
            p + "q_norm": m.q_norm.weight, p + "k_norm": m.k_norm.weight,
            p + "out": m.out_proj.weight,
            p + "index_q": m.index_q.weight, p + "index_k": m.index_k.weight,
            p + "index_k_gain": m.index_k_norm.gamma,
            p + "index_k_bias": m.index_k_norm.beta,
            p + "index_w": m.index_w.weight,
            p + "ffn_norm": ffn.norm.weight, p + "router": f.router.weight,
            p + "experts_gate": f.experts_gate,
            p + "experts_up": f.experts_up,
            p + "experts_down": f.experts_down})
    return named


def keye_vl_lm(cfg, weights, dtype="float32", return_logits=True):
    """``KeyeVLTextLM`` at ``cfg``'s sizes, this share's experts held,
    holding ``weights``.  Returns (net, {name: Parameter})."""
    import mxtpu as mx
    from mxtpu.models.keye_vl import keye_vl_from_config
    from mxtpu.ndarray import NDArray

    net = keye_vl_from_config(
        cfg, held=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_total=cfg["num_experts_total"],
        return_logits=return_logits)
    net.initialize(mx.init.Zero())
    if dtype != "float32":
        net.cast(dtype)
    named = keye_vl_params(net)
    if set(named) != set(weights):
        raise ValueError("weights and parameters differ in %r"
                         % sorted(set(named) ^ set(weights)))
    for name, param in named.items():
        # a copy: the trainer donates its parameters' buffers
        param.set_data(NDArray(jnp.array(weights[name], dtype=dtype,
                                         copy=True)))
    return net, named


def keye_vl_trainer(cfg, train, weights, devices):
    """``SPMDTrainer`` over the model as a user builds it (dp=1 mesh on
    one device, Adam, the model's own two-term loss at the
    configuration's weight taken through the head in blocks of rows,
    recomputation per unit as ``train["remat"]`` says), in
    ``train["dtype"]``.  Returns (trainer, {name: Parameter})."""
    from mxtpu.parallel import SPMDTrainer

    net, named = keye_vl_lm(cfg, weights, train["dtype"],
                            return_logits=False)
    trainer = SPMDTrainer(net, net.loss(cfg["index_loss_weight"]),
                          train["optimizer"], models.one_chip_mesh(devices),
                          optimizer_params={
                              "learning_rate": train["learning_rate"]},
                          remat=train["remat"])
    return trainer, named
