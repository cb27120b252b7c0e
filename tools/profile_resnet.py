"""Layout/batch/BN-dtype experiment for the ResNet-50 bench (VERDICT r2 #1).

Raw-JAX ResNet-50 train step (no framework overhead) to locate the MFU
ceiling on the real chip: NHWC vs NCHW conv layout, fp32-cast vs bf16
BatchNorm, batch {64,128,256}.  Run on the TPU; each config prints one
JSON line.  The winning config drives the mxtpu model-zoo/bench changes.
"""
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FLOPS_PER_IMG = 3 * 4.09e9
PEAK = 197e12

LAYERS = [3, 4, 6, 3]
WIDTHS = [64, 128, 256, 512]


MM1X1 = False  # 1x1-as-matmul measured slower (49.2 vs 46.8 ms): XLA's
# conv path already handles 1x1; the reshape adds copies. Kept for record.

# MXTPU_PALLAS_CONV_BWD=1: route 3x3/s1 convs through the fused Pallas
# dW+dX backward (mxtpu/ops/pallas/conv_bwd.py) — the round-4 candidate
# for the conv-weight-grad bandwidth problem this tool diagnosed.
import os as _os
_PALLAS_BWD = _os.environ.get("MXTPU_PALLAS_CONV_BWD", "") not in ("", "0")
_os.sys.path.insert(0, _os.path.join(_os.path.dirname(
    _os.path.abspath(__file__)), ".."))


def conv(x, w, stride, layout):
    if (_PALLAS_BWD and layout == "NHWC" and stride == 1
            and w.shape[0] == 3 and w.shape[1] == 3):
        from mxtpu.ops.pallas import conv_bwd
        return conv_bwd.conv3x3_s1(x, w)
    if layout == "NCHW_i":  # NCHW API, NHWC internal: XLA cancels the
        # transpose pairs between consecutive convs (hypothesis under test)
        y = conv(jnp.transpose(x, (0, 2, 3, 1)),
                 jnp.transpose(w, (2, 3, 1, 0)), stride, "NHWC")
        return jnp.transpose(y, (0, 3, 1, 2))
    if layout == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        dn = ("NCHW", "OIHW", "NCHW")
    kh = w.shape[0] if layout == "NHWC" else w.shape[2]
    if MM1X1 and kh == 1 and layout == "NHWC":
        if stride > 1:
            x = x[:, ::stride, ::stride, :]
        B, H, W, Cin = x.shape
        y = x.reshape(B * H * W, Cin) @ w.reshape(Cin, -1)
        return y.reshape(B, H, W, -1)
    pad = (kh - 1) // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=dn)


def bn(x, gamma, beta, layout, mode):
    """mode: 'fp32cast' = round-2 op (whole-activation fp32 cast);
    'bf16chain' = one-pass E[x]/E[x^2] stats with fp32 accumulation, then a
    single bf16 x*scale+shift elementwise chain (per-channel scale/shift
    folded in fp32 — the big tensor never leaves bf16)."""
    axis = 3 if layout == "NHWC" else 1
    red = tuple(i for i in range(4) if i != axis)
    in_dtype = x.dtype
    shape = [1 if i in red else -1 for i in range(4)]
    if mode == "fp32cast":
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=red)
        var = jnp.mean(jnp.square(x - mean.reshape(shape)), axis=red)
        inv = lax.rsqrt(var + 1e-5).reshape(shape)
        out = (x - mean.reshape(shape)) * inv
        out = out * gamma.reshape(shape) + beta.reshape(shape)
        return out.astype(in_dtype)
    # bf16chain
    xf = x.astype(jnp.float32)  # fused into the reduces, not materialized
    mean = jnp.mean(xf, axis=red)
    var = jnp.mean(lax.square(xf - mean.reshape(shape)), axis=red)
    scale = gamma * lax.rsqrt(var + 1e-5)
    shift = beta - mean * scale
    return (x * scale.reshape(shape).astype(in_dtype)
            + shift.reshape(shape).astype(in_dtype))


def init_params(key, layout, dtype, s2d=False):
    params = {}

    def cv(name, kh, cin, cout, kw=None):
        nonlocal key
        key, k = jax.random.split(key)
        kw = kw if kw is not None else kh
        fan = kh * kw * cin
        w = jax.random.normal(k, (kh, kw, cin, cout), dtype) * float(
            np.sqrt(2 / fan))
        if layout.startswith("NCHW"):
            w = jnp.transpose(w, (3, 2, 0, 1))
        params[name] = w

    def bnp(name, c):
        params[name + "_g"] = jnp.ones((c,), jnp.float32)
        params[name + "_b"] = jnp.zeros((c,), jnp.float32)

    if s2d:
        # space-to-depth stem (MLPerf ResNet trick): 7x7/s2 conv on
        # 224x224x3 == 4x4/s1 conv on 112x112x12 after 2x2 block reshape;
        # weights stay mathematically equivalent (8x8 zero-padded 7x7).
        cv("stem", 4, 12, 64)
    else:
        cv("stem", 7, 3, 64)
    bnp("stem_bn", 64)
    cin = 64
    for s, (n, wdt) in enumerate(zip(LAYERS, WIDTHS)):
        cout = wdt * 4
        for b in range(n):
            p = f"s{s}b{b}"
            cv(p + "_c1", 1, cin, wdt)
            bnp(p + "_bn1", wdt)
            cv(p + "_c2", 3, wdt, wdt)
            bnp(p + "_bn2", wdt)
            cv(p + "_c3", 1, wdt, cout)
            bnp(p + "_bn3", cout)
            if b == 0:
                cv(p + "_ds", 1, cin, cout)
                bnp(p + "_dsbn", cout)
            cin = cout
    key, k = jax.random.split(key)
    params["fc_w"] = jax.random.normal(k, (2048, 1000), dtype) * 0.01
    params["fc_w"] = params["fc_w"].astype(dtype)
    params["fc_b"] = jnp.zeros((1000,), jnp.float32)
    return params


def forward(params, x, layout, bn_mode, s2d=False):
    def B(name, y):
        return bn(y, params[name + "_g"], params[name + "_b"], layout,
                  bn_mode)

    if s2d:  # x arrives pre-reshaped (B,112,112,12); 4x4/s1 pad (2,1)
        y = lax.conv_general_dilated(
            x, params["stem"], (1, 1), [(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    else:
        y = conv(x, params["stem"], 2, layout)
    y = jax.nn.relu(B("stem_bn", y))
    window = (1, 3, 3, 1) if layout == "NHWC" else (1, 1, 3, 3)
    strides = (1, 2, 2, 1) if layout == "NHWC" else (1, 1, 2, 2)
    pad = [(0, 0), (1, 1), (1, 1), (0, 0)] if layout == "NHWC" else \
        [(0, 0), (0, 0), (1, 1), (1, 1)]
    y = lax.reduce_window(y, -jnp.inf, lax.max, window, strides, pad)
    for s, n in enumerate(LAYERS):
        for b in range(n):
            p = f"s{s}b{b}"
            stride = 2 if (b == 0 and s > 0) else 1
            r = conv(y, params[p + "_c1"], 1, layout)
            r = jax.nn.relu(B(p + "_bn1", r))
            r = conv(r, params[p + "_c2"], stride, layout)
            r = jax.nn.relu(B(p + "_bn2", r))
            r = conv(r, params[p + "_c3"], 1, layout)
            r = B(p + "_bn3", r)
            if b == 0:
                y = B(p + "_dsbn", conv(y, params[p + "_ds"], stride, layout))
            y = jax.nn.relu(y + r)
    axes = (1, 2) if layout == "NHWC" else (2, 3)
    y = jnp.mean(y, axis=axes)
    return y @ params["fc_w"] + params["fc_b"][None]


def loss_fn(params, x, lab, layout, bn_mode, s2d=False):
    logits = forward(params, x, layout, bn_mode, s2d).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], axis=1))


def run(layout, batch, bn_mode, s2d=False, iters=40):
    dtype = jnp.bfloat16
    params = init_params(jax.random.PRNGKey(0), layout, dtype, s2d)
    mom = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, mom, x, lab):
        loss, g = jax.value_and_grad(loss_fn)(params, x, lab, layout,
                                              bn_mode, s2d)
        new_m = jax.tree_util.tree_map(lambda m, gg: 0.9 * m + gg, mom, g)
        new_p = jax.tree_util.tree_map(
            lambda p, m: (p - 0.1 * m.astype(jnp.float32)).astype(p.dtype),
            params, new_m)
        return new_p, new_m, loss

    shape = (batch, 224, 224, 3) if layout == "NHWC" else (batch, 3, 224, 224)
    x = jnp.asarray(np.random.rand(*shape), dtype)
    if s2d:
        B, H, W, C = x.shape
        x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(
            0, 1, 3, 2, 4, 5).reshape(B, H // 2, W // 2, 4 * C)
    lab = jnp.asarray(np.random.randint(0, 1000, (batch,)), jnp.int32)
    for _ in range(3):
        params, mom, loss = step(params, mom, x, lab)
    lv0 = float(np.asarray(loss))
    t0 = time.perf_counter()
    for _ in range(iters):
        params, mom, loss = step(params, mom, x, lab)
    lv = float(np.asarray(loss))  # real host transfer: drains the queue
    dt = time.perf_counter() - t0
    ips = batch * iters / dt
    print(json.dumps({
        "layout": layout, "batch": batch, "bn": bn_mode, "s2d": s2d,
        "img_per_sec": round(ips, 1),
        "step_ms": round(dt / iters * 1e3, 2),
        "loss0": round(lv0, 3), "loss": round(lv, 3),
        "mfu": round(ips * FLOPS_PER_IMG / PEAK, 4)}), flush=True)


if __name__ == "__main__":
    from mxtpu.runtime import enable_compile_cache

    enable_compile_cache()
    configs = [
        ("NHWC", 128, "bf16chain", False),
        ("NHWC", 128, "bf16chain", True),
        ("NHWC", 256, "bf16chain", True),
        ("NHWC", 512, "bf16chain", True),
        ("NHWC", 128, "fp32cast", False),
        ("NCHW", 64, "fp32cast", False),
    ]
    if len(sys.argv) > 1:
        idx = [int(i) for i in sys.argv[1].split(",")]
        configs = [configs[i] for i in idx]
    for cfg in configs:
        run(*cfg)
