"""Neural-net ops (parity: src/operator/nn/ — Convolution, FullyConnected,
BatchNorm, LayerNorm, Pooling, Activation, Dropout, softmax*, Embedding —
where the reference dispatches to cuDNN/oneDNN kernels).

On TPU all of these lower to XLA HLO that the compiler tiles onto the MXU
(conv/matmul) or fuses into elementwise chains (activations/norms), so the
cuDNN wrapper layer (src/operator/nn/cudnn/*) has no analogue: `lax.conv_
general_dilated` and `jnp.dot` ARE the tuned kernels.

Layout: the MXNet API default NCHW is preserved at the op boundary, but 2-D
convolutions run NHWC INTERNALLY (transpose in/out; XLA's algebraic
simplifier cancels the transpose pairs between consecutive convs).
Measured on a real v5e in round 2 (a raw-JAX ResNet-50 fwd+bwd+SGD
profile, batch 128 bf16; no ledger cell guards it — ROADMAP W6): NCHW
end-to-end 13.2% MFU, NHWC-internal 16.9% — the round-2 docstring's
claim that XLA re-lays out NCHW for free was wrong on TPU.  The remaining gap to peak is HBM bandwidth, not layout: the profiler
trace shows conv fusions at ~754 GB/s (~92% of v5e's 819 GB/s) with conv
weight-gradients alone moving 14 GB/step — ResNet-50's arithmetic
intensity (~140 flops/byte fwd+bwd) sits below the v5e ridge point
(240 flops/byte), so the op set is bandwidth-bound by roofline, and
normalization math is written to keep the big tensors in bf16 end-to-end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import register_op

# ---------------------------------------------------------------------------
# dense / conv — MXU ops
# ---------------------------------------------------------------------------

@register_op("FullyConnected", aliases=("fully_connected",))
def fully_connected(x, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    if flatten and x.ndim > 2:
        x = jnp.reshape(x, (x.shape[0], -1))
    # weight layout (num_hidden, in_units) as in the reference
    from .tensor import matmul_precision

    y = jnp.matmul(x, weight.T, precision=matmul_precision(x, weight))
    if bias is not None and not no_bias:
        y = y + bias
    return y


def _conv_dn(ndim, layout):
    if ndim == 1:
        return ("NCW", "OIW", "NCW")
    if ndim == 2:
        if layout == "NHWC":
            # MXNet NHWC weight convention: (num_filter, kh, kw, channels)
            return ("NHWC", "OHWI", "NHWC")
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


@register_op("Convolution", aliases=("convolution",))
def convolution(x, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                layout=None, cudnn_tune=None, cudnn_off=False,
                workspace=1024):
    """N-D convolution (1/2/3D by kernel length). Weight layout OIHW (MXNet;
    OHWI when layout='NHWC').  2-D NCHW convs transpose to NHWC internally —
    the measured-faster layout on TPU (see module docstring)."""
    ndim = len(kernel) if kernel else x.ndim - 2
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad = tuple(pad) if pad else (0,) * ndim
    layout = layout or ("NCHW" if ndim == 2 else None)
    from .tensor import matmul_precision

    if ndim == 2 and layout == "NCHW":
        x_nhwc = jnp.transpose(x, (0, 2, 3, 1))
        w_hwio = jnp.transpose(weight, (2, 3, 1, 0))  # OIHW -> HWIO
        y = lax.conv_general_dilated(
            x_nhwc, w_hwio,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=num_group,
            precision=matmul_precision(x, weight),
        )
        if bias is not None and not no_bias:
            y = y + bias
        return jnp.transpose(y, (0, 3, 1, 2))

    dn = _conv_dn(ndim, layout)
    y = lax.conv_general_dilated(
        x, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        precision=matmul_precision(x, weight),
    )
    if bias is not None and not no_bias:
        if ndim == 2 and layout == "NHWC":
            y = y + bias
        else:
            y = y + bias.reshape((1, -1) + (1,) * ndim)
    return y


@register_op("Deconvolution", aliases=("deconvolution",))
def deconvolution(x, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter=0, num_group=1, no_bias=False,
                  layout=None, target_shape=None, cudnn_tune=None,
                  cudnn_off=False, workspace=1024):
    """Transposed conv = gradient of conv wrt its input: lhs-dilate by
    stride, spatially flip the kernel, swap I/O filter axes.
    out = (in-1)*stride - 2*pad + (kernel-1)*dilate + 1 + adj
    (adj derived from target_shape when given, as in the reference).
    """
    ndim = len(kernel) if kernel else x.ndim - 2
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad = tuple(pad) if pad else (0,) * ndim
    ke = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    if target_shape:
        adj = tuple(
            t - ((x.shape[2 + i] - 1) * stride[i] - 2 * pad[i] + ke[i])
            for i, t in enumerate(target_shape))
    else:
        adj = tuple(adj) if adj else (0,) * ndim
    dn = _conv_dn(ndim, layout or "NCHW")
    padding = [(k - 1 - p, k - 1 - p + a) for k, p, a in zip(ke, pad, adj)]

    from .tensor import matmul_precision

    def one_group(xi, wi):
        return lax.conv_general_dilated(
            xi, jnp.flip(jnp.swapaxes(wi, 0, 1), axis=tuple(range(2, 2 + ndim))),
            window_strides=(1,) * ndim,
            padding=padding,
            lhs_dilation=stride,
            rhs_dilation=dilate,
            dimension_numbers=dn,
            precision=matmul_precision(xi, wi),
        )

    if num_group == 1:
        y = one_group(x, weight)
    else:
        xs = jnp.split(x, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        y = jnp.concatenate([one_group(xi, wi) for xi, wi in zip(xs, ws)],
                            axis=1)
    if bias is not None and not no_bias:
        y = y + bias.reshape((1, -1) + (1,) * ndim)
    return y


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register_op("Pooling", aliases=("pooling",))
def pooling(x, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", count_include_pad=True,
            cudnn_off=False, layout=None):
    sdims = x.ndim - 2  # spatial dims, layout NC + spatial
    if global_pool:
        axes = tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else (1,) * sdims
    pad = tuple(pad) if pad else (0,) * sdims
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    # 'full' convention (reference: ceil output sizing) = extra right-pad
    extra = [0] * sdims
    if pooling_convention == "full":
        for i in range(sdims):
            in_sz = x.shape[2 + i]
            valid_out = (in_sz + 2 * pad[i] - kernel[i]) // stride[i] + 1
            full_out = -(-(in_sz + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            extra[i] = (full_out - valid_out) * stride[i]
    padding = ((0, 0), (0, 0)) + tuple(
        (p, p + e) for p, e in zip(pad, extra))
    # reduce_window's reverse-mode (select_and_gather_add) rejects 16-bit
    # floats on some backends; pool in fp32 and cast back (max is exact,
    # avg/sum gain accuracy)
    in_dtype = x.dtype
    if in_dtype in (jnp.bfloat16, jnp.float16):
        x = x.astype(jnp.float32)
    # NOTE: init MUST be a python scalar literal — a traced array defeats
    # jax's monoid recognition and reduce_window loses its autodiff rule
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else int(jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max,
                                 window, strides, padding).astype(in_dtype)
    if pool_type in ("avg", "sum"):
        zero = 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0
        summed = lax.reduce_window(x, zero, lax.add,
                                   window, strides, padding)
        if pool_type == "sum":
            return summed.astype(in_dtype)
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return (summed / denom).astype(in_dtype)
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, zero, lax.add,
                                   window, strides, padding)
        return (summed / counts).astype(in_dtype)
    if pool_type == "lp":
        p2 = lax.reduce_window(jnp.square(x), 0.0, lax.add,
                               window, strides, padding)
        return jnp.sqrt(p2).astype(in_dtype)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

@register_op("Activation", aliases=("activation",))
def activation_op(x, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(x, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    raise ValueError(f"unknown act_type {act_type}")


@register_op("LeakyReLU")
def leaky_relu(x, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        return 1.0507009873554805 * jnp.where(
            x >= 0, x, 1.6732632423543772 * jnp.expm1(x))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "prelu":
        g = gamma
        shape = [1] * x.ndim
        if g.ndim == 1 and x.ndim > 1:
            shape[1] = g.shape[0]
            g = g.reshape(shape)
        return jnp.where(x >= 0, x, g * x)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(x >= 0, x, mid * x)
    raise ValueError(f"unknown act_type {act_type}")


@register_op("gelu_tanh")
def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


@register_op("swish", aliases=("silu",))
def swish(x, beta=1.0):
    return x * jax.nn.sigmoid(beta * x)


@register_op("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register_op("softmax")
def softmax(x, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        steps = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = steps.reshape(shape) < length.reshape(
            (-1,) + (1,) * (x.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmin")
def softmin(x, axis=-1):
    return jax.nn.softmax(-x, axis=axis)


@register_op("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    """Fused softmax + CE (parity: src/operator/loss_binary_op.cc).
    label is class indices; returns scalar sum loss."""
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(
        logp, label.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll)


#: rows of a head's logits that ``linear_cross_entropy`` forms at a time
CROSS_ENTROPY_ROWS = 1024


@register_op("linear_cross_entropy")
def linear_cross_entropy(data, weight, label,
                         block_rows=CROSS_ENTROPY_ROWS):
    """Softmax cross-entropy of a linear head, ``-log softmax(data
    weight^T)[label]`` for every row, without the logits: ``data``
    (.., C), ``weight`` (V, C) as ``FullyConnected`` lays it, ``label``
    (..) class indices; returns (..).  The rows go through in blocks of
    at most ``block_rows`` and each block is formed again in the
    backward pass, so a block's (rows, V) logits are alive at a time —
    where a head's whole logits (8,192 x 19,360 float32 are 634 MB, and
    their log-softmax and cotangent as much again) would not fit.  The
    price is the head's product a second time."""
    from .tensor import matmul_precision

    lead, width = data.shape[:-1], data.shape[-1]
    x = data.reshape(-1, width)
    y = label.astype(jnp.int32).reshape(-1)
    total = x.shape[0]
    rows = max(r for r in range(1, min(int(block_rows), total) + 1)
               if total % r == 0)

    @jax.checkpoint
    def block(start):
        xb = lax.dynamic_slice_in_dim(x, start, rows, 0)
        yb = lax.dynamic_slice_in_dim(y, start, rows, 0)
        logits = jnp.matmul(xb, weight.T,
                            precision=matmul_precision(xb, weight))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    nll = lax.map(block, jnp.arange(0, total, rows))
    return nll.reshape(lead).astype(data.dtype)


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------

@register_op("LayerNorm", aliases=("layer_norm",))
def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return (x - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)


@register_op("BatchNorm", aliases=("batch_norm",), differentiable=True)
def batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               axis=1, output_mean_var=False, _training=False):
    """BatchNorm forward.  Stats selection follows the reference
    (src/operator/nn/batch_norm.cc): batch stats when training and not
    use_global_stats, else moving stats.  The moving-stat update is done by
    the Gluon layer (aux-state write-back), not inside this pure op.
    """
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    red = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    if _training and not use_global_stats:
        # Two-pass batch stats: the fp32 casts fuse into the reduces
        # (convert_reduce_fusion on TPU) so the activation is never
        # materialized in fp32 — measured vs the round-2 whole-activation
        # fp32 cast on a real v5e (round 2, no ledger cell).  The centered
        # second pass avoids E[x^2]-E[x]^2 catastrophic cancellation for
        # large-mean channels.
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        var = jnp.mean(lax.square(xf - mean.reshape(shape)), axis=red)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
    # fold per-channel scale/shift in fp32; the big tensor stays in x.dtype
    scale = gamma.astype(jnp.float32) * lax.rsqrt(var + eps)
    shift = beta.astype(jnp.float32) - mean * scale
    out = x * scale.reshape(shape).astype(x.dtype) \
        + shift.reshape(shape).astype(x.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register_op("InstanceNorm")
def instance_norm(x, gamma, beta, eps=1e-3):
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register_op("GroupNorm")
def group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    b, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = x.reshape((b, num_groups, c // num_groups) + spatial)
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=red, keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    out = xg.reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register_op("L2Normalization", aliases=("l2_normalization",))
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, x.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, x.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + eps)
    return x / nrm


# ---------------------------------------------------------------------------
# dropout / embedding
# ---------------------------------------------------------------------------

@register_op("Dropout", aliases=("dropout",))
def dropout_op(x, p=0.5, mode="training", axes=(), _training=False, _key=None):
    """Dropout.  _training/_key are injected by the NDArray wrapper: the key
    comes from the global key-ring (eager) or the traced per-call key under
    hybridize (see mxtpu/random.py), so compiled nets get fresh randomness
    each step — the TPU answer to the reference's per-device cuDNN dropout
    state (src/operator/nn/dropout-inl.h).
    """
    if (not _training and mode != "always") or p == 0 or _key is None:
        return x
    shape = list(x.shape)
    for ax in axes or ():
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(_key, keep, tuple(shape)).astype(x.dtype)
    return x * mask / keep


@register_op("Embedding", aliases=("embedding",))
def embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# legacy symbolic-loss heads
# ---------------------------------------------------------------------------

# The *Output heads carry the reference's implicit-loss-gradient semantics
# (src/operator/softmax_output.cc, regression_output-inl.h): forward is the
# prediction; backward wrt data is the LOSS gradient (the incoming cotangent
# — ones from Executor.backward — is ignored), encoded via custom_vjp.

import functools


@functools.lru_cache(maxsize=64)
def _softmax_output_cvjp(grad_scale, ignore_label, multi_output, use_ignore,
                         normalization, smooth_alpha):
    """custom_vjp softmax-output specialized on its static config."""

    @jax.custom_vjp
    def op(data, label):
        return jax.nn.softmax(data, axis=1 if multi_output else -1)

    def op_fwd(data, label):
        return op(data, label), (op(data, label), label)

    def op_bwd(res, g):
        p, label = res
        axis = 1 if multi_output else -1
        nclass = p.shape[axis]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, nclass, axis=axis, dtype=p.dtype)
        if smooth_alpha:
            onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / nclass
        grad = p - onehot
        if use_ignore:
            valid = (lab != ignore_label)
            grad = grad * jnp.expand_dims(valid, axis).astype(p.dtype)
        if normalization == "batch":
            grad = grad / p.shape[0]
        elif normalization == "valid":
            if use_ignore:
                grad = grad / jnp.maximum(valid.sum(), 1).astype(p.dtype)
            else:
                grad = grad / p.shape[0]
        return (grad * grad_scale, None)

    op.defvjp(op_fwd, op_bwd)
    return op


@register_op("SoftmaxOutput", aliases=("softmax_output",))
def softmax_output(data, label=None, grad_scale=1.0, ignore_label=-1,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    if label is None:
        return jax.nn.softmax(data, axis=1 if multi_output else -1)
    return _softmax_output_cvjp(float(grad_scale), int(ignore_label),
                                bool(multi_output), bool(use_ignore),
                                str(normalization),
                                float(smooth_alpha))(data, label)


def _make_regression_output(grad_fn, pred_fn=lambda d: d):
    @functools.lru_cache(maxsize=16)
    def specialized(grad_scale):
        @jax.custom_vjp
        def op(data, label):
            return pred_fn(data)

        def op_fwd(data, label):
            return pred_fn(data), (data, label)

        def op_bwd(res, g):
            data, label = res
            lab = label.reshape(data.shape).astype(data.dtype)
            return (grad_fn(data, lab) * grad_scale, None)

        op.defvjp(op_fwd, op_bwd)
        return op

    return lambda data, label, grad_scale: \
        specialized(float(grad_scale))(data, label)


_linreg_cvjp = _make_regression_output(lambda d, l: d - l)
_maereg_cvjp = _make_regression_output(lambda d, l: jnp.sign(d - l))
_logreg_cvjp = _make_regression_output(
    lambda d, l: jax.nn.sigmoid(d) - l, pred_fn=jax.nn.sigmoid)


@register_op("LinearRegressionOutput")
def linear_regression_output(data, label=None, grad_scale=1.0):
    if label is None:
        return data
    return _linreg_cvjp(data, label, grad_scale)


@register_op("MAERegressionOutput")
def mae_regression_output(data, label=None, grad_scale=1.0):
    if label is None:
        return data
    return _maereg_cvjp(data, label, grad_scale)


@register_op("LogisticRegressionOutput")
def logistic_regression_output(data, label=None, grad_scale=1.0):
    if label is None:
        return jax.nn.sigmoid(data)
    return _logreg_cvjp(data, label, grad_scale)


@register_op("BilinearSampler")
def bilinear_sampler(data, grid):
    # data: (B, C, H, W); grid: (B, 2, Ho, Wo) in [-1, 1]
    B, C, H, W = data.shape
    gx = (grid[:, 0] + 1) * (W - 1) / 2
    gy = (grid[:, 1] + 1) * (H - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = gx - x0
    wy = gy - y0

    def gather(y, x):
        yc = jnp.clip(y, 0, H - 1)
        xc = jnp.clip(x, 0, W - 1)
        idx = yc * W + xc  # (B, Ho, Wo)
        flat = data.reshape(B, C, H * W)
        g = jnp.take_along_axis(
            flat, idx.reshape(B, 1, -1).repeat(C, axis=1), axis=2)
        valid = ((y >= 0) & (y <= H - 1) & (x >= 0) & (x <= W - 1))
        return g.reshape(B, C, *idx.shape[1:]) * valid[:, None].astype(data.dtype)

    out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[:, None]
           + gather(y0, x1) * (wx * (1 - wy))[:, None]
           + gather(y1, x0) * ((1 - wx) * wy)[:, None]
           + gather(y1, x1) * (wx * wy)[:, None])
    return out


@register_op("ctc_loss", aliases=("CTCLoss", "_contrib_ctc_loss"))
def ctc_loss(data, label=None, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first", _layout="TNC"):
    """CTC loss per sequence (parity: src/operator/nn/ctc_loss.cc which binds
    warp-ctc/cuDNN; here optax's XLA-native lattice implementation).

    MXNet op semantics: data (T, B, V) [the reference op's layout], label
    (B, L) int, labels < 1 treated as padding when use_label_lengths=False
    (blank index 0 = blank_label='first').  _layout='NTC' is an internal
    escape used by gluon.loss.CTCLoss to skip the transpose."""
    import optax

    if blank_label != "first":
        raise ValueError("mxtpu ctc_loss supports blank_label='first' only")
    logits = jnp.swapaxes(data, 0, 1) if _layout == "TNC" else data  # (B,T,V)
    B, T, _ = logits.shape
    labels = label.astype(jnp.int32)
    if use_data_lengths and data_lengths is not None:
        logit_paddings = (jnp.arange(T)[None, :]
                          >= data_lengths.astype(jnp.int32)[:, None]
                          ).astype(jnp.float32)
    else:
        logit_paddings = jnp.zeros((B, T), jnp.float32)
    L = labels.shape[1]
    if use_label_lengths and label_lengths is not None:
        label_paddings = (jnp.arange(L)[None, :]
                          >= label_lengths.astype(jnp.int32)[:, None]
                          ).astype(jnp.float32)
    else:
        label_paddings = (labels < 1).astype(jnp.float32)
    return optax.ctc_loss(logits, logit_paddings, labels, label_paddings,
                          blank_id=0)


# ----------------------------------------------------------------- fused RNN

def _rnn_param_sizes(mode, input_size, state_size, num_layers, bidirectional,
                     projection_size=None):
    """Per-(layer, direction) packed weight/bias shapes in cuDNN order
    (parity: src/operator/rnn-inl.h GetRnnParamSize). With projection_size
    (LSTMP), h2h consumes the projected state and per-cell h2r projection
    weights are appended after all biases."""
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]
    dirs = 2 if bidirectional else 1
    hid_out = projection_size if projection_size else state_size
    shapes = []
    for layer in range(num_layers):
        in_size = input_size if layer == 0 else hid_out * dirs
        for _ in range(dirs):
            shapes.append(("i2h_w", (ngates * state_size, in_size)))
            shapes.append(("h2h_w", (ngates * state_size, hid_out)))
    for layer in range(num_layers):
        for _ in range(dirs):
            shapes.append(("i2h_b", (ngates * state_size,)))
            shapes.append(("h2h_b", (ngates * state_size,)))
    if projection_size:
        for layer in range(num_layers):
            for _ in range(dirs):
                shapes.append(("h2r_w", (projection_size, state_size)))
    return ngates, dirs, shapes


def rnn_param_count(mode, input_size, state_size, num_layers, bidirectional,
                    projection_size=None):
    import math
    _, _, shapes = _rnn_param_sizes(mode, input_size, state_size, num_layers,
                                    bidirectional, projection_size)
    return sum(math.prod(s) for _, s in shapes)


def _unpack_rnn_params(params, mode, input_size, state_size, num_layers,
                       bidirectional, projection_size=None):
    ngates, dirs, shapes = _rnn_param_sizes(
        mode, input_size, state_size, num_layers, bidirectional,
        projection_size)
    out = []
    offset = 0
    for _, shape in shapes:
        size = 1
        for d in shape:
            size *= d
        out.append(params[offset:offset + size].reshape(shape))
        offset += size
    # regroup: weights first (2 per layer-dir), then biases, then projections
    n = num_layers * dirs
    cells = []
    for i in range(n):
        i2h_w, h2h_w = out[2 * i], out[2 * i + 1]
        i2h_b, h2h_b = out[2 * n + 2 * i], out[2 * n + 2 * i + 1]
        h2r_w = out[4 * n + i] if projection_size else None
        cells.append((i2h_w, h2h_w, i2h_b, h2h_b, h2r_w))
    return cells


def _rnn_cell_step(mode, w, carry, x):
    """One timestep. carry: (h,) or (h, c). x: (B, in). Returns new carry +
    output h."""
    i2h_w, h2h_w, i2h_b, h2h_b, h2r_w = w
    if mode in ("rnn_relu", "rnn_tanh"):
        (h,) = carry
        pre = x @ i2h_w.T + i2h_b + h @ h2h_w.T + h2h_b
        h_new = jax.nn.relu(pre) if mode == "rnn_relu" else jnp.tanh(pre)
        return (h_new,), h_new
    if mode == "lstm":
        h, c = carry
        pre = x @ i2h_w.T + i2h_b + h @ h2h_w.T + h2h_b
        i, f, g, o = jnp.split(pre, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        o = jax.nn.sigmoid(o)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        if h2r_w is not None:  # LSTMP: project hidden before recurrence
            h_new = h_new @ h2r_w.T
        return (h_new, c_new), h_new
    if mode == "gru":
        (h,) = carry
        gi = x @ i2h_w.T + i2h_b
        gh = h @ h2h_w.T + h2h_b
        ir, iz, inw = jnp.split(gi, 3, axis=-1)
        hr, hz, hn = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(ir + hr)
        z = jax.nn.sigmoid(iz + hz)
        n = jnp.tanh(inw + r * hn)
        h_new = (1.0 - z) * n + z * h
        return (h_new,), h_new
    raise ValueError("unknown RNN mode %r" % mode)


@register_op("RNN", aliases=("rnn",))
def rnn(data, parameters, state, state_cell=None, state_size=0, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, sequence_length=None,
        use_sequence_length=False, _training=False, _key=None):
    """Fused multi-layer (bi)RNN (parity: src/operator/rnn.cc backed by
    cuDNN cudnnRNNForward; here a lax.scan over timesteps per layer — XLA
    fuses the gate matmuls into MXU-sized batched GEMMs).

    data: (T, B, I). parameters: packed 1-D vector in cuDNN layout.
    state: (L*D, B, H); state_cell likewise for LSTM.
    Returns output (T, B, H*D) or [output, h_n(, c_n)] when state_outputs.
    """
    if projection_size and mode != "lstm":
        raise ValueError("projection_size is only supported for mode='lstm'")
    T, B, _ = data.shape
    input_size = data.shape[2]
    cells = _unpack_rnn_params(parameters, mode, input_size, state_size,
                               num_layers, bidirectional, projection_size)
    dirs = 2 if bidirectional else 1
    is_lstm = mode == "lstm"

    lengths = None
    if use_sequence_length and sequence_length is not None:
        lengths = sequence_length.astype(jnp.int32)  # (B,)

    h_states = []
    c_states = []
    x = data
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            idx = layer * dirs + d
            w = cells[idx]
            h0 = state[idx]
            carry = (h0, state_cell[idx]) if is_lstm else (h0,)
            if lengths is None:
                seq = x if d == 0 else x[::-1]

                def step(carry, xt, w=w):
                    return _rnn_cell_step(mode, w, carry, xt)

                carry, ys = lax.scan(step, carry, seq)
                if d == 1:
                    ys = ys[::-1]
            else:
                # variable length: reverse only each row's valid prefix for
                # the backward direction, freeze the carry past each row's
                # length, and zero padded outputs — matches the reference's
                # use_sequence_length cuDNN path observable semantics.
                t_idx = jnp.arange(T)[:, None]  # (T, 1)
                if d == 1:
                    gather = jnp.where(t_idx < lengths[None, :],
                                       lengths[None, :] - 1 - t_idx, t_idx)
                    seq = jnp.take_along_axis(x, gather[:, :, None], axis=0)
                else:
                    seq = x

                def step(carry, inp, w=w):
                    xt, t = inp
                    new_carry, y = _rnn_cell_step(mode, w, carry, xt)
                    valid = (t < lengths)[:, None]
                    new_carry = tuple(
                        jnp.where(valid, n, o)
                        for n, o in zip(new_carry, carry))
                    y = jnp.where(valid, y, jnp.zeros_like(y))
                    return new_carry, y

                carry, ys = lax.scan(step, carry, (seq, jnp.arange(T)))
                if d == 1:
                    gather = jnp.where(t_idx < lengths[None, :],
                                       lengths[None, :] - 1 - t_idx, t_idx)
                    ys = jnp.take_along_axis(ys, gather[:, :, None], axis=0)
                    valid = t_idx < lengths[None, :]
                    ys = jnp.where(valid[:, :, None], ys,
                                   jnp.zeros_like(ys))
            outs.append(ys)
            h_states.append(carry[0])
            if is_lstm:
                c_states.append(carry[1])
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and _training and layer < num_layers - 1 \
                and _key is not None:
            import jax.random as jrandom
            keep = jrandom.bernoulli(jrandom.fold_in(_key, layer), 1.0 - p,
                                     x.shape)
            x = jnp.where(keep, x / (1.0 - p), 0.0)
    if not state_outputs:
        return x
    if is_lstm:
        return x, jnp.stack(h_states), jnp.stack(c_states)
    return x, jnp.stack(h_states)


@register_op("SoftmaxActivation", differentiable=True)
def softmax_activation(x, mode="instance"):
    """Deprecated reference op (src/operator/nn/softmax_activation.cc):
    softmax over channels (mode='channel', axis 1) or over all non-batch
    dims flattened (mode='instance')."""
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    flat = jnp.reshape(x, (x.shape[0], -1))
    return jnp.reshape(jax.nn.softmax(flat, axis=-1), x.shape)


# ---------------------------------------------------------------------------
# spatial-transform / legacy vision ops (round 4: op-surface widening)
# ---------------------------------------------------------------------------

@register_op("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Sampling-grid generation (parity: src/operator/grid_generator.cc).
    affine: data (B, 6) -> grid (B, 2, H, W) in [-1, 1].
    warp: data (B, 2, H, W) pixel flow added to the identity grid."""
    if transform_type == "affine":
        H, W = int(target_shape[0]), int(target_shape[1])
        theta = data.reshape(-1, 2, 3)
        xs = jnp.linspace(-1.0, 1.0, W)
        ys = jnp.linspace(-1.0, 1.0, H)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx.ravel(), gy.ravel(),
                          jnp.ones(H * W, data.dtype)])  # (3, H*W)
        out = jnp.einsum("bij,jk->bik", theta.astype(jnp.float32),
                         base.astype(jnp.float32))       # (B, 2, H*W)
        return out.reshape(-1, 2, H, W).astype(data.dtype)
    if transform_type == "warp":
        B, _, H, W = data.shape
        xs = jnp.arange(W, dtype=jnp.float32)
        ys = jnp.arange(H, dtype=jnp.float32)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        fx = data[:, 0].astype(jnp.float32) + gx
        fy = data[:, 1].astype(jnp.float32) + gy
        # normalize to [-1, 1]
        nx = 2.0 * fx / jnp.maximum(W - 1, 1) - 1.0
        ny = 2.0 * fy / jnp.maximum(H - 1, 1) - 1.0
        return jnp.stack([nx, ny], axis=1).astype(data.dtype)
    raise ValueError("GridGenerator: unknown transform_type %r"
                     % (transform_type,))


@register_op("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine",
                        sampler_type="bilinear", cudnn_off=False):
    """STN (parity: src/operator/spatial_transformer.cc): affine grid
    from loc + bilinear sampling."""
    if sampler_type != "bilinear":
        raise ValueError("SpatialTransformer: only bilinear sampling")
    grid = grid_generator(loc, transform_type, target_shape)
    return bilinear_sampler(data, grid)


@register_op("LRN", aliases=("lrn",))
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Across-channel local response normalization (parity:
    src/operator/nn/lrn.cc — the AlexNet-era op)."""
    sq = jnp.square(data.astype(jnp.float32))
    half = nsize // 2
    ssum = lax.reduce_window(sq, 0.0, lax.add, (1, nsize, 1, 1),
                             (1, 1, 1, 1),
                             [(0, 0), (half, half), (0, 0), (0, 0)])
    denom = jnp.power(knorm + (alpha / nsize) * ssum, beta)
    return (data.astype(jnp.float32) / denom).astype(data.dtype)


def _resize_bilinear_ac(data, oh, ow):
    """align_corners bilinear resize on NCHW (the reference's
    BilinearResize2D convention: scale = (in-1)/(out-1))."""
    B, C, H, W = data.shape
    x = data.astype(jnp.float32)

    def along(arr, axis, out_size, in_size):
        if in_size == 1 or out_size == 1:
            pos = jnp.zeros((out_size,), jnp.float32)
        else:
            pos = jnp.linspace(0.0, in_size - 1.0, out_size)
        i0 = jnp.floor(pos).astype(jnp.int32)
        i1 = jnp.minimum(i0 + 1, in_size - 1)
        w1 = pos - i0
        a0 = jnp.take(arr, i0, axis=axis)
        a1 = jnp.take(arr, i1, axis=axis)
        shape = [1] * arr.ndim
        shape[axis] = out_size
        w1 = w1.reshape(shape)
        return a0 * (1 - w1) + a1 * w1

    x = along(x, 2, oh, H)
    x = along(x, 3, ow, W)
    return x.astype(data.dtype)


@register_op("BilinearResize2D", aliases=("_contrib_BilinearResize2D",))
def bilinear_resize_2d(data, height=0, width=0, scale_height=None,
                       scale_width=None, mode="size"):
    """(parity: src/operator/contrib/bilinear_resize.cc)"""
    if mode != "size":
        raise ValueError(
            "BilinearResize2D: mode=%r unsupported (only 'size'; the "
            "'like'/odd_scale variants need a second input)" % (mode,))
    B, C, H, W = data.shape
    oh = int(round(H * scale_height)) if scale_height else int(height)
    ow = int(round(W * scale_width)) if scale_width else int(width)
    return _resize_bilinear_ac(data, oh, ow)


@register_op("UpSampling")
def upsampling(*data, scale=1, sample_type="nearest", num_args=1,
               workspace=512, num_filter=0, multi_input_mode="concat"):
    """(parity: src/operator/nn/upsampling.cc).  nearest repeats pixels;
    bilinear resizes (the reference's bilinear variant is a fixed-kernel
    deconvolution — same result for align_corners geometry).  Multiple
    inputs are upsampled to the first input's scaled size and
    concatenated on channels."""
    scale = int(scale)
    B, C, H, W = data[0].shape
    oh, ow = H * scale, W * scale
    outs = []
    for d in data:
        if sample_type == "nearest":
            r = oh // d.shape[2]
            u = jnp.repeat(jnp.repeat(d, r, axis=2), ow // d.shape[3],
                           axis=3)
        else:
            u = _resize_bilinear_ac(d, oh, ow)
        outs.append(u)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        return sum(outs[1:], outs[0])
    return jnp.concatenate(outs, axis=1)


@register_op("Crop", aliases=("crop",))
def crop_op(*data, offset=(0, 0), h_w=(0, 0), center_crop=False,
            num_args=1):
    """Legacy Crop (parity: src/operator/crop.cc): crop data[0] to
    data[1]'s spatial size (or h_w) at offset / centered."""
    x = data[0]
    H, W = x.shape[2], x.shape[3]
    if len(data) > 1:
        th, tw = data[1].shape[2], data[1].shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    if center_crop:
        oy, ox = (H - th) // 2, (W - tw) // 2
    else:
        oy, ox = int(offset[0]), int(offset[1])
    return x[:, :, oy:oy + th, ox:ox + tw]


@register_op("MakeLoss", aliases=("make_loss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0,
              normalization="null"):
    """(parity: src/operator/make_loss.cc): forward is identity; the
    BACKWARD ignores the incoming gradient and emits grad_scale — the
    symbolic 'this output IS the loss' marker."""
    if normalization == "batch":
        denom = data.shape[0]
    elif normalization == "valid":
        denom = None  # computed from data at runtime
    else:
        denom = 1.0

    @jax.custom_vjp
    def f(x):
        return x

    def f_fwd(x):
        return x, x

    def f_bwd(x, g):
        if denom is None:
            n = jnp.maximum(jnp.sum(
                (x > valid_thresh).astype(jnp.float32)), 1.0)
        else:
            n = denom
        return (jnp.full_like(x, grad_scale) / n,)

    f.defvjp(f_fwd, f_bwd)
    return f(data)


@register_op("im2col")
def im2col(data, kernel=(), stride=(), dilate=(), pad=()):
    """(parity: src/operator/nn/im2col.h exposed as the im2col op):
    (B, C, H, W) -> (B, C*kh*kw, Ho*Wo)."""
    kh, kw = kernel
    ndim = 2
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad = tuple(pad) if pad else (0,) * ndim
    patches = lax.conv_general_dilated_patches(
        data, (kh, kw), stride, [(pad[0], pad[0]), (pad[1], pad[1])],
        rhs_dilation=dilate)  # (B, C*kh*kw, Ho, Wo)
    B, CKK = patches.shape[:2]
    return patches.reshape(B, CKK, -1)


@register_op("col2im")
def col2im(data, output_size=(), kernel=(), stride=(), dilate=(),
           pad=()):
    """Adjoint of im2col (parity: col2im — overlapping patches sum)."""
    kh, kw = kernel
    C = data.shape[1] // (kh * kw)
    B = data.shape[0]
    shape = (B, C, int(output_size[0]), int(output_size[1]))
    _, vjp = jax.vjp(
        lambda a: im2col(a, kernel=kernel, stride=stride, dilate=dilate,
                         pad=pad), jnp.zeros(shape, data.dtype))
    return vjp(data)[0]


def _abs_bilinear_gather(data, ys, xs):
    """Bilinear sample NCHW data at absolute coords ys/xs (B, Ho, Wo);
    out-of-bounds contributes zero (matches BilinearSampler)."""
    B, C, H, W = data.shape
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1, x1 = y0 + 1, x0 + 1
    wy = ys - y0
    wx = xs - x0

    flat = data.reshape(B, C, H * W)

    def gather(y, x):
        yc = jnp.clip(y, 0, H - 1)
        xc = jnp.clip(x, 0, W - 1)
        idx = (yc * W + xc).reshape(B, 1, -1)
        g = jnp.take_along_axis(flat, jnp.broadcast_to(
            idx, (B, C, idx.shape[-1])), axis=2)
        valid = ((y >= 0) & (y <= H - 1) & (x >= 0) & (x <= W - 1))
        return (g.reshape(B, C, *y.shape[1:])
                * valid[:, None].astype(data.dtype))

    return (gather(y0, x0) * ((1 - wx) * (1 - wy))[:, None]
            + gather(y0, x1) * (wx * (1 - wy))[:, None]
            + gather(y1, x0) * ((1 - wx) * wy)[:, None]
            + gather(y1, x1) * (wx * wy)[:, None])


@register_op("deformable_convolution",
             aliases=("_contrib_DeformableConvolution",))
def deformable_convolution(data, offset, weight, bias=None, kernel=(),
                           stride=(), dilate=(), pad=(), num_filter=0,
                           num_group=1, num_deformable_group=1,
                           no_bias=False, workspace=1024, layout=None):
    """Deformable conv v1 (parity: src/operator/contrib/
    deformable_convolution.cc).  Each kernel tap samples the input at its
    regular position plus a learned per-position (y, x) offset, via
    bilinear interpolation; the deformed im2col columns then contract
    with the weights on the MXU."""
    if num_group != 1:
        raise ValueError("deformable_convolution: num_group>1 TBD")
    kh, kw = kernel
    ndim = 2
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad = tuple(pad) if pad else (0,) * ndim
    B, C, H, W = data.shape
    Ho = (H + 2 * pad[0] - dilate[0] * (kh - 1) - 1) // stride[0] + 1
    Wo = (W + 2 * pad[1] - dilate[1] * (kw - 1) - 1) // stride[1] + 1
    DG = num_deformable_group
    off = offset.reshape(B, DG, kh, kw, 2, Ho, Wo).astype(jnp.float32)
    cg = C // DG

    base_y = (jnp.arange(Ho) * stride[0] - pad[0]).astype(jnp.float32)
    base_x = (jnp.arange(Wo) * stride[1] - pad[1]).astype(jnp.float32)
    gy, gx = jnp.meshgrid(base_y, base_x, indexing="ij")  # (Ho, Wo)

    cols = []
    for g in range(DG):
        dslice = data[:, g * cg:(g + 1) * cg]
        for i in range(kh):
            for j in range(kw):
                ys = gy[None] + i * dilate[0] + off[:, g, i, j, 0]
                xs = gx[None] + j * dilate[1] + off[:, g, i, j, 1]
                cols.append(_abs_bilinear_gather(dslice, ys, xs))
    # (B, DG*kh*kw*cg, Ho, Wo) ordered [dg][i][j][c] -> regroup to
    # [dg][c][i][j] = weight's (O, C, kh, kw) contraction order
    col = jnp.stack(cols, axis=1).reshape(B, DG, kh * kw, cg, Ho, Wo)
    col = col.transpose(0, 1, 3, 2, 4, 5).reshape(B, C * kh * kw, Ho, Wo)
    from .tensor import matmul_precision
    w2 = weight.reshape(num_filter, -1)  # (O, C*kh*kw)
    y = jnp.einsum("ok,bkhw->bohw", w2, col,
                   precision=matmul_precision(data, weight))
    if bias is not None and not no_bias:
        y = y + bias.reshape(1, -1, 1, 1)
    return y.astype(data.dtype)


@register_op("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1,
                stride1=1, stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation (parity: src/operator/correlation.cc),
    kernel_size=1 form: one output channel per displacement, each the
    channel-mean of data1 * shifted(data2)."""
    if kernel_size != 1:
        raise ValueError("Correlation: kernel_size>1 TBD")
    B, C, H, W = data1.shape
    p = pad_size
    d1 = jnp.pad(data1, ((0, 0), (0, 0), (p, p), (p, p)))
    d2 = jnp.pad(data2, ((0, 0), (0, 0), (p, p), (p, p)))
    Hp, Wp = H + 2 * p, W + 2 * p
    drange = range(-max_displacement, max_displacement + 1, stride2)
    outs = []
    for dy in drange:
        for dx in drange:
            shifted = jnp.roll(d2, (-dy, -dx), axis=(2, 3))
            if is_multiply:
                prod = d1 * shifted
            else:
                prod = jnp.abs(d1 - shifted)
            # zero out wrapped-around borders
            ys = jnp.arange(Hp)[None, None, :, None] + dy
            xs = jnp.arange(Wp)[None, None, None, :] + dx
            valid = ((ys >= 0) & (ys < Hp) & (xs >= 0)
                     & (xs < Wp)).astype(prod.dtype)
            corr = jnp.mean(prod * valid, axis=1)  # (B, Hp, Wp)
            outs.append(corr)
    out = jnp.stack(outs, axis=1)  # (B, D*D, Hp, Wp)
    # reference shape contract (correlation.cc): trim the displacement
    # border, then stride — top = (H + 2*pad - 2*border) / stride1 with
    # border = max_displacement + kernel_radius (radius 0 at ks=1)
    border = max_displacement
    out = out[:, :, border:Hp - border, border:Wp - border]
    if stride1 > 1:
        out = out[:, :, ::stride1, ::stride1]
    return out


# ---------------------------------------------------------------------------
# round-5 tail (VERDICT r4 item 2): ROIPooling, SVMOutput, KL sparse-reg
# identity, rnn_param_concat

@register_op("ROIPooling", aliases=("roi_pooling",))
def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    """Legacy max ROI pooling (src/operator/roi_pooling.cc): integer bin
    boundaries (Fast-RCNN), unlike ROIAlign's bilinear sampling.  Empty
    bins produce 0, matching the reference kernel."""
    B, C, H, W = data.shape
    ph, pw = pooled_size

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        i = jnp.arange(ph)
        j = jnp.arange(pw)
        hstart = y1 + jnp.floor(i * rh / ph).astype(jnp.int32)
        hend = y1 + jnp.ceil((i + 1) * rh / ph).astype(jnp.int32)
        wstart = x1 + jnp.floor(j * rw / pw).astype(jnp.int32)
        wend = x1 + jnp.ceil((j + 1) * rw / pw).astype(jnp.int32)
        hs = jnp.arange(H)
        ws = jnp.arange(W)
        mh = (hs[None, :] >= jnp.clip(hstart, 0, H)[:, None]) \
            & (hs[None, :] < jnp.clip(hend, 0, H)[:, None])    # (ph, H)
        mw = (ws[None, :] >= jnp.clip(wstart, 0, W)[:, None]) \
            & (ws[None, :] < jnp.clip(wend, 0, W)[:, None])    # (pw, W)
        mask = mh[:, None, :, None] & mw[None, :, None, :]     # (ph,pw,H,W)
        img = data[bidx]                                       # (C, H, W)
        neg = jnp.asarray(-jnp.inf, img.dtype)
        vals = jnp.where(mask[:, :, None], img[None, None], neg)
        out = vals.max(axis=(-1, -2))                          # (ph, pw, C)
        out = jnp.where(jnp.isfinite(out), out, 0)
        return jnp.transpose(out, (2, 0, 1))                   # (C, ph, pw)

    return jax.vmap(one_roi)(rois)


@functools.lru_cache(maxsize=16)
def _svm_output_cvjp(margin, reg_coef, use_linear):
    """custom_vjp one-vs-all SVM head (svm_output-inl.h): forward is the
    identity prediction; backward wrt data is the hinge-loss gradient
    (incoming cotangent ignored — same implicit-loss contract as
    SoftmaxOutput)."""

    @jax.custom_vjp
    def op(data, label):
        return data

    def op_fwd(data, label):
        return data, (data, label)

    def op_bwd(res, g):
        data, label = res
        nclass = data.shape[-1]
        t = 2.0 * jax.nn.one_hot(label.astype(jnp.int32), nclass,
                                 dtype=data.dtype) - 1.0
        slack = margin - t * data
        if use_linear:          # L1-SVM: d/df max(0, m - t f) = -t [slack>0]
            grad = -reg_coef * t * (slack > 0)
        else:                   # L2-SVM: d/df max(0, m - t f)^2
            grad = -2.0 * reg_coef * t * jnp.maximum(slack, 0)
        return (grad.astype(data.dtype), None)

    op.defvjp(op_fwd, op_bwd)
    return op


@register_op("SVMOutput", aliases=("svm_output",))
def svm_output(data, label=None, margin=1.0,
               regularization_coefficient=1.0, use_linear=False):
    if label is None:
        return data
    return _svm_output_cvjp(float(margin),
                            float(regularization_coefficient),
                            bool(use_linear))(data, label)


@functools.lru_cache(maxsize=16)
def _kl_sparse_reg_cvjp(sparseness_target, penalty):
    """Identity forward; backward adds the KL sparsity penalty gradient on
    the mean activation (identity_attach_KL_sparse_reg-inl.h).
    Divergence: the reference keeps a momentum-smoothed moving average of
    the mean activation rho_hat across calls (mutable aux state); here
    rho_hat is the current batch mean — functional, and identical in the
    momentum=0 configuration."""

    @jax.custom_vjp
    def op(data):
        return data

    def op_fwd(data):
        return data, data

    def op_bwd(data, g):
        rho_hat = jnp.clip(jnp.mean(data, axis=0), 1e-6, 1 - 1e-6)
        kl_grad = penalty * (-sparseness_target / rho_hat
                             + (1.0 - sparseness_target) / (1.0 - rho_hat))
        return (g + kl_grad / data.shape[0],)

    op.defvjp(op_fwd, op_bwd)
    return op


@register_op("IdentityAttachKLSparseReg",
             aliases=("identity_attach_KL_sparse_reg",))
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    return _kl_sparse_reg_cvjp(float(sparseness_target),
                               float(penalty))(data)


@register_op("rnn_param_concat", aliases=("_rnn_param_concat",))
def rnn_param_concat(*data, dim=0, num_args=None):
    """Concat specialized for RNN parameter packing (rnn_param_concat.cc
    — same compute as Concat, but mixed-rank inputs flatten first when
    packing along dim 0: the op's whole purpose is fusing 2-D weight
    matrices and 1-D biases into the single packed RNN parameter)."""
    if dim == 0 and len({d.ndim for d in data}) > 1:
        return jnp.concatenate([d.reshape(-1) for d in data], axis=0)
    return jnp.concatenate(list(data), axis=dim)
