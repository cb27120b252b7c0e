"""The gated short convolution of convolution-attention hybrids (LFM2:
``model_type: lfm2`` / ``lfm2_moe``): a token mixer with no attention,
no recurrence and no activation,

    [b | c | x] = the input's three chunks of C columns, in that order
    z_t = b_t * x_t
    y_t = c_t * sum_j filt[:, j] * z_{t - (W - 1) + j}      j = 0 .. W - 1

a causal depthwise cross-correlation of ``W`` taps over time, one filter
a channel, zeros before the sequence's start, the last tap on the
current position, between two element-wise gates.  The projections that
make ``bcx`` and take ``y`` are the model's.

Plain ``jax.numpy`` that JAX differentiates: at three taps the mixer is
a few passes over (B, T, C) — memory-bound and small beside its two
projections — and XLA fuses the gates with the taps.
``causal_short_conv`` is the convolution alone, which KDA's q, k, v
filters take too (``ops/pallas/kda.py``).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..base import register_op


def causal_short_conv(x, filt):
    """Causal depthwise convolution over time: x (B, T, *channels), filt
    (*channels, W), one filter a channel, left-padded with zeros."""
    W, T = filt.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (W - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(padded[:, j:j + T] * filt[..., j] for j in range(W))


@register_op("gated_short_conv", aliases=("_contrib_gated_short_conv",))
def gated_short_conv(bcx, filt):
    """``bcx`` (B, T, 3 C), ``filt`` (C, W) -> (B, T, C), accumulated in
    float32 and returned in ``bcx``'s type."""
    C = filt.shape[0]
    if bcx.shape[-1] != 3 * C:
        raise ValueError("bcx has %d columns, the filter %d channels: "
                         "3 x %d wanted" % (bcx.shape[-1], C, C))
    b, c, x = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32)
               for i in range(3))
    w = filt.astype(jnp.float32)
    y = causal_short_conv(b * x, w)
    return (c * y).astype(bcx.dtype)
