"""Registry-wide operator sweep (VERDICT r2 task 3; parity:
tests/python/unittest/test_operator.py's per-op gradient checks +
check_consistency).

Every UNIQUE op in the mxtpu registry must appear either in CASES (and get
eager-vs-jit consistency, bf16-vs-fp32 consistency, and — when marked
differentiable — a numeric-vs-autodiff gradient check) or in SKIP with a
stated reason.  test_registry_fully_covered enforces completeness, so a
newly registered op fails CI until it is covered or explicitly skipped.
"""

import functools

import numpy as onp
import pytest
import jax
import jax.numpy as jnp

from mxtpu import base
import mxtpu.contrib.quantization  # noqa: F401 — registers the int8 ops

R = onp.random.RandomState(42)


def A(*shape, lo=-2.0, hi=2.0, dtype="float32"):
    """Dense float input away from kinks/domain edges by construction."""
    return jnp.asarray(R.uniform(lo, hi, shape).astype(dtype))


def POS(*shape, lo=0.5, hi=2.0):
    return A(*shape, lo=lo, hi=hi)


def UNIT(*shape):
    return A(*shape, lo=-0.9, hi=0.9)


def IDX(*shape, n=4):
    return jnp.asarray(R.randint(0, n, shape).astype("int32"))


def I8(*shape):
    """int8 payload input (quantized cache / packed-weight ops)."""
    return jnp.asarray(R.randint(-127, 128, shape).astype("int8"))


def SCL(*shape):
    """Small positive per-head scales (quantized-cache scale tensors)."""
    return POS(*shape, lo=0.01, hi=0.1)


def SPD(n=3):
    """Well-conditioned symmetric positive-definite matrix."""
    a = R.randn(n, n).astype("float32")
    return jnp.asarray(a @ a.T + 3 * onp.eye(n, dtype="float32"))


def LTRI(n=3):
    """Well-conditioned lower-triangular matrix (positive diagonal)."""
    a = onp.tril(R.randn(n, n).astype("float32"))
    return jnp.asarray(a + 3 * onp.eye(n, dtype="float32"))


def _BOXES(n):
    """Valid corner boxes (x1<x2, y1<y2) on a bf16-exact 1/32 grid."""
    xy = R.randint(0, 8, (n, 2)).astype("float32") / 32.0
    wh = R.randint(4, 12, (n, 2)).astype("float32") / 32.0
    return jnp.asarray(onp.concatenate([xy, xy + wh], axis=1))


def _MB_LABEL(B=2, M=3):
    """Padded (B, M, 5) detection labels [cls, x1, y1, x2, y2]."""
    lab = onp.full((B, M, 5), -1.0, "float32")
    for b in range(B):
        for m in range(M - 1):  # leave one padding row
            xy = R.rand(2) * 0.4
            wh = R.rand(2) * 0.4 + 0.15
            lab[b, m] = [R.randint(0, 3), xy[0], xy[1],
                         xy[0] + wh[0], xy[1] + wh[1]]
    return jnp.asarray(lab)


def _NMS_DATA(n=6):
    ids = R.randint(0, 2, (n, 1)).astype("float32")
    scores = (R.permutation(n).reshape(n, 1).astype("float32") + 1) / n
    return jnp.asarray(
        onp.concatenate([ids, scores, onp.asarray(_BOXES(n))], axis=1)
    )[None]  # (1, n, 6)


class Case:
    def __init__(self, args, kwargs=None, grad=True, grad_args=None,
                 jit=True, bf16=True, rtol=1e-2, atol=1e-3):
        self.args = args            # callable -> tuple of jax arrays
        self.kwargs = kwargs or {}
        self.grad = grad            # run numeric-vs-autodiff gradient
        self.grad_args = grad_args  # indices of args to differentiate
        self.jit = jit              # eager-vs-jit consistency
        self.bf16 = bf16            # bf16-vs-fp32 consistency
        self.rtol = rtol
        self.atol = atol


C = Case

_UNARY_ANY = ["negative", "square", "exp", "expm1", "sin", "cos", "tanh",
              "sinh", "cosh", "arctan", "arcsinh", "erf", "sigmoid",
              "softsign", "gelu_tanh", "swish", "hard_sigmoid", "identity",
              "relu"]
_UNARY_POS = ["sqrt", "rsqrt", "log", "log10", "log2", "log1p", "cbrt",
              "rcbrt", "reciprocal", "gammaln", "gamma", "abs"]
_UNARY_UNIT = ["arcsin", "arccos", "arctanh", "erfinv"]
_UNARY_NONDIFF = ["rint", "round", "floor", "ceil", "trunc", "fix", "sign",
                  "isnan", "isinf", "isfinite", "logical_not"]
_BINARY = ["add", "subtract", "multiply", "elemwise_sub", "elemwise_mul",
           "maximum", "minimum", "hypot", "broadcast_plus",
           "broadcast_minus", "broadcast_sub", "broadcast_mul"]
_BINARY_DIV = ["divide", "elemwise_div", "broadcast_div"]
_CMP = ["equal", "not_equal", "greater", "greater_equal", "lesser",
        "lesser_equal", "logical_and", "logical_or", "logical_xor"]
_SCALAR_DIFF = ["_plus_scalar", "_minus_scalar", "_rminus_scalar",
                "_mul_scalar", "_div_scalar", "_rdiv_scalar",
                "_power_scalar", "_rpower_scalar", "_maximum_scalar"]
_SCALAR_CMP = ["_equal_scalar", "_not_equal_scalar", "_greater_scalar",
               "_greater_equal_scalar", "_lesser_scalar",
               "_lesser_equal_scalar", "_mod_scalar", "_rmod_scalar"]
_REDUCE = ["sum", "mean", "max", "min", "nansum", "cumsum"]

CASES = {}
for _n in _UNARY_ANY:
    CASES[_n] = C(lambda: (A(3, 4),))
for _n in _UNARY_POS:
    CASES[_n] = C(lambda: (POS(3, 4),))
for _n in _UNARY_UNIT:
    CASES[_n] = C(lambda: (UNIT(3, 4),), rtol=5e-2, atol=5e-3)
for _n in _UNARY_NONDIFF:
    CASES[_n] = C(lambda: (A(3, 4),), grad=False)
for _n in _BINARY:
    CASES[_n] = C(lambda: (A(3, 4), A(3, 4)))
for _n in _BINARY_DIV:
    CASES[_n] = C(lambda: (A(3, 4), POS(3, 4)))
for _n in _CMP:
    CASES[_n] = C(lambda: (A(3, 4), A(3, 4)), grad=False)
for _n in _SCALAR_DIFF:
    CASES[_n] = C(lambda: (POS(3, 4),), {"scalar": 2.0})
for _n in _SCALAR_CMP:
    # 0.25-grid values are exactly representable in bf16, so no element
    # can round across the 0.7 threshold and flip the comparison
    CASES[_n] = C(lambda: (jnp.asarray(
        R.randint(2, 9, (3, 4)).astype("float32") * 0.25),),
        {"scalar": 0.7}, grad=False)
for _n in _REDUCE:
    CASES[_n] = C(lambda: (A(3, 4),))

CASES.update({
    # keep every element pair separated by >= 0.5 with RANDOM winner per
    # element: no near-tie hits the subgradient kink, yet both selection
    # branches carry gradient (globally disjoint ranges would test only
    # one branch)
    "maximum": C(lambda: (lambda x, d: (x, x + d))(
        A(3, 4), A(3, 4, lo=0.5, hi=1.5) * jnp.asarray(
            R.choice([-1.0, 1.0], (3, 4)).astype("float32")))),
    "minimum": C(lambda: (lambda x, d: (x, x + d))(
        A(3, 4), A(3, 4, lo=0.5, hi=1.5) * jnp.asarray(
            R.choice([-1.0, 1.0], (3, 4)).astype("float32")))),
    "power": C(lambda: (POS(3, 4), A(3, 4, lo=0.5, hi=1.5))),
    "arctan2": C(lambda: (POS(3, 4), POS(3, 4))),
    "arccosh": C(lambda: (A(3, 4, lo=1.5, hi=3.0),)),
    "tan": C(lambda: (A(3, 4, lo=0.1, hi=1.2),)),  # stay below the pi/2 pole
    # scalar 2.5 keeps every input strictly on the x branch (no kink)
    "_minimum_scalar": C(lambda: (POS(3, 4),), {"scalar": 2.5}),
    "mod": C(lambda: (POS(3, 4, lo=2.0, hi=3.0), POS(3, 4)), grad=False),
    "prod": C(lambda: (POS(2, 3),)),
    "norm": C(lambda: (POS(3, 4),)),
    "add_n": C(lambda: (A(3, 4), A(3, 4), A(3, 4))),
    "SoftmaxActivation": C(lambda: (A(3, 4),), {"mode": "channel"}),
    # -- linalg family (la_op.cc) ---------------------------------------
    "linalg_gemm": C(lambda: (A(3, 4), A(4, 5), A(3, 5)),
                     {"alpha": 1.5, "beta": 0.5}),
    "linalg_potrf": C(lambda: (SPD(),), rtol=5e-2, atol=5e-3,
                      bf16=False),
    "linalg_potri": C(lambda: (LTRI(),), rtol=5e-2, atol=5e-3),
    "linalg_trmm": C(lambda: (LTRI(), A(3, 2))),
    "linalg_trsm": C(lambda: (LTRI(), A(3, 2)), rtol=5e-2, atol=5e-3),
    "linalg_syrk": C(lambda: (A(3, 4),)),
    "linalg_sumlogdiag": C(lambda: (LTRI(),)),
    "linalg_extractdiag": C(lambda: (A(3, 3),)),
    "linalg_makediag": C(lambda: (A(4),)),
    "linalg_extracttrian": C(lambda: (A(3, 3),)),
    "linalg_maketrian": C(lambda: (A(6),)),
    "linalg_inverse": C(lambda: (SPD(),), rtol=5e-2, atol=5e-3,
                        bf16=False),
    "linalg_det": C(lambda: (SPD(),), rtol=5e-2, atol=5e-3),
    "linalg_slogdet": C(lambda: (SPD(),), grad=False, bf16=False),
    "linalg_gelqf": C(lambda: (A(2, 4),), grad=False, bf16=False),
    "linalg_syevd": C(lambda: (SPD(),), grad=False, bf16=False),
    "clip": C(lambda: (A(3, 4),), {"a_min": -1.0, "a_max": 1.0},
              grad=False),
    "smooth_l1": C(lambda: (POS(3, 4),)),
    "where": C(lambda: (IDX(3, 4, n=2).astype(bool), A(3, 4), A(3, 4)),
               grad_args=(1, 2)),
    "cast": C(lambda: (A(3, 4),), {"dtype": "float32"}, grad=False),
    "stop_gradient": C(lambda: (A(3, 4),), grad=False),
    # -- structural ------------------------------------------------------
    "reshape": C(lambda: (A(3, 4),), {"shape": (4, 3)}),
    "reshape_like": C(lambda: (A(3, 4), A(2, 6)), grad_args=(0,)),
    "transpose": C(lambda: (A(3, 4),)),
    "swapaxes": C(lambda: (A(2, 3, 4),), {"dim1": 0, "dim2": 2}),
    "expand_dims": C(lambda: (A(3, 4),), {"axis": 1}),
    "squeeze": C(lambda: (A(3, 1, 4),)),
    "flatten": C(lambda: (A(2, 3, 4),)),
    "flip": C(lambda: (A(3, 4),), {"axis": 0}),
    "tile": C(lambda: (A(2, 3),), {"reps": (2, 2)}),
    "repeat": C(lambda: (A(2, 3),), {"repeats": 2, "axis": 1}),
    "stack": C(lambda: (A(2, 3), A(2, 3)), {"axis": 1}),
    "concat": C(lambda: (A(2, 3), A(2, 3)), {"dim": 1}),
    "split": C(lambda: (A(4, 6),), {"num_outputs": 2, "axis": 1}),
    "split_v2": C(lambda: (A(4, 6),), {"indices_or_sections": 2, "axis": 1}),
    "slice": C(lambda: (A(4, 6),), {"begin": (1, 0), "end": (3, 4)}),
    "slice_axis": C(lambda: (A(4, 6),), {"axis": 1, "begin": 1, "end": 4}),
    "slice_like": C(lambda: (A(4, 6), A(2, 3)), grad_args=(0,)),
    "broadcast_to": C(lambda: (A(1, 4),), {"shape": (3, 4)}),
    "broadcast_axis": C(lambda: (A(1, 4),), {"axis": 0, "size": 3}),
    "broadcast_like": C(lambda: (A(1, 4), A(3, 4)), grad_args=(0,)),
    "pad": C(lambda: (A(1, 1, 3, 4),),
             {"mode": "constant",
              "pad_width": (0, 0, 0, 0, 1, 1, 2, 2)}),
    "depth_to_space": C(lambda: (A(1, 4, 2, 2),), {"block_size": 2}),
    "space_to_depth": C(lambda: (A(1, 1, 4, 4),), {"block_size": 2}),
    "diag": C(lambda: (A(4, 4),)),
    "pick": C(lambda: (A(3, 5), IDX(3, n=5)), grad_args=(0,)),
    "take": C(lambda: (A(5, 3), IDX(4, n=5)), grad_args=(0,)),
    "one_hot": C(lambda: (IDX(5, n=4),), {"depth": 4}, grad=False),
    "gather_nd": C(lambda: (A(4, 5), IDX(2, 3, n=4)), grad_args=(0,)),
    "scatter_nd": C(lambda: (A(3,), IDX(1, 3, n=4)),
                    {"shape": (4,)}, grad_args=(0,)),
    "index_copy": C(lambda: (A(5, 3), jnp.asarray([1, 3]), A(2, 3)),
                    grad_args=(0, 2)),
    "index_array": C(lambda: (A(3, 4),), grad=False),
    "sequence_mask": C(
        lambda: (A(4, 3, 2), jnp.asarray([2.0, 4.0, 1.0])),
        {"use_sequence_length": True}, grad_args=(0,)),
    "sequence_reverse": C(
        lambda: (A(4, 3, 2), jnp.asarray([2.0, 4.0, 1.0])),
        {"use_sequence_length": True}, grad_args=(0,)),
    "sequence_last": C(
        lambda: (A(4, 3, 2), jnp.asarray([2.0, 4.0, 1.0])),
        {"use_sequence_length": True}, grad_args=(0,)),
    # -- sorting / indexing (non-diff paths) -----------------------------
    "argmax": C(lambda: (A(3, 4),), grad=False),
    "argmin": C(lambda: (A(3, 4),), grad=False),
    # ordering ops: values on a 0.25 grid are exactly representable in
    # bf16 and pairwise distinct, so rank order is dtype-independent
    # (random floats can collide after bf16 rounding and swap ranks)
    "argsort": C(lambda: (jnp.asarray(
        R.permutation(12).reshape(3, 4).astype("float32") * 0.25),),
        grad=False),
    "sort": C(lambda: (jnp.asarray(
        R.permutation(12).reshape(3, 4).astype("float32") * 0.25),),
        grad=False),
    "topk": C(lambda: (jnp.asarray(
        R.permutation(15).reshape(3, 5).astype("float32") * 0.25),),
        {"k": 2}, grad=False),
    "shape_array": C(lambda: (A(3, 4),), grad=False),
    "size_array": C(lambda: (A(3, 4),), grad=False),
    "einsum": C(lambda: (A(3, 4), A(4, 5)),
                {"equation": "ij,jk->ik"}),
    # -- spatial transform / legacy vision (round 4) ---------------------
    "LRN": C(lambda: (POS(2, 8, 6, 6),)),
    "GridGenerator": C(lambda: (A(2, 6, lo=-0.5, hi=0.5),),
                       {"transform_type": "affine",
                        "target_shape": (4, 5)}),
    # |theta| bounded so every sample point stays interior: the border's
    # zero-padding is a genuine derivative cliff (numeric != autodiff at
    # the boundary by construction)
    "SpatialTransformer": C(lambda: (A(2, 3, 6, 6),
                                     A(2, 6, lo=-0.25, hi=0.25)),
                            {"target_shape": (4, 4)}, bf16=False),
    "BilinearResize2D": C(lambda: (A(2, 3, 4, 4),),
                          {"height": 7, "width": 5}),
    "UpSampling": C(lambda: (A(2, 3, 4, 4),),
                    {"scale": 2, "sample_type": "nearest"}),
    "Crop": C(lambda: (A(2, 3, 6, 6),),
              {"h_w": (4, 4), "offset": (1, 1)}),
    "im2col": C(lambda: (A(2, 3, 5, 5),),
                {"kernel": (3, 3), "pad": (1, 1)}),
    "col2im": C(lambda: (A(2, 27, 25),),
                {"output_size": (5, 5), "kernel": (3, 3),
                 "pad": (1, 1)}),
    "deformable_convolution": C(
        lambda: (A(2, 4, 6, 6), A(2, 18, 6, 6, lo=-0.4, hi=0.4),
                 A(8, 4, 3, 3, lo=-0.5, hi=0.5)),
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 8,
         "no_bias": True}, bf16=False),
    "Correlation": C(lambda: (A(2, 4, 5, 5), A(2, 4, 5, 5)),
                     {"max_displacement": 1, "pad_size": 1}),
    "multibox_prior": C(lambda: (A(1, 3, 4, 4),),
                        {"sizes": (0.5, 0.25), "ratios": (1.0, 2.0)},
                        grad=False),
    "multibox_target": C(
        lambda: (_BOXES(8)[None], _MB_LABEL(), A(2, 4, 8, lo=0.0,
                                                 hi=1.0)),
        {"overlap_threshold": 0.3}, grad=False, bf16=False),
    "multibox_detection": C(
        lambda: (POS(2, 4, 8, lo=0.01, hi=1.0), A(2, 32, lo=-0.3,
                                                  hi=0.3),
                 _BOXES(8)[None]),
        {"nms_threshold": 0.5}, grad=False, bf16=False),
    "fft": C(lambda: (A(2, 8),), grad=False),
    "ifft": C(lambda: (A(2, 16),), grad=False),
    # -- bounding boxes --------------------------------------------------
    "box_iou": C(lambda: (_BOXES(3), _BOXES(2)), grad=False),
    # nms decisions are discontinuous in the overlap threshold: bf16
    # rounding can legitimately flip a borderline suppression
    "box_nms": C(lambda: (_NMS_DATA(),),
                 {"overlap_thresh": 0.5, "id_index": 0, "score_index": 1,
                  "coord_start": 2}, grad=False, bf16=False),
    # -- creation --------------------------------------------------------
    "zeros": C(lambda: (), {"shape": (2, 3)}, grad=False, bf16=False),
    "ones": C(lambda: (), {"shape": (2, 3)}, grad=False, bf16=False),
    "full": C(lambda: (), {"shape": (2, 3), "val": 1.5}, grad=False,
              bf16=False),
    "eye": C(lambda: (), {"N": 3}, grad=False, bf16=False),
    "arange": C(lambda: (), {"start": 0, "stop": 6}, grad=False,
                bf16=False),
    "linspace": C(lambda: (), {"start": 0.0, "stop": 1.0, "num": 5},
                  grad=False, bf16=False),
    "zeros_like": C(lambda: (A(2, 3),), grad=False),
    "ones_like": C(lambda: (A(2, 3),), grad=False),
    "full_like": C(lambda: (A(2, 3),), {"fill_value": 2.0}, grad=False),
    "arange_like": C(lambda: (A(2, 3),), grad=False),
    # -- matmul family ---------------------------------------------------
    "dot": C(lambda: (A(3, 4), A(4, 5))),
    "batch_dot": C(lambda: (A(2, 3, 4), A(2, 4, 5))),
    "linalg_gemm2": C(lambda: (A(3, 4), A(4, 5))),
    "khatri_rao": C(lambda: (A(2, 3), A(4, 3))),
    "batch_dot_attn": C(lambda: (A(2, 2, 4, 8), A(2, 2, 4, 8))),
    "attn_value": C(lambda: (A(2, 2, 4, 4), A(2, 2, 4, 8))),
    "causal_mask_fill": C(lambda: (A(2, 2, 4, 4),), grad=False),
    "masked_softmax": C(lambda: (A(2, 3, 4),)),
    "div_sqrt_dim": C(lambda: (A(3, 4),)),
    "interleaved_matmul_selfatt_qk": C(
        lambda: (A(5, 2, 24),), {"heads": 2}),
    "interleaved_matmul_selfatt_valatt": C(
        lambda: (A(5, 2, 24), A(4, 5, 5)), {"heads": 2}),
    "interleaved_matmul_encdec_qk": C(
        lambda: (A(5, 2, 8), A(5, 2, 16)), {"heads": 2}),
    "interleaved_matmul_encdec_valatt": C(
        lambda: (A(5, 2, 16), A(4, 5, 5)), {"heads": 2}),
    "rms_norm": C(lambda: (A(3, 8), POS(8))),
    "rope": C(lambda: (A(2, 2, 4, 8),)),
    "mrope": C(lambda: (A(2, 2, 4, 8), IDX(3, 4, n=9)),
               {"sections": (1, 2, 1)}, grad_args=(0,)),
    "gated_short_conv": C(lambda: (A(2, 5, 12), A(4, 3))),
    "smooth_l1_dup": None,  # placeholder removed below
    # -- nn ops ----------------------------------------------------------
    "FullyConnected": C(lambda: (A(3, 4), A(5, 4), A(5)),
                        {"num_hidden": 5}),
    "Convolution": C(lambda: (A(2, 3, 8, 8), A(4, 3, 3, 3), A(4)),
                     {"kernel": (3, 3), "num_filter": 4, "pad": (1, 1)},
                     rtol=2e-2, atol=2e-2),
    "Deconvolution": C(lambda: (A(2, 3, 6, 6), A(3, 4, 3, 3), A(4)),
                       {"kernel": (3, 3), "num_filter": 4},
                       rtol=2e-2, atol=2e-2),
    "Pooling": C(lambda: (A(2, 2, 6, 6),),
                 {"kernel": (2, 2), "pool_type": "avg", "stride": (2, 2)}),
    "Activation": C(lambda: (A(3, 4),), {"act_type": "tanh"}),
    "LeakyReLU": C(lambda: (POS(3, 4),), {"act_type": "leaky"}),
    "softmax": C(lambda: (A(3, 4),)),
    "log_softmax": C(lambda: (A(3, 4),)),
    "softmin": C(lambda: (A(3, 4),)),
    "softmax_cross_entropy": C(lambda: (A(3, 5), IDX(3, n=5)),
                               grad_args=(0,)),
    "linear_cross_entropy": C(lambda: (A(6, 4), A(5, 4), IDX(6, n=5)),
                              {"block_rows": 2}, grad_args=(0, 1)),
    "LayerNorm": C(lambda: (A(3, 8), POS(8), A(8))),
    "GroupNorm": C(lambda: (A(2, 4, 3, 3), POS(4), A(4)),
                   {"num_groups": 2}),
    "InstanceNorm": C(lambda: (A(2, 3, 4, 4), POS(3), A(3))),
    "L2Normalization": C(lambda: (POS(3, 4),)),
    "BatchNorm": C(
        lambda: (A(4, 3, 5, 5), POS(3), A(3), A(3, lo=-0.1, hi=0.1),
                 POS(3)),
        {"fix_gamma": False, "_training": True}, grad_args=(0, 1, 2),
        rtol=2e-2, atol=2e-2),
    "Embedding": C(lambda: (IDX(6, n=5), A(5, 4)), grad_args=(1,)),
    "boolean_mask": C(
        lambda: (A(5, 3), jnp.asarray([1, 0, 1, 1, 0], "int32")),
        grad=False, jit=False, bf16=False),  # data-dependent output shape
    "BilinearSampler": C(lambda: (A(2, 3, 5, 5), UNIT(2, 2, 4, 4)),
                         grad_args=(0,), rtol=3e-2, atol=3e-2),
    "quantize": C(lambda: (UNIT(3, 4), jnp.asarray(-1.0),
                           jnp.asarray(1.0)), grad=False, bf16=False),
    "dequantize": C(
        lambda: (jnp.asarray(R.randint(0, 255, (3, 4)).astype("uint8")),
                 jnp.asarray(-1.0), jnp.asarray(1.0)),
        grad=False, bf16=False),
})
del CASES["smooth_l1_dup"]

SKIP = {
    "_contrib_quantize_v2": "int8 quantization op (non-differentiable); "
                            "round-trip + model accuracy covered by "
                            "tests/test_quantization.py",
    "_contrib_dequantize_v2": "inverse of quantize_v2; covered by "
                              "tests/test_quantization.py",
    "_contrib_quantized_fully_connected": "int8 GEMM; quantized-vs-fp32 "
                                          "parity covered by "
                                          "tests/test_quantization.py",
    "_contrib_quantized_conv": "int8 conv; covered by "
                               "tests/test_quantization.py",
    "Dropout": "random: needs injected RNG key (_key); covered by "
               "tests/test_gluon.py dropout tests",
    "RNN": "stateful packed-weight fused op; covered by "
           "tests/test_gluon_rnn.py fused-vs-unfused parity",
    "ctc_loss": "optax lattice op; covered by gluon CTCLoss test; numeric "
                "grad over the lattice is O(T*V) slow",
    "flash_attention": "covered by tests/test_flash_attention.py "
                       "(fwd parity + gradients)",
    "indexed_attention": "a discrete selection (a row's k-th largest) "
                         "and Pallas kernels: numeric gradients cross "
                         "the selection's boundaries; value and every "
                         "input's gradient covered by tests/test_keye_vl.py "
                         "against the plain reference",
    "kda": "chunked recurrence with Pallas state kernels; covered by "
           "tests/test_kda.py (forward and every input's gradient "
           "against the step-by-step recurrence)",
    "kda_mixer": "the fused KDA mixer core around that recurrence; "
                 "covered by tests/test_kimi_linear.py (model against "
                 "its plain reference: loss, gradients, Adam steps)",
    "moe_expert_share": "discrete top-k routing: numeric gradients cross "
                        "routing decision boundaries by construction; "
                        "value, gradients and the shares' sum covered by "
                        "tests/test_moe_share.py",
    "paged_decode_attention": "ragged Pallas kernel; covered by "
                              "tests/test_paged_attention_pallas.py "
                              "(XLA-path parity matrix incl. int8)",
    "paged_prefill_attention": "chunked-prefill Pallas kernel; covered "
                               "by tests/test_prefill_attention_pallas"
                               ".py (XLA-path parity matrix incl. "
                               "int8/bf16 + engine integration)",
    "ring_attention": "needs a device mesh; covered by "
                      "tests/test_parallel.py exact-vs-dense test",
    "ROIAlign": "covered by detection-op usage; numeric grad unstable at "
                "bin boundaries by construction",
    "SoftmaxOutput": "custom_vjp carries the IMPLICIT loss gradient "
                     "(reference semantics): autodiff deliberately "
                     "diverges from the forward's numeric jacobian; "
                     "semantics tested in tests/test_module.py",
    "LinearRegressionOutput": "same implicit-loss-gradient contract",
    "MAERegressionOutput": "same implicit-loss-gradient contract",
    "LogisticRegressionOutput": "same implicit-loss-gradient contract",
    "_internal_getitem": "internal indexing helper for NDArray.__getitem__;"
                         " exercised by tests/test_ndarray.py slicing",
    "foreach": "takes a body callable (not arrays-only); value+gradient "
               "covered by tests/test_control_flow.py",
    "while_loop": "takes cond/func callables; value+gradient covered by "
                  "tests/test_control_flow.py",
    "cond": "takes branch callables; value+gradient covered by "
            "tests/test_control_flow.py",
    "Custom": "user-extension dispatch op (callable registry, host "
              "callback); covered by tests/test_custom_op.py",
    "switch_moe": "discrete top-1 routing: numeric gradients cross "
                  "routing decision boundaries by construction; value + "
                  "gradient + ep-sharding covered by tests/test_moe.py",
    "MakeLoss": "custom_vjp carries the 'output IS the loss' gradient "
                "contract (grad_scale, incoming cotangent ignored): "
                "autodiff deliberately diverges from the numeric "
                "jacobian; semantics in tests/test_legacy_vision_ops.py",
    "_internal_tree_verify_attn": "tree-verify attention over a pooled "
                                  "slot cache with per-lane ancestor "
                                  "bitmasks; bit-exact stream parity + "
                                  "kernel parity covered by tests/"
                                  "test_tree_speculative.py and tests/"
                                  "test_paged_attention_pallas.py",
    "_internal_cache_permute_span": "side-branch cache fix-up (permute "
                                    "accepted lanes into place) for the "
                                    "slot engine; covered by tests/"
                                    "test_tree_speculative.py bit-exact "
                                    "parity + fixup program counts",
    "_internal_cache_permute_span_q8": "int8 variant of the slot fix-up; "
                                       "same coverage (cache_dtype grid "
                                       "in tests/test_tree_speculative"
                                       ".py)",
    "_paged_cache_permute_span": "side-branch cache fix-up for the paged "
                                 "engine; covered by tests/"
                                 "test_tree_speculative.py paged parity "
                                 "+ fixup program counts",
    "_paged_cache_permute_span_q8": "int8 variant of the paged fix-up; "
                                    "same coverage (cache_dtype grid in "
                                    "tests/test_tree_speculative.py)",
}


# ---------------------------------------------------------------------------
# round-5 tail (VERDICT r4 item 2): optimizer update ops, random sampling
# ops, indexing/special-function tail, contrib tail

def _KEY():
    return jax.random.key(7)


_OPT_W = lambda: (A(3, 4), A(3, 4))  # noqa: E731 — (weight, grad)

CASES.update({
    # special functions / elementwise tail
    "digamma": C(lambda: (POS(3, 4, lo=1.0, hi=3.0),), rtol=5e-2),
    "degrees": C(lambda: (A(3, 4),)),
    "radians": C(lambda: (A(3, 4),)),
    "nanprod": C(lambda: (POS(2, 3),), {"axis": 1}),
    # indexing tail
    "batch_take": C(lambda: (A(4, 5), IDX(4, n=5)), grad_args=(0,)),
    "ravel_multi_index": C(
        lambda: (jnp.asarray(R.randint(0, 3, (2, 6)).astype("int32")),),
        {"shape": (3, 4)}, grad=False, bf16=False),
    "unravel_index": C(
        lambda: (jnp.asarray(R.randint(0, 12, (6,)).astype("int32")),),
        {"shape": (3, 4)}, grad=False, bf16=False),
    "argmax_channel": C(lambda: (A(3, 5),), grad=False),
    "moments": C(lambda: (A(3, 4),), {"axes": (0,)}),
    "choose_element_0index": C(lambda: (A(4, 5), IDX(4, n=5)), grad=False),
    "fill_element_0index": C(lambda: (A(4, 5), A(4), IDX(4, n=5)),
                             grad=False),
    # nn tail
    "ROIPooling": C(
        lambda: (A(1, 2, 8, 8),
                 jnp.asarray([[0, 0, 0, 5, 5], [0, 1, 2, 7, 6]],
                             jnp.float32)),
        {"pooled_size": (2, 2), "spatial_scale": 1.0}, grad_args=(0,)),
    "rnn_param_concat": C(lambda: (A(6), A(4)), {"dim": 0}),
    # contrib tail
    "AdaptiveAvgPooling2D": C(lambda: (A(2, 3, 6, 6),),
                              {"output_size": (2, 2)}),
    "bipartite_matching": C(lambda: (POS(4, 5),),
                            {"threshold": 0.6, "topk": 3}, grad=False,
                            bf16=False),  # discrete argmax: bf16
                                          # near-ties flip indices
    "_internal_cache_write": C(
        lambda: (A(2, 3, 8, 4), A(2, 3, 1, 4)), {"pos": 5}, grad=False),
    "_internal_cache_write_rows": C(
        lambda: (A(2, 3, 8, 4), A(2, 3, 1, 4)),
        {"pos": jnp.asarray([5, 2])}, grad=False),
    "_internal_cache_write_slot": C(
        lambda: (A(2, 3, 8, 4), A(1, 3, 4, 4)), {"slot": 1, "pos": 2},
        grad=False),
    # speculative-verify window writes (ISSUE 8): per-row W-token spans
    # with valid_len masking (invalid lanes drop / hit the null page)
    "_internal_cache_write_span": C(
        lambda: (A(2, 3, 8, 4), A(2, 3, 4, 4)),
        {"pos": jnp.asarray([2, 5]),
         "valid_len": jnp.asarray([4, 2])}, grad=False),
    "_paged_cache_write_span": C(
        lambda: (A(5, 3, 4, 2), A(2, 3, 4, 2), IDX(2, 3, n=5),
                 jnp.asarray([3, 2]), jnp.asarray([4, 2])), grad=False),
    # block-paged cache family (PagedContinuousBatchingEngine): pool
    # (pages=5, KV=3, block=4, D=2); tables are int32 page indices
    "_paged_cache_gather": C(
        lambda: (A(5, 3, 4, 2), IDX(2, 3, n=5)), grad=False),
    "_paged_cache_write": C(
        lambda: (A(5, 3, 4, 2), A(1, 3, 6, 2), IDX(3, n=5)),
        {"start_pos": 2}, grad=False),
    "_paged_cache_write_rows": C(
        lambda: (A(5, 3, 4, 2), A(2, 3, 1, 2), IDX(2, 3, n=5),
                 jnp.asarray([5, 2])), grad=False),
    "_paged_block_copy": C(
        lambda: (A(5, 3, 4, 2),), {"src": 1, "dst": 3}, grad=False),
    # int8 KV-cache family (ISSUE 10): quantized twins of the cache
    # writes — payload int8 + per-head-per-position f32 scales; the
    # bf16 leg compares only the float outputs (scales/dequant), the
    # int8 payloads are exact by construction
    "_internal_cache_dequant": C(
        lambda: (I8(2, 3, 8, 4), SCL(2, 3, 8)), grad=False),
    "_internal_cache_write_q8": C(
        lambda: (I8(2, 3, 8, 4), SCL(2, 3, 8), A(2, 3, 2, 4)),
        {"pos": 5}, grad=False, bf16=False),   # bf16 rounding can move
    #                                            a value one int8 level
    "_internal_cache_write_rows_q8": C(
        lambda: (I8(2, 3, 8, 4), SCL(2, 3, 8), A(2, 3, 1, 4),
                 jnp.asarray([5, 2])), grad=False, bf16=False),
    "_internal_cache_write_span_q8": C(
        lambda: (I8(2, 3, 8, 4), SCL(2, 3, 8), A(2, 3, 4, 4),
                 jnp.asarray([2, 4]), jnp.asarray([4, 2])),
        grad=False, bf16=False),
    "_internal_cache_write_slot_q8": C(
        lambda: (I8(2, 3, 8, 4), SCL(2, 3, 8), I8(1, 3, 4, 4),
                 SCL(1, 3, 4)), {"slot": 1, "pos": 2}, grad=False),
    "_paged_cache_gather_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), IDX(2, 3, n=5)),
        grad=False),
    "_paged_cache_write_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), A(1, 3, 6, 2),
                 IDX(3, n=5)), {"start_pos": 2}, grad=False,
        bf16=False),
    "_paged_cache_write_rows_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), A(2, 3, 1, 2),
                 IDX(2, 3, n=5), jnp.asarray([5, 2])), grad=False,
        bf16=False),
    "_paged_cache_write_span_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), A(2, 3, 4, 2),
                 IDX(2, 3, n=5), jnp.asarray([3, 2]),
                 jnp.asarray([4, 2])), grad=False, bf16=False),
    # weight-only packed matmuls (contrib.quantization): dequant fused
    # into the contraction; scales kept small so outputs stay O(1)
    "wq_matmul_i8": C(
        lambda: (A(3, 4), I8(5, 4), SCL(5)), grad=False),
    "wq_matmul_i4": C(
        lambda: (A(3, 4), I8(5, 2), SCL(5, 2)),
        {"group_size": 2, "in_units": 4}, grad=False),
    "wq_matmul_i8_q8": C(
        lambda: (A(3, 4), I8(6, 4), SCL(6)),
        {"head_dim": 2}, grad=False, bf16=False),
    # pre-quantized paged landings (fused int8 epilogue, ISSUE 16):
    # rows/scales arrive already int8 so the write is a pure scatter
    "_paged_cache_write_rows_pre_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), I8(2, 3, 1, 2),
                 SCL(2, 3, 1), IDX(2, 3, n=5), jnp.asarray([5, 2])),
        grad=False, bf16=False),
    "_paged_cache_write_span_pre_q8": C(
        lambda: (I8(5, 3, 4, 2), SCL(5, 3, 4), I8(2, 3, 4, 2),
                 SCL(2, 3, 4), IDX(2, 3, n=5), jnp.asarray([3, 2]),
                 jnp.asarray([4, 2])), grad=False, bf16=False),
    "_npi_einsum": C(lambda: (A(2, 3), A(3, 4)),
                     {"subscripts": "ij,jk->ik"}),
    "gradientmultiplier": C(lambda: (A(3, 4),), {"scalar": 1.0}),
    "allclose": C(lambda: (A(3, 4), A(3, 4)), grad=False),
    "quadratic": C(lambda: (A(3, 4),), {"a": 0.5, "b": -1.0, "c": 2.0}),
    # AMP ops
    "amp_cast": C(lambda: (A(3, 4),), {"dtype": "float32"}, grad=False),
    "amp_multicast": C(lambda: (A(3, 4), A(3, 4)), grad=False),
    "all_finite": C(lambda: (A(3, 4),), grad=False),
    "multi_all_finite": C(lambda: (A(3, 4), A(2, 2)), grad=False),
    # optimizer update ops (all non-differentiable by contract)
    "sgd_update": C(_OPT_W, {"lr": 0.1, "wd": 0.01}, grad=False),
    "sgd_mom_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4)),
                        {"lr": 0.1, "momentum": 0.9}, grad=False),
    "mp_sgd_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4)),
                       {"lr": 0.1, "wd": 0.01}, grad=False, bf16=False),
    "mp_sgd_mom_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4), A(3, 4)),
                           {"lr": 0.1, "momentum": 0.9}, grad=False,
                           bf16=False),
    "multi_sgd_update": C(lambda: (A(3, 4), A(3, 4), A(2, 2), A(2, 2)),
                          {"lrs": (0.1, 0.2), "wds": (0.0, 0.01),
                           "num_weights": 2}, grad=False),
    "multi_sgd_mom_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(2, 2), A(2, 2), A(2, 2)),
        {"lrs": (0.1, 0.2), "wds": (0.0, 0.01), "momentum": 0.9,
         "num_weights": 2}, grad=False),
    "multi_mp_sgd_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(2, 2), A(2, 2), A(2, 2)),
        {"lrs": (0.1, 0.2), "wds": (0.0, 0.01), "num_weights": 2},
        grad=False, bf16=False),
    "multi_mp_sgd_mom_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(3, 4),
                 A(2, 2), A(2, 2), A(2, 2), A(2, 2)),
        {"lrs": (0.1, 0.2), "wds": (0.0, 0.01), "momentum": 0.9,
         "num_weights": 2}, grad=False, bf16=False),
    "preloaded_multi_sgd_update": C(
        lambda: (A(3, 4), A(3, 4), A(2, 2), A(2, 2),
                 jnp.asarray([0.1, 0.2]), jnp.asarray([0.0, 0.01])),
        {"num_weights": 2}, grad=False),
    "preloaded_multi_sgd_mom_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(2, 2), A(2, 2), A(2, 2),
                 jnp.asarray([0.1, 0.2]), jnp.asarray([0.0, 0.01])),
        {"momentum": 0.9, "num_weights": 2}, grad=False),
    "preloaded_multi_mp_sgd_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(2, 2), A(2, 2), A(2, 2),
                 jnp.asarray([0.1, 0.2]), jnp.asarray([0.0, 0.01])),
        {"num_weights": 2}, grad=False, bf16=False),
    "preloaded_multi_mp_sgd_mom_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), A(3, 4),
                 A(2, 2), A(2, 2), A(2, 2), A(2, 2),
                 jnp.asarray([0.1, 0.2]), jnp.asarray([0.0, 0.01])),
        {"momentum": 0.9, "num_weights": 2}, grad=False, bf16=False),
    "nag_mom_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4)),
                        {"lr": 0.1, "momentum": 0.9}, grad=False),
    "mp_nag_mom_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4), A(3, 4)),
                           {"lr": 0.1, "momentum": 0.9}, grad=False,
                           bf16=False),
    "adam_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4)),
                     {"lr": 0.01}, grad=False),
    "adamw_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4),
                 jnp.ones(())),
        {"lr": 0.01, "wd": 0.01, "eta": 1.0}, grad=False),
    "mp_adamw_update": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4), A(3, 4),
                 jnp.ones(())),
        {"lr": 0.01, "wd": 0.01, "eta": 1.0}, grad=False, bf16=False),
    "ftrl_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4)),
                     {"lr": 0.1}, grad=False),
    "rmsprop_update": C(lambda: (A(3, 4), A(3, 4), POS(3, 4)),
                        {"lr": 0.01}, grad=False),
    "rmspropalex_update": C(
        lambda: (A(3, 4), A(3, 4), POS(3, 4, lo=4.5, hi=6.0), UNIT(3, 4),
                 A(3, 4)),
        {"lr": 0.01}, grad=False),
    "signsgd_update": C(_OPT_W, {"lr": 0.01}, grad=False),
    "signum_update": C(lambda: (A(3, 4), A(3, 4), A(3, 4)),
                       {"lr": 0.01, "momentum": 0.9}, grad=False),
    "lamb_update_phase1": C(lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4)),
                            {"t": 2}, grad=False),
    "lamb_update_phase2": C(
        lambda: (A(3, 4), A(3, 4), jnp.asarray(2.0), jnp.asarray(1.5)),
        {"lr": 0.01}, grad=False),
    "mp_lamb_update_phase1": C(
        lambda: (A(3, 4), A(3, 4), A(3, 4), POS(3, 4), A(3, 4)),
        {"t": 2}, grad=False, bf16=False),
    "mp_lamb_update_phase2": C(
        lambda: (A(3, 4), A(3, 4), jnp.asarray(2.0), jnp.asarray(1.5),
                 A(3, 4)),
        {"lr": 0.01}, grad=False, bf16=False),
    "multi_sum_sq": C(lambda: (A(3, 4), A(2, 2)), {"num_arrays": 2},
                      grad=False),
    "multi_lars": C(lambda: (POS(4), POS(4), POS(4), POS(4)),
                    {"eta": 0.001}, grad=False),
    # random draws: explicit _key makes eager-vs-jit deterministic
    "random_uniform": C(lambda: (), {"low": -1.0, "high": 1.0,
                                     "shape": (3, 4), "_key": _KEY()},
                        grad=False, bf16=False),
    "random_normal": C(lambda: (), {"loc": 1.0, "scale": 2.0,
                                    "shape": (3, 4), "_key": _KEY()},
                       grad=False, bf16=False),
    "random_gamma": C(lambda: (), {"alpha": 2.0, "beta": 1.5,
                                   "shape": (3, 4), "_key": _KEY()},
                      grad=False, bf16=False),
    "random_exponential": C(lambda: (), {"lam": 2.0, "shape": (3, 4),
                                         "_key": _KEY()},
                            grad=False, bf16=False),
    "random_poisson": C(lambda: (), {"lam": 3.0, "shape": (3, 4),
                                     "_key": _KEY()},
                        grad=False, bf16=False),
    "random_negative_binomial": C(
        lambda: (), {"k": 3, "p": 0.5, "shape": (3, 4), "_key": _KEY()},
        grad=False, bf16=False),
    "random_generalized_negative_binomial": C(
        lambda: (), {"mu": 2.0, "alpha": 0.5, "shape": (3, 4),
                     "_key": _KEY()}, grad=False, bf16=False),
    "random_randint": C(lambda: (), {"low": 0, "high": 10,
                                     "shape": (3, 4), "_key": _KEY()},
                        grad=False, bf16=False),
    "random_uniform_like": C(lambda: (A(3, 4),), {"_key": _KEY()},
                             grad=False, bf16=False),
    "random_normal_like": C(lambda: (A(3, 4),), {"_key": _KEY()},
                            grad=False, bf16=False),
    "random_gamma_like": C(lambda: (A(3, 4),), {"alpha": 2.0,
                                                "_key": _KEY()},
                           grad=False, bf16=False),
    "random_exponential_like": C(lambda: (A(3, 4),), {"_key": _KEY()},
                                 grad=False, bf16=False),
    "random_poisson_like": C(lambda: (A(3, 4),), {"lam": 3.0,
                                                  "_key": _KEY()},
                             grad=False, bf16=False),
    "random_negative_binomial_like": C(
        lambda: (A(3, 4),), {"k": 3, "p": 0.5, "_key": _KEY()},
        grad=False, bf16=False),
    "random_generalized_negative_binomial_like": C(
        lambda: (A(3, 4),), {"mu": 2.0, "alpha": 0.5, "_key": _KEY()},
        grad=False, bf16=False),
    "sample_uniform": C(lambda: (POS(3, lo=0.1, hi=0.4), POS(3, lo=1.0)),
                        {"shape": (4,), "_key": _KEY()}, grad=False,
                        bf16=False),
    "sample_normal": C(lambda: (A(3), POS(3)),
                       {"shape": (4,), "_key": _KEY()}, grad=False,
                       bf16=False),
    "sample_gamma": C(lambda: (POS(3), POS(3)),
                      {"shape": (4,), "_key": _KEY()}, grad=False,
                      bf16=False),
    "sample_exponential": C(lambda: (POS(3),),
                            {"shape": (4,), "_key": _KEY()}, grad=False,
                            bf16=False),
    "sample_poisson": C(lambda: (POS(3),),
                        {"shape": (4,), "_key": _KEY()}, grad=False,
                        bf16=False),
    "sample_negative_binomial": C(
        lambda: (POS(3, lo=1.0, hi=4.0), UNIT(3)),
        {"shape": (4,), "_key": _KEY()}, grad=False, bf16=False),
    "sample_generalized_negative_binomial": C(
        lambda: (POS(3), POS(3, lo=0.3, hi=0.8)),
        {"shape": (4,), "_key": _KEY()}, grad=False, bf16=False),
    "_sample_multinomial": C(
        lambda: (jnp.asarray([[0.2, 0.3, 0.5], [0.6, 0.2, 0.2]],
                             jnp.float32),),
        {"shape": (4,), "_key": _KEY()}, grad=False, bf16=False),
    "shuffle": C(lambda: (A(5, 3),), {"_key": _KEY()}, grad=False,
                 bf16=False),
})

SKIP.update({
    "SVMOutput": "custom_vjp carries the IMPLICIT hinge-loss gradient "
                 "(reference svm_output-inl.h contract): autodiff "
                 "deliberately diverges from the forward's numeric "
                 "jacobian; semantics pinned in tests/test_op_tail.py",
    "IdentityAttachKLSparseReg": "custom_vjp ADDS the KL sparsity "
                                 "penalty gradient to the cotangent "
                                 "(implicit-regularizer contract); "
                                 "semantics pinned in "
                                 "tests/test_op_tail.py",
})


def _unique_ops():
    seen = {}
    for spec in base._OP_REGISTRY.values():
        seen.setdefault(id(spec), spec.name)
    return sorted(set(seen.values()))


def test_registry_fully_covered():
    missing = [n for n in _unique_ops() if n not in CASES and n not in SKIP]
    assert not missing, f"ops with no sweep case or skip reason: {missing}"
    stale = [n for n in list(CASES) + list(SKIP)
             if n not in base._OP_REGISTRY]
    assert not stale, f"sweep table names unknown ops: {stale}"


def _call(name, args, kwargs):
    out = base.get_op(name).fn(*args, **kwargs)
    return out


def _flatsum(out):
    leaves = jax.tree_util.tree_leaves(out)
    return sum(jnp.sum(l.astype(jnp.float32)) for l in leaves
               if jnp.issubdtype(l.dtype, jnp.inexact))


def _case_args(name, case):
    """Build a case's inputs with a per-op-seeded stream: input values
    depend only on the op name (stable crc32 — python hash() is
    per-process randomized), never on how many cases ran before
    (table-order shifts repeatedly produced accidental near-ties)."""
    import zlib

    R.seed(zlib.crc32(name.encode()) % (2**31))
    return case.args()


@pytest.mark.parametrize("name", sorted(CASES))
def test_op_eager_vs_jit(name):
    case = CASES[name]
    if not case.jit:
        pytest.skip("data-dependent output shape: eager-only op")
    args = _case_args(name, case)
    eager = _call(name, args, case.kwargs)
    jitted = jax.jit(functools.partial(base.get_op(name).fn, **case.kwargs))(
        *args)
    for e, j in zip(jax.tree_util.tree_leaves(eager),
                    jax.tree_util.tree_leaves(jitted)):
        onp.testing.assert_allclose(onp.asarray(e), onp.asarray(j),
                                    rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", sorted(CASES))
def test_op_bf16_consistency(name):
    case = CASES[name]
    if not case.bf16:
        pytest.skip("integer/creation op: no float input to downcast")
    args = _case_args(name, case)
    if not any(a.dtype == jnp.float32 for a in args):
        pytest.skip("no fp32 array input")
    f32 = _call(name, args, case.kwargs)
    bargs = tuple(a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
                  for a in args)
    b16 = _call(name, bargs, case.kwargs)
    for e, j in zip(jax.tree_util.tree_leaves(f32),
                    jax.tree_util.tree_leaves(b16)):
        if not jnp.issubdtype(e.dtype, jnp.inexact):
            continue
        onp.testing.assert_allclose(
            onp.asarray(e, dtype="float32"), onp.asarray(j, "float32"),
            rtol=0.1, atol=0.1)


@pytest.mark.parametrize(
    "name", sorted(n for n, c in CASES.items() if c.grad))
def test_op_numeric_gradient(name):
    """Central-difference jacobian-vector action vs jax.grad."""
    case = CASES[name]
    args = _case_args(name, case)
    widx = case.grad_args
    if widx is None:
        widx = tuple(i for i, a in enumerate(args)
                     if jnp.issubdtype(a.dtype, jnp.inexact))
    assert widx, f"{name}: grad case with no float args"
    fn = base.get_op(name).fn

    def scalar_of(*wargs):
        full = list(args)
        for i, w in zip(widx, wargs):
            full[i] = w
        return _flatsum(fn(*full, **case.kwargs))

    wargs = tuple(args[i] for i in widx)
    grads = jax.grad(scalar_of, argnums=tuple(range(len(wargs))))(*wargs)

    eps = 1e-2
    for gi, (w, g) in enumerate(zip(wargs, grads)):
        # probe a handful of coordinates (full FD sweep is O(n) evals)
        flat = onp.asarray(w, dtype="float64").ravel()
        coords = R.choice(flat.size, size=min(6, flat.size), replace=False)
        for c in coords:
            def at(val):
                f = flat.copy()
                f[c] = val
                ws = list(wargs)
                ws[gi] = jnp.asarray(f.astype("float32")).reshape(w.shape)
                return float(scalar_of(*ws))

            fd = (at(flat[c] + eps) - at(flat[c] - eps)) / (2 * eps)
            an = float(onp.asarray(g).ravel()[c])
            onp.testing.assert_allclose(
                an, fd, rtol=case.rtol, atol=case.atol,
                err_msg=f"{name}: grad arg {gi} coord {c}")
