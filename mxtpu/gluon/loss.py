"""Losses (parity: python/mxnet/gluon/loss.py).

Every loss is a HybridBlock over (pred, label[, sample_weight]) with
batch_axis/weight semantics identical to the reference.  CTC uses optax's
XLA-native ctc_loss (the reference binds warp-ctc / cuDNN CTC); blank label
index 0 matching the reference op's default blank_label='first'.
"""

from __future__ import annotations

import numpy as onp

from ..base import MXTPUError
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss",
           "SigmoidBinaryCrossEntropyLoss", "SigmoidBCELoss",
           "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "MultiTokenLoss", "IndexedAttentionLoss", "KLDivLoss", "CTCLoss",
           "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "TripletLoss",
           "PoissonNLLLoss", "CosineEmbeddingLoss"]


def _apply_weighting(F, loss, weight=None, sample_weight=None):
    """Parity: _apply_weighting."""
    if sample_weight is not None:
        loss = F.broadcast_mul(loss, sample_weight)
    if weight is not None:
        assert isinstance(weight, (float, int)), "weight must be a number"
        loss = loss * weight
    return loss


def _reshape_like(F, x, y):
    return x.reshape_like(y)


class Loss(HybridBlock):
    """Base loss (parity: gluon.loss.Loss)."""

    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return (f"{self.__class__.__name__}(batch_axis={self._batch_axis}, "
                f"w={self._weight})")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


class L2Loss(Loss):
    """0.5 * w * (pred - label)^2 (parity: L2Loss)."""

    def __init__(self, weight=1., batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.square(label - pred)
        loss = _apply_weighting(F, loss, self._weight / 2, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class L1Loss(Loss):
    """w * |pred - label| (parity: L1Loss)."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.abs(label - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """BCE with optional logits input (parity: SigmoidBCELoss); uses the
    log-sum-exp stable form when from_sigmoid=False, incl. pos_weight."""

    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None,
                       pos_weight=None):
        label = _reshape_like(F, label, pred)
        if not self._from_sigmoid:
            if pos_weight is None:
                # stable: max(x,0) - x*z + log(1+exp(-|x|))
                loss = F.relu(pred) - pred * label + F.Activation(
                    -F.abs(pred), act_type="softrelu")
            else:
                log_weight = 1 + F.broadcast_mul(pos_weight - 1, label)
                loss = (pred - pred * label + log_weight
                        * (F.Activation(-F.abs(pred), act_type="softrelu")
                           + F.relu(-pred)))
        else:
            eps = 1e-12
            if pos_weight is None:
                loss = -(F.log(pred + eps) * label
                         + F.log(1. - pred + eps) * (1. - label))
            else:
                loss = -(F.broadcast_mul(F.log(pred + eps) * label, pos_weight)
                         + F.log(1. - pred + eps) * (1. - label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Softmax + CE, fused log-softmax form (parity: SoftmaxCELoss —
    XLA fuses this chain into the reference's fused softmax-CE kernel)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        if self._sparse_label:
            loss = -F.pick(pred, label, axis=self._axis, keepdims=True)
        else:
            label = _reshape_like(F, label, pred)
            loss = -F.sum(pred * label, axis=self._axis, keepdims=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


def _head_cross_entropy(F, head, out, label):
    """Per-position cross-entropy of ``out``: logits when ``head`` is
    None, else the input of ``head`` (a ``Dense`` block, no bias), taken
    through it in blocks of rows (``F.linear_cross_entropy``)."""
    if head is None:
        return -F.pick(F.log_softmax(out, axis=-1), label, axis=-1)
    return F.linear_cross_entropy(out, head.weight.data(out.context), label)


class MultiTokenLoss(Loss):
    """Next-token cross-entropy plus ``mtp_weight`` times that of a
    multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437 section
    2.2), for a model that returns (logits, the module's logits), both
    (B, T, V), with labels (B, T) that are each position's next token.
    The module at position i predicts the token after the next, which is
    label i + 1; its last position has no target and is left out.  Each
    term is a mean over its own positions: T, and T - 1.

    With ``head`` — the ``Dense`` block (no bias) both sets of logits
    come from — the model's outputs are the head's two inputs instead,
    (B, T, C) each, and each cross-entropy is taken through the head in
    blocks of rows (``F.linear_cross_entropy``): neither set of logits is
    ever whole.  The head's parameters stay the model's.

    ``record``, if given, is called in every pass with (positions that
    entered the module's term, the main term's sum, the module's sum),
    all on the device (a model's counters: ``PredictionModule.count``).
    """

    #: SPMDTrainer hands the model's whole output to such a loss
    accepts_full_output = True

    def __init__(self, mtp_weight=0.3, record=None, head=None,
                 batch_axis=0, **kwargs):
        super().__init__(mtp_weight, batch_axis, **kwargs)
        self._record, self._head = record, head

    def forward(self, outputs, label):
        main, mtp = outputs
        return super().forward(main, mtp, label)

    def _cross_entropy(self, F, out, label):
        return _head_cross_entropy(F, self._head, out, label)

    def hybrid_forward(self, F, main_out, mtp_out, label):
        main = self._cross_entropy(F, main_out, label)
        # the labels one position on; the module's output is not sliced
        # (a copy of (B, T - 1, V) logits): its last position, whose
        # target here is a filler, leaves with the per-position losses
        ahead = F.concat(label[:, 1:], label[:, :1], dim=1)
        mtp = self._cross_entropy(F, mtp_out, ahead)[:, :-1]
        if self._record is not None:
            count = mtp.shape[0] * mtp.shape[1]
            self._record(F.full((1,), count, dtype="int32"), F.sum(main),
                         F.sum(mtp))
        return F.mean(main, axis=self._batch_axis, exclude=True) \
            + self._weight * F.mean(mtp, axis=self._batch_axis, exclude=True)


class IndexedAttentionLoss(Loss):
    """Next-token cross-entropy plus ``index_weight`` times the loss of
    the attention layers' indexers (DeepSeek Sparse Attention's sparse
    stage), for a model that returns (logits (B, T, V), the indexers'
    loss (B,) summed over its layers) with labels (B, T).  The second
    term reaches the indexers' parameters alone and the first everything
    else: the model sees to that (``ops/dsa.py``), the loss only adds.

    With ``head`` — the ``Dense`` block (no bias) the logits come from —
    the model's first output is the head's input (B, T, C) and the
    cross-entropy is taken through the head in blocks of rows
    (``F.linear_cross_entropy``).
    """

    #: SPMDTrainer hands the model's whole output to such a loss
    accepts_full_output = True

    def __init__(self, index_weight=1.0, head=None, batch_axis=0, **kwargs):
        super().__init__(index_weight, batch_axis, **kwargs)
        self._head = head

    def forward(self, outputs, label):
        main, index_loss = outputs
        return super().forward(main, index_loss, label)

    def hybrid_forward(self, F, out, index_loss, label):
        ce = _head_cross_entropy(F, self._head, out, label)
        return F.mean(ce, axis=self._batch_axis, exclude=True) \
            + self._weight * index_loss


class KLDivLoss(Loss):
    """KL divergence (parity: KLDivLoss)."""

    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        loss = label * (F.log(label + 1e-12) - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class CTCLoss(Loss):
    """Connectionist temporal classification loss (parity: gluon.loss.CTCLoss
    over src/operator/nn/ctc_loss.cc).  Computed with optax.ctc_loss
    (XLA-native) — blank index 0, matching the reference op default
    blank_label='first'."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None,
                 **kwargs):
        assert layout in ("NTC", "TNC"), \
            f"Only 'NTC' and 'TNC' layouts for pred are supported. Got: {layout}"
        assert label_layout in ("NT", "TN"), \
            f"Only 'NT' and 'TN' layouts for label are supported. Got: {label_layout}"
        self._layout = layout
        self._label_layout = label_layout
        batch_axis = label_layout.find("N")
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, pred_lengths=None,
                       label_lengths=None, sample_weight=None):
        if self._layout == "TNC":
            pred = F.swapaxes(pred, 0, 1)  # → (B, T, V)
        if self._batch_axis == 1:
            label = F.swapaxes(label, 0, 1)
        loss = F.ctc_loss(pred, label, pred_lengths, label_lengths,
                          use_data_lengths=pred_lengths is not None,
                          use_label_lengths=label_lengths is not None,
                          _layout="NTC")
        return _apply_weighting(F, loss, self._weight, sample_weight)


class HuberLoss(Loss):
    """Smooth L1 with threshold rho (parity: HuberLoss)."""

    def __init__(self, rho=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.abs(label - pred)
        loss = F.where(loss > self._rho,
                       loss - 0.5 * self._rho,
                       (0.5 / self._rho) * F.square(loss))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class HingeLoss(Loss):
    """max(0, margin - pred*label) (parity: HingeLoss)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.relu(self._margin - pred * label)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SquaredHingeLoss(Loss):
    """max(0, margin - pred*label)^2 (parity: SquaredHingeLoss)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.square(F.relu(self._margin - pred * label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class LogisticLoss(Loss):
    """log(1 + exp(-pred*label)) (parity: LogisticLoss)."""

    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format
        if self._label_format not in ["signed", "binary"]:
            raise ValueError(
                f"label_format can only be signed or binary, recieved "
                f"{label_format}")

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = F.relu(pred) - pred * label + F.Activation(
            -F.abs(pred), act_type="softrelu")
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class TripletLoss(Loss):
    """max(0, |p-pos|^2 - |p-neg|^2 + margin) (parity: TripletLoss)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(F, positive, pred)
        negative = _reshape_like(F, negative, pred)
        loss = (F.sum(F.square(positive - pred), axis=self._batch_axis,
                      exclude=True)
                - F.sum(F.square(negative - pred), axis=self._batch_axis,
                        exclude=True))
        loss = F.relu(loss + self._margin)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class PoissonNLLLoss(Loss):
    """Poisson negative log likelihood (parity: PoissonNLLLoss)."""

    def __init__(self, weight=None, from_logits=True, batch_axis=0,
                 compute_full=False, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def hybrid_forward(self, F, pred, target, sample_weight=None,
                       epsilon=1e-08):
        target = _reshape_like(F, target, pred)
        if self._from_logits:
            loss = F.exp(pred) - target * pred
        else:
            loss = pred - target * F.log(pred + epsilon)
        if self._compute_full:
            stirling_factor = (target * F.log(target)
                               - target + 0.5 * F.log(2 * target * 3.1415926))
            stirling_factor = stirling_factor * (target > 1)
            loss = loss + stirling_factor
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss)


class CosineEmbeddingLoss(Loss):
    """Cosine-distance loss between paired vectors (parity:
    CosineEmbeddingLoss)."""

    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, input1, input2, label, sample_weight=None):
        input1 = _reshape_like(F, input1, input2)
        cos_dist = self._cosine_similarity(F, input1, input2)
        label = label.reshape((-1, 1))
        loss = F.where(label == 1,
                       1.0 - cos_dist,
                       F.relu(cos_dist - self._margin))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)

    def _cosine_similarity(self, F, x, y, axis=-1):
        x_norm = F.norm(x, axis=axis).reshape((-1, 1))
        y_norm = F.norm(y, axis=axis).reshape((-1, 1))
        x_dot_y = F.sum(x * y, axis=axis).reshape((-1, 1))
        eps_arr = 1e-12
        return x_dot_y / F.broadcast_maximum(
            x_norm * y_norm, F.ones_like(x_norm) * eps_arr)
