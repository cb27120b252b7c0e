"""Pallas TPU kernels (the hot-op escape hatch; parity target:
src/operator/contrib/transformer.cc fused attention + fusion/fused_op RTC —
where the reference hand-wrote CUDA, mxtpu hand-writes Pallas)."""

from . import counters
from .flash_attention import flash_attention
from .paged_attention import paged_decode_attention
from .prefill_attention import paged_prefill_attention
from .kda import kda, kda_mixer
from .indexer import indexer_probs, indexer_scores, indexer_threshold
