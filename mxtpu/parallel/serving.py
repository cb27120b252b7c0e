"""Continuous-batching decode engine with slot-based KV cache reuse.

``ShardedDecoder.generate`` is strictly run-to-completion: one fixed
batch allocates a fresh KV cache, every sequence decodes to
max_new_tokens, and only then does new work get in — a single long
request pins the whole batch and short requests pay worst-case latency.
This module adds the standard serving fix (Orca iteration-level
scheduling + vLLM-style cache-slot reuse, adapted to the static-shape
discipline TPUs want):

- ONE persistent pool of ``num_slots`` cache rows over one on-mesh
  sharded KV cache (allocated once, donated between steps, never
  reallocated per request);
- per-slot ``pos``/active state threaded through a single compiled
  per-row-position decode step (cache form ``step_slots``): finished
  sequences free their row MID-FLIGHT and queued requests join at the
  next iteration boundary;
- admission via a compiled SLOT PREFILL: the prompt is right-padded to
  the existing power-of-two buckets, run through the block's chunked
  prefill against a batch-1 scratch cache, and written into the slot's
  pool region with ``dynamic_update_slice`` — the slot index is traced,
  so one program per bucket serves every slot;
- an inactive-slot mask keeps dead lanes out of sampling and the
  fixed-shape repetition-penalty bookkeeping.

Compile-count guarantee: admission/eviction is host-side bookkeeping —
the device only ever sees (#prefill buckets) slot-prefill programs plus
ONE pooled decode step, bounded by the bucket count, not by traffic.

Per-request parity: each slot keeps its own RNG stream (root
``jax.random.key(seed)``, counter fold-in — exactly the global
key-ring's derivation), its own seen-token penalty row, and attends
only its own [0, pos] prefix, so every request's token stream is
IDENTICAL to an isolated ``ShardedDecoder.generate`` call with the same
seed (asserted in tests/test_serving.py).

Speculative decoding (``spec_k > 0``; docs/inference.md): decode is
HBM-bandwidth-bound, so verifying k drafted tokens against the KV cache
in ONE compiled call is a direct tokens/s multiplier.  A host-side
n-gram / prompt-lookup drafter (``models.sampler.NGramDrafter`` — no
extra weights, no extra HBM) proposes up to ``spec_k`` tokens per slot
from the request's own prompt+output history; one pooled
``verify_slots`` / ``verify_pages`` program scores every
row's window in one cache read and the engine accepts the longest
prefix whose candidates equal what sequential decode would have
emitted.  Parity is preserved EXACTLY: the emitted token at each
position is computed from that position's logits with the same
greedy/penalty rule — or the same per-slot RNG key (keys are PEEKED
for the whole window and the stream advanced by only the tokens
actually emitted) — as the non-speculative path, so every stream stays
bit-identical to its isolated ``ShardedDecoder.generate`` reference;
rejection merely bounds how many positions one call may emit.  Window
sizes come from a power-of-two ladder, so the verify program family is
bounded (|ladder| programs, C004-bucketed).  Rejected lanes roll the
host position back; their cache writes sit beyond every validity mask
until sequential re-writes overtake them (for the paged engine the
pages past the accept point stay with the slot — rollback never
touches the allocator).  An optional small draft model
(``draft_block=``) rides the same verify program with greedy pooled
drafting over its own slot-cache pool.  MoE blocks opt OUT of
speculation automatically (unbounded decode-routing capacity is a
function of the window batch — the same caveat class as prefix
sharing).  New fault sites ``serving.draft`` / ``serving.verify``
quarantine only the offending slot, like ``serving.step``.

Failure paths (docs/resilience.md): a host-side exception in a
per-slot path — admission prefill, the ``serving.step`` /
``serving.admit`` fault-injection sites, the per-slot eos check —
quarantines ONLY the offending slot: the request finishes with status
``"failed"`` (or re-queues while it has ``retries`` left), the row is
scrubbed and returned to the pool, and every OTHER in-flight request's
token stream stays bit-identical to a fault-free run (per-slot RNG
streams and penalty rows make the proof local — removing one lane
cannot shift another lane's draws; asserted under injected faults in
tests/test_serving_faults.py).  Per-request wall-clock deadlines evict
expired requests at iteration boundaries with status ``"expired"``;
bounded admission (``max_pending``) sheds load with a typed
:class:`~mxtpu.resilience.LoadShedError` instead of unbounded queue
growth.  A failure of the POOLED compiled step itself is pool-level by
construction and propagates to the caller — on-device dispatch cannot
attribute a fault to one lane, and the host-side per-slot paths above
are where per-request failures actually arise.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import PartitionSpec as P

from .. import random as _random
from ..ndarray import NDArray, array as nd_array
from ..observability.flight import get_flight as _flight
from ..observability.trace import get_tracer as _tracer
from ..resilience import LoadShedError
from ..resilience.counters import bump as _bump
from ..resilience.faults import inject as _inject
from .decode import ShardedDecoder, _bucket, resolve_cache_dtype
from .mesh import DeviceMesh
from .paging import (NULL_PAGE, BlockPool, HierarchicalCache,
                     PrefixIndex, _sanitizer)
from .sharding import ShardingRules

__all__ = ["ContinuousBatchingEngine", "PagedContinuousBatchingEngine",
           "Request"]


def _host_read(value):
    """``jax.device_get`` of a device value the host loop has to wait
    for, as an ``engine.host_read`` boundary span: the time the engine
    spends blocked on the device, inside whichever phase asked."""
    with _tracer().span("engine.host_read"):
        return jax.device_get(value)


def _parse_spec_tree(value):
    """Normalize a tree-speculation config to ``(max_nodes, branch)``
    ints: 1 <= max_nodes <= 31 (the 32-lane int32 ancestor-bitmask cap
    of the paged tree kernel — root lane + 31 draft nodes) and
    branch >= 1.  Accepts a tuple/list, a bare int (branch defaults to
    2), or a ``"nodes,branch"`` string (the MXTPU_SPEC_TREE form)."""
    if isinstance(value, str):
        parts = [p for p in value.replace(",", " ").split() if p]
        value = tuple(parts)
    if isinstance(value, int):
        value = (value, 2)
    try:
        nodes = int(value[0])
        branch = int(value[1]) if len(value) > 1 else 2
    except (TypeError, ValueError, IndexError):
        raise ValueError(
            "spec_tree must be (max_nodes, branch), a bare node count, "
            "or a 'nodes,branch' string — got %r" % (value,))
    if not 1 <= nodes <= 31:
        raise ValueError(
            "spec_tree max_nodes must be in [1, 31] (root + 31 draft "
            "nodes fill the verify kernel's 32-lane int32 ancestor "
            "bitmask), got %d" % nodes)
    if branch < 1:
        raise ValueError(
            "spec_tree branch must be >= 1, got %d" % branch)
    return nodes, branch


def _ambient_spec_tree():
    """Engine-default tree config from MXTPU_SPEC_TREE ("nodes,branch";
    unset/empty = tree speculation off)."""
    v = os.environ.get("MXTPU_SPEC_TREE", "").strip()
    return _parse_spec_tree(v) if v else None


class Request:
    """One generation request (host-side record)."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "temperature",
                 "top_k", "top_p", "repetition_penalty", "seed",
                 "eos_id", "deadline_at", "retries_left", "speculative",
                 "session", "spec_tree")

    def __init__(self, rid, prompt, max_new_tokens, temperature=0.0,
                 top_k=0, top_p=0.0, repetition_penalty=1.0, seed=None,
                 eos_id=None, deadline_at=None, retries=0,
                 speculative=None, session=None, spec_tree=None):
        self.rid = rid
        self.prompt = prompt            # (1, Tp) int32 numpy
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature or 0.0)
        self.top_k = int(top_k or 0)
        self.top_p = float(top_p or 0.0)
        self.repetition_penalty = float(repetition_penalty or 1.0)
        self.seed = seed
        self.eos_id = eos_id
        self.deadline_at = deadline_at  # absolute clock() value or None
        self.retries_left = int(retries)
        self.speculative = speculative  # None = engine default
        self.session = session          # paged engine only
        self.spec_tree = spec_tree      # None = engine default;
        #                                 False = force linear drafting

    @property
    def sampled(self):
        return self.temperature > 0.0

    @property
    def penalized(self):
        return self.repetition_penalty != 1.0

    @property
    def sample_config(self):
        """Slots sharing a config batch into ONE pooled sampling call."""
        return (self.temperature, self.top_k, self.top_p,
                self.repetition_penalty)


def _slot_keys(seed):
    """Per-slot RNG stream: a private _KeyRing instance, so a slot's
    draws use EXACTLY the derivation ``mx.random.seed(seed)`` +
    ``next_key()`` would — which is what makes an engine slot's samples
    bit-identical to an isolated ``generate(..., seed=seed)``."""
    return _random._KeyRing(int(seed))


class _SpecTokens:
    """One speculative iteration's emitted tokens for ONE slot (host
    int array, >= 1 long) — the per-slot entry form of ``_Slot.emitted``
    for verify iterations.  Plain-step iterations keep appending the
    pooled (B,) device vector (the deferred-materialization fast path);
    ``_finish`` handles both."""

    __slots__ = ("toks",)

    def __init__(self, toks):
        self.toks = toks


class _TreeDraft:
    """One slot's proposed draft TREE for one verify iteration (host
    ints; docs/inference.md "Tree speculation").  ``parent[j]`` is the
    WINDOW LANE of node j's parent (lane 0 carries the committed root
    token; node j itself rides window lane ``j + 1``), so topological
    order is ``parent[j] <= j``.  A linear draft [t1..tk] is the
    degenerate chain parent = [0, 1, .., k-1]."""

    __slots__ = ("toks", "parent")

    def __init__(self, toks, parent):
        self.toks = [int(t) for t in toks]
        self.parent = [int(p) for p in parent]
        if len(self.parent) != len(self.toks):
            raise ValueError(
                "tree draft needs one parent lane per node: %d nodes "
                "vs %d parents" % (len(self.toks), len(self.parent)))
        for j, p in enumerate(self.parent):
            if not 0 <= p <= j:
                raise ValueError(
                    "tree draft is not topological: node %d (window "
                    "lane %d) names parent lane %d" % (j, j + 1, p))

    def __len__(self):
        return len(self.toks)


class _Slot:
    """Host-side state of one cache row.  ``emitted`` holds references
    to the pool-wide (B,) token vector of each iteration — row ``row``
    is this slot's token; materializing per-slot streams is deferred to
    finish time so the steady-state loop dispatches O(1) host ops per
    iteration, not O(slots).  Speculative slots additionally carry a
    host mirror of their token ``history`` (prompt + emitted — what the
    n-gram drafter proposes from) and append :class:`_SpecTokens`
    entries on verify iterations; ``n_emitted`` counts emitted tokens
    across both entry forms."""

    __slots__ = ("req", "row", "pos", "emitted", "keys", "history",
                 "n_emitted", "param_gen")

    def __init__(self, req, row, pos, first_tokens, keys):
        self.req = req
        self.row = row
        self.pos = pos             # cache position of the LAST sampled
        #                            token (the next step writes here)
        self.emitted = [first_tokens]  # list of (B,) device vectors
        self.keys = keys
        self.history = None        # host ints; set when speculating
        self.n_emitted = 1
        self.param_gen = 0         # weight generation pinned at
        #                            admission (hot-swap invariant)


class ContinuousBatchingEngine:
    """Iteration-level scheduler over a fixed pool of KV-cache slots.

    Parameters
    ----------
    block : TransformerLM-like block (init_cache / prefill /
        cached_forward / write_cache_slot).
    mesh / rules / cache_spec : as ShardedDecoder — training shardings
        are consumed in place, caches live on-mesh over the kv-head axis.
    num_slots : pool size B (the compiled step's batch dimension).
    max_length : per-slot cache length; every request must satisfy
        prompt + max_new_tokens <= max_length.
    bucket_prefill : right-pad prompts to power-of-two buckets so mixed
        prompt lengths share a handful of compiled slot-prefills
        (disabled automatically for MoE blocks, same as ShardedDecoder).
    spec_k : maximum drafted tokens per slot per iteration (0 = no
        speculation, the default).  With spec_k > 0 the engine
        self-drafts with an n-gram prompt-lookup drafter and verifies
        each slot's window in one pooled compiled call — every stream
        stays bit-identical to its non-speculative reference (module
        docstring).  Disabled automatically for MoE blocks.
    spec_ngram : longest n-gram the self-drafter matches (>= 1).
    draft_block : optional small TransformerLM-like DENSE draft model;
        proposals come from pooled greedy decode over its own slot-cache
        pool instead of the n-gram lookup (the verify side is
        identical).  Requires spec_k >= 1.
    draft_rules : ShardingRules for the draft model (default: ``rules``).
    spec_tree : optional ``(max_nodes, branch)`` — draft multi-branch
        TREES instead of single chains and verify every branch in ONE
        pooled cache read (per-lane ancestor masks; docs/inference.md
        "Tree speculation").  ``max_nodes`` <= 31 caps the tree (root +
        31 draft lanes fill the paged kernel's 32-lane int32 ancestor
        bitmask), ``branch`` caps any node's children.  None reads
        ``MXTPU_SPEC_TREE`` ("nodes,branch"; unset = off).  Requests
        opt out per-submit with ``spec_tree=False`` (linear drafting)
        or override with their own tuple; mixed pools share one verify
        program — linear windows ride it as degenerate chains.
        Self-drafting only (exclusive with draft_block); MoE blocks
        opt out of speculation entirely, tree included.
    ledger_tag : optional per-replica compile-ledger label
        (``serving.step@TAG`` — see ShardedDecoder); a multi-replica
        pool (``mxtpu.serving``) tags each replica so per-replica
        program families stay separable under ``compile_budget``.
    """

    def __init__(self, block, mesh: DeviceMesh,
                 rules: Optional[ShardingRules] = None,
                 num_slots: int = 4, max_length: int = 256,
                 cache_dtype: Optional[str] = None,
                 cache_spec: P = P(None, "tp", None, None),
                 bucket_prefill: bool = True,
                 max_pending: Optional[int] = None, clock=None,
                 history: int = 1024, spec_k: int = 0,
                 spec_ngram: int = 3, draft_block=None,
                 draft_rules: Optional[ShardingRules] = None,
                 ledger_tag: Optional[str] = None, spec_tree=None):
        self._dec = ShardedDecoder(block, mesh, rules, cache_spec,
                                   bucket_prefill,
                                   ledger_tag=ledger_tag)
        self._block = block
        self._mesh = mesh
        self._num_slots = int(num_slots)
        self._max_length = int(max_length)
        # None → MXTPU_CACHE_DTYPE default ("int8" = quantized cache)
        self._cache_dtype = resolve_cache_dtype(cache_dtype)
        self._pool = None                       # cache leaves, lazy
        self._slots: List[Optional[_Slot]] = [None] * self._num_slots
        self._queue: List[Request] = []
        self._results: Dict[int, Any] = {}
        self._next_rid = 0
        self._seen = None                       # (B, V) penalty rows
        self._last_tokens = None                # (B,) pooled last draw
        self._prompt_dtype = None
        self._steps = 0
        self._tokens_generated = 0
        self._prefill_tokens = 0        # prompt tokens run through prefill
        self._iter_decoding = 0         # slots in this iteration's decode
        self._iter_prefilling = 0       # slots still prefilling after it
        # -- resilience state (docs/resilience.md) -----------------------
        self._max_pending = (None if max_pending is None
                             else int(max_pending))
        self._clock = clock if clock is not None else time.monotonic
        self._status: Dict[int, str] = {}       # rid -> lifecycle status
        self._errors: Dict[int, dict] = {}      # rid -> last error record
        # status/error records of TERMINAL requests are kept for the
        # last `history` completions only — a long-lived engine must not
        # grow per-request bookkeeping without bound
        self._history = max(int(history), 2 * self._num_slots)
        self._done: List[int] = []              # terminal rids, oldest first
        self._quarantined = 0
        self._retries = 0
        self._deadline_evictions = 0
        self._shed = 0
        # -- speculative decoding (docs/inference.md) --------------------
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0, got %d" % spec_k)
        self._spec_k = int(spec_k)
        self._spec_ngram = int(spec_ngram)
        # -- tree speculation (docs/inference.md "Tree speculation") -----
        if spec_tree is None:
            spec_tree = _ambient_spec_tree()
        self._spec_tree = (None if spec_tree is None
                           else _parse_spec_tree(spec_tree))
        if self._spec_tree is not None and draft_block is not None:
            raise ValueError(
                "spec_tree drafting is self-drafted (n-gram tree "
                "lookup) — it cannot be combined with draft_block; "
                "pick one proposal source")
        # MoE decode routing capacity is a function of the window batch,
        # so a W-token window is not routing-parity-safe — same opt-out
        # class as prefix sharing / prefill bucketing (linear AND tree)
        self._spec_on = ((self._spec_k > 0
                          or self._spec_tree is not None)
                         and not self._dec._block_has_moe())
        self._drafter = None
        self._tree_drafters: Dict[Any, Any] = {}  # (nodes, branch) ->
        #                                           TreeDrafter
        if self._spec_on and draft_block is None:
            from ..models.sampler import NGramDrafter
            self._drafter = NGramDrafter(max_ngram=spec_ngram)
        self._draft_block = draft_block
        self._draft_dec = None
        self._draft_pool = None
        if draft_block is not None:
            if self._spec_k < 1:
                raise ValueError(
                    "draft_block needs spec_k >= 1 (it bounds the "
                    "drafted window)")
            if not self._spec_on:
                # self-drafting silently opts out for MoE targets, but
                # an EXPLICIT draft model is a configuration the user
                # asked for — fail loudly instead of no-op'ing
                raise ValueError(
                    "draft_block speculation is unsupported for MoE "
                    "target blocks: their decode routing is not "
                    "window-parity-safe, so MoE targets opt out of "
                    "speculation entirely (docs/inference.md)")
            ddec = ShardedDecoder(draft_block, mesh,
                                  draft_rules or rules, cache_spec,
                                  bucket_prefill,
                                  ledger_tag=ledger_tag)
            if ddec._block_has_moe():
                raise ValueError(
                    "draft_block must be a dense block: MoE decode "
                    "routing is not window-parity-safe (the same "
                    "reason MoE targets opt out of speculation)")
            self._draft_dec = ddec
        self._drafted_tokens = 0
        self._accepted_tokens = 0
        self._tree_nodes_drafted = 0   # draft nodes proposed as trees
        self._tree_paths = 0           # root-to-leaf paths proposed
        self._verify_calls = 0
        self._slot_iterations = 0   # slot-participations in decode
        #                             calls: tokens/slot_iterations is
        #                             the per-cache-read multiplier
        # -- live weight hot-swap (docs/serving.md "Elastic serving") ----
        self._param_gen = 0                 # current weight generation
        self._staged_adoption = None        # placed leaves awaiting an
        #                                     empty iteration boundary
        self._prev_leaves = None            # rollback target
        self._adoption_staged_step = None   # _steps when staged
        self._adoptions = 0
        self._adoption_failures = 0
        self._rollbacks = 0
        self._last_adoption_steps = 0       # stage->install latency in
        #                                     engine iterations
        # -- observability (docs/observability.md) -----------------------
        # correlation-id scope: replica pools stamp the replica id via
        # InProcessReplica; standalone multi-engine tracing should pass
        # distinct ledger_tag= so timelines never collide
        self._trace_tag = ledger_tag or "eng"

    # -- observability plumbing (docs/observability.md) ------------------
    def _trace_key(self, rid) -> str:
        """Correlation id of one engine request ("<tag>:<rid>"); the
        transport aliases it onto the gateway id at submit so one
        request's events assemble into one timeline."""
        return "%s:%s" % (self._trace_tag, rid)

    def _emit(self, etype, rid, **fields):
        """Record one per-request trace event (no-op while tracing and
        flight recording are both off — the instrumented paths stay
        host-side bookkeeping and compile nothing)."""
        tr = _tracer()
        if tr.active:
            tr.emit(etype,
                    rid=None if rid is None else self._trace_key(rid),
                    **fields)

    def _flight_failure(self, kind, rid=None, **context):
        fl = _flight()
        if fl.active:
            rids = () if rid is None else (self._trace_key(rid),)
            fl.failure(kind, rids=rids, engine=self._trace_tag,
                       **context)

    # -- introspection ---------------------------------------------------
    @property
    def num_slots(self):
        return self._num_slots

    @property
    def free_slots(self):
        return sum(1 for s in self._slots if s is None)

    @property
    def pending(self):
        return len(self._queue)

    @property
    def active(self):
        return self._num_slots - self.free_slots

    @property
    def stats(self):
        # canonical key names use the *_requests/*_tokens/*_blocks
        # suffix convention (the deprecated pre-PR-14 spellings are
        # gone — mapping table in docs/observability.md)
        return {
            "steps": self._steps,
            "generated_tokens": self._tokens_generated,
            "prefill_tokens": self._prefill_tokens,
            "quarantined_requests": self._quarantined,
            "retried_requests": self._retries,
            "expired_requests": self._deadline_evictions,
            "shed_requests": self._shed,
            "drafted_tokens": self._drafted_tokens,
            "accepted_tokens": self._accepted_tokens,
            "tree_nodes_drafted": self._tree_nodes_drafted,
            "tree_paths": self._tree_paths,
            "slot_iterations": self._slot_iterations,
            "draft_hit_rate": (
                self._accepted_tokens / self._drafted_tokens
                if self._drafted_tokens else 0.0),
            "verify_calls": self._verify_calls,
            # live weight hot-swap (docs/serving.md "Elastic serving")
            "param_generation": self._param_gen,
            "adoptions": self._adoptions,
            "adoption_failures": self._adoption_failures,
            "rollbacks": self._rollbacks,
            "adoption_staged": int(self._staged_adoption is not None),
            "last_adoption_steps": self._last_adoption_steps,
            "compiled_programs": sorted(
                k[0] for k in self._dec._jit_cache),
        }

    def status(self, rid) -> str:
        """Lifecycle status of one request: ``queued`` / ``active`` /
        ``ok`` / ``failed`` / ``expired`` / ``cancelled`` (``unknown``
        for a rid this engine never issued)."""
        return self._status.get(rid, "unknown")

    def error(self, rid) -> Optional[dict]:
        """The last error record of a quarantined/failed request
        (``{"type", "error", "site", "step", "emitted"}``) or None.
        Kept even after a successful retry, for observability."""
        return self._errors.get(rid)

    # -- request intake --------------------------------------------------
    #: whether this engine honors ``submit(session=...)`` (the paged
    #: engine's hierarchical cache; the slot engine has no page chains
    #: to pin, so it rejects the knob loudly instead of no-op'ing)
    _supports_sessions = False

    def submit(self, prompt_ids, max_new_tokens, temperature=0.0,
               top_k=0, top_p=0.0, repetition_penalty=1.0, seed=None,
               eos_id=None, deadline_s=None, retries=0,
               speculative=None, session=None, spec_tree=None) -> int:
        """Queue one request; returns its id.  Sampling knobs follow the
        ``generate`` contract (temperature=0 greedy; seed reproduces).

        ``deadline_s``: wall-clock budget in seconds (engine clock);
        past it the request is evicted at the next iteration boundary
        with status ``"expired"`` and its partial output.  ``retries``:
        how many times a quarantined (step/admission-failed) request is
        re-queued and restarted from scratch before it is marked
        ``"failed"`` — a restart is bit-identical to a fresh submit
        (per-slot RNG streams re-derive from the seed).
        ``speculative``: per-request opt-out (False) from a
        speculation-enabled engine, or the engine default (None); the
        output is bit-identical either way — speculation only changes
        how many positions one iteration may emit.  ``session``: a
        conversation handle (paged engine only, docs/inference.md
        "Hierarchical prefix cache") — the finished request's page
        chain stays pinned so the NEXT turn's prompt (this transcript
        plus the new message) prefills only the new suffix; release
        with ``close_session``.  ``spec_tree``: per-request TREE
        drafting config — None rides the engine default, False forces
        linear (single-chain) drafting, a ``(max_nodes, branch)`` tuple
        overrides; output is bit-identical in every mode
        (docs/inference.md "Tree speculation")."""
        if spec_tree is not None and spec_tree is not False:
            spec_tree = _parse_spec_tree(spec_tree)
            if not self._spec_on or self._drafter is None:
                raise ValueError(
                    "submit(spec_tree=...) needs a self-drafting "
                    "speculation-enabled engine (spec_k > 0 or "
                    "spec_tree= at construction, a dense non-MoE "
                    "block, and no draft_block)")
        if session is not None and not self._supports_sessions:
            raise ValueError(
                "submit(session=...) needs the paged engine's "
                "hierarchical cache (PagedContinuousBatchingEngine) — "
                "the slot engine has no page chains to pin")
        prompt_ids = prompt_ids if isinstance(prompt_ids, NDArray) \
            else nd_array(prompt_ids)
        if prompt_ids.ndim != 2 or prompt_ids.shape[0] != 1:
            raise ValueError(
                "submit() takes ONE request: prompt_ids must be "
                "(1, T_prompt), got %r" % (prompt_ids.shape,))
        Tp = prompt_ids.shape[1]
        if Tp + int(max_new_tokens) > self._max_length:
            raise ValueError(
                "request needs %d cache positions > slot max_length %d"
                % (Tp + int(max_new_tokens), self._max_length))
        if self._max_pending is not None and \
                len(self._queue) >= self._max_pending:
            self._shed += 1
            _bump("shed_requests")
            self._emit("engine.shed", None,
                       queue_depth=len(self._queue),
                       limit=self._max_pending)
            self._flight_failure("shed", queue_depth=len(self._queue),
                                 limit=self._max_pending)
            raise LoadShedError(
                "admission queue full (%d pending >= max_pending=%d): "
                "request shed — back off and resubmit"
                % (len(self._queue), self._max_pending),
                queue_depth=len(self._queue), limit=self._max_pending,
                # queued work drains ~num_slots requests per slot
                # turnover: a deterministic host-counter estimate of
                # iterations until a queue position frees
                retry_after_ticks=max(
                    1, -(-len(self._queue) // self._num_slots)))
        if self._prompt_dtype is None:
            self._prompt_dtype = prompt_ids.dtype
        rid = self._next_rid
        self._next_rid += 1
        prompt = onp.asarray(prompt_ids.asnumpy(), dtype=onp.int32)
        deadline_at = (None if deadline_s is None
                       else self._clock() + float(deadline_s))
        self._queue.append(Request(
            rid, prompt, max_new_tokens, temperature, top_k, top_p,
            repetition_penalty, seed, eos_id, deadline_at=deadline_at,
            retries=retries, speculative=speculative, session=session,
            spec_tree=spec_tree))
        self._status[rid] = "queued"
        return rid

    # -- pool plumbing ---------------------------------------------------
    def _ensure_pool(self, sample_prompt):
        self._dec._ensure_staged(sample_prompt)
        self._ensure_draft_pool(sample_prompt)
        if self._pool is not None:
            return
        self._pool = self._dec._place_cache(self._block.init_cache(
            self._num_slots, self._max_length, self._cache_dtype))

    def _ensure_draft_pool(self, sample_prompt):
        """Stage the optional draft model and allocate its own slot
        pool (same rows/length as the target pool — the draft cache
        mirrors the target row/position-wise, which is what makes
        rollback a shared host position fix-up)."""
        if self._draft_dec is None or self._draft_pool is not None:
            return
        self._draft_dec._ensure_staged(sample_prompt)
        self._draft_pool = self._draft_dec._place_cache(
            self._draft_block.init_cache(
                self._num_slots, self._max_length, self._cache_dtype))

    def _ensure_seen(self, vocab):
        if self._seen is None or self._seen.shape[-1] != vocab:
            self._seen = jnp.zeros((self._num_slots, vocab), bool)

    # -- admission -------------------------------------------------------
    @staticmethod
    def _emitted_count(emitted):
        """Token count of an ``emitted`` list (mixed pooled-vector /
        _SpecTokens entries)."""
        return sum(len(e.toks) if isinstance(e, _SpecTokens) else 1
                   for e in emitted or [])

    def _finish(self, slot_idx_or_none, req, emitted, row, status="ok"):
        prompt = jnp.asarray(req.prompt, jnp.int32)
        if emitted and not any(isinstance(e, _SpecTokens)
                               for e in emitted):
            # fast path: every entry is a pooled (B,) vector
            toks = jnp.stack(emitted)[:, row].reshape(1, -1)
            out = jnp.concatenate([prompt, toks], axis=1)
        elif emitted:
            parts = [e.toks.reshape(-1) if isinstance(e, _SpecTokens)
                     else e[row].reshape(1) for e in emitted]
            toks = jnp.concatenate(
                [jnp.asarray(p, jnp.int32) for p in parts]).reshape(1, -1)
            out = jnp.concatenate([prompt, toks], axis=1)
        else:
            out = prompt
        dt = self._prompt_dtype or onp.int32
        self._results[req.rid] = NDArray(out.astype(jnp.dtype(dt)))
        self._status[req.rid] = status
        self._emit("engine.finish", req.rid, status=status,
                   emitted=self._emitted_count(emitted))
        self._done.append(req.rid)
        if len(self._done) > self._history:
            evicted = self._done[:-self._history]
            del self._done[:-self._history]
            for rid in evicted:
                self._status.pop(rid, None)
                self._errors.pop(rid, None)
        if slot_idx_or_none is not None:
            self._slots[slot_idx_or_none] = None

    # -- failure paths ---------------------------------------------------
    def _scrub_row(self, row):
        """Return a cache row to the pool: zero its penalty bookkeeping.
        The KV contents need no scrub — the next admission's slot
        prefill overwrites [0, Tb) and per-row validity masks already
        keep a dead lane's positions out of every other lane's
        attention (the normal slot-reuse discipline)."""
        if self._seen is not None:
            self._seen = self._seen.at[row].set(False)

    def _record_error(self, req, exc, site, emitted_n):
        self._errors[req.rid] = {
            "type": type(exc).__name__,
            "error": str(exc),
            "site": site,
            "step": self._steps,
            "emitted": emitted_n,
        }

    def _requeue_or_fail(self, req, exc, site, emitted=None, row=0):
        """Shared tail of every per-request failure: re-queue while the
        request has retries left (a from-scratch restart — bit-identical
        to a fresh submit), else finish it with status ``failed`` and
        its partial output."""
        self._record_error(req, exc, site, self._emitted_count(emitted))
        if req.retries_left > 0:
            req.retries_left -= 1
            self._retries += 1
            _bump("retries")
            self._emit("engine.requeue", req.rid,
                       retries_left=req.retries_left, site=site)
            self._status[req.rid] = "queued"
            self._queue.append(req)
        else:
            self._finish(None, req, emitted or [], row, status="failed")

    def _quarantine_request(self, req, exc, site, row, emitted=None):
        """Shared quarantine tail (occupied slot and failed admission
        alike): scrub the row's bookkeeping and fail/re-queue the
        request."""
        self._scrub_row(row)
        self._quarantined += 1
        _bump("quarantined_slots")
        self._emit("engine.quarantine", req.rid, site=site,
                   error=type(exc).__name__, step=self._steps)
        self._flight_failure("quarantine", rid=req.rid, site=site,
                             error=type(exc).__name__, step=self._steps)
        self._requeue_or_fail(req, exc, site, emitted=emitted, row=row)

    def _quarantine(self, slot_idx, exc, site):
        """Evict ONLY the offending slot: scrub the row, return it to
        the pool, and fail/re-queue the request.  Every other slot's
        state (its own RNG stream, penalty row, cache row) is untouched,
        which is what keeps the other streams bit-identical to a
        fault-free run."""
        slot = self._slots[slot_idx]
        self._slots[slot_idx] = None
        self._quarantine_request(slot.req, exc, site, slot.row,
                                 emitted=slot.emitted)

    def _evict_expired(self):
        """Iteration-boundary deadline sweep over active slots AND the
        queue; expired requests finish with status ``expired`` and their
        partial output."""
        now = self._clock()

        def expired(req):
            return req.deadline_at is not None and now >= req.deadline_at

        for i, slot in enumerate(self._slots):
            if slot is not None and expired(slot.req):
                self._slots[i] = None
                self._scrub_row(slot.row)
                self._deadline_evictions += 1
                _bump("deadline_evictions")
                self._finish(None, slot.req, slot.emitted, slot.row,
                             status="expired")
        if self._queue and any(expired(r) for r in self._queue):
            keep = []
            for req in self._queue:
                if expired(req):
                    self._deadline_evictions += 1
                    _bump("deadline_evictions")
                    self._finish(None, req, [], 0, status="expired")
                else:
                    keep.append(req)
            self._queue = keep

    def _admit(self, req, slot_idx):
        """Compiled slot-prefill + first-token sample; mirrors the
        prefill half of ShardedDecoder.generate exactly (bucketed
        right-padding, seed applied AFTER prefill, first draw from the
        prompt's last real logit row)."""
        from ..models.sampler import sample_next_token

        _inject("serving.admit", key=req.rid)
        Tp = req.prompt.shape[1]
        self._emit("engine.admit", req.rid, prompt_tokens=Tp)
        bucketing = (self._dec._bucket_prefill
                     and not self._dec._block_has_moe())
        raw = jnp.asarray(req.prompt, jnp.int32)
        if bucketing:
            Tb = min(_bucket(Tp), self._max_length)
            if Tb > Tp:
                raw = jnp.pad(raw, ((0, 0), (0, Tb - Tp)))
        logits, self._pool = self._dec._run(
            "slot_prefill", self._pool, raw, jnp.int32(slot_idx))
        self._prefill_tokens += Tp
        last = logits[:, Tp - 1]                       # (1, V)
        keys = None
        if req.seed is not None and req.sampled:
            # seed AFTER prefill — the ordering generate() guarantees
            keys = _slot_keys(req.seed)
        elif req.sampled:
            keys = _slot_keys(onp.random.randint(0, 2**31 - 1))
        self._ensure_seen(last.shape[-1])
        if req.penalized:
            row = jnp.zeros((last.shape[-1],), bool).at[
                jnp.asarray(req.prompt[0], jnp.int32)].set(True)
            self._seen = self._seen.at[slot_idx].set(row)
        tok = sample_next_token(
            last, keys.next_key() if req.sampled else None,
            req.temperature, req.top_k, req.top_p,
            req.repetition_penalty,
            seen_mask=self._seen[slot_idx:slot_idx + 1]
            if req.penalized else None)
        tok = tok.astype(jnp.int32)                    # (1,)
        if req.penalized:
            self._seen = self._seen.at[slot_idx, tok[0]].set(True)
        if self._last_tokens is None:
            self._last_tokens = jnp.zeros((self._num_slots,), jnp.int32)
        self._last_tokens = self._last_tokens.at[slot_idx].set(tok[0])
        slot = _Slot(req, slot_idx, Tp, self._last_tokens, keys)
        slot.param_gen = self._param_gen
        if self._slot_done(slot):
            self._finish(None, req, slot.emitted, slot_idx)
            return
        # arm BEFORE occupying: a failed admission (incl. a draft-pool
        # prefill fault) must never leave the slot assigned
        self._arm_speculation(slot, req, tok[0])
        self._slots[slot_idx] = slot
        self._status[req.rid] = "active"

    def _slot_done(self, slot):
        if slot.n_emitted >= slot.req.max_new_tokens:
            return True
        if slot.req.eos_id is not None:
            last = slot.emitted[-1]
            if isinstance(last, _SpecTokens):
                return int(last.toks[-1]) == slot.req.eos_id
            # eos needs a host read; only requests that opted into an
            # eos token pay the sync
            return int(_host_read(last[slot.row])) == slot.req.eos_id
        return False

    # -- speculative decoding --------------------------------------------
    def _speculates(self, req):
        """Whether this request self-drafts: engine speculation on
        (spec_k > 0, non-MoE block) and the request did not opt out."""
        return (self._spec_on and req.speculative is not False
                and req.max_new_tokens > 1)

    def _arm_speculation(self, slot, req, first_tok):
        """Admission tail for speculating requests: start the host
        history mirror (prompt + first token — what the drafter
        proposes from; one small host read per admission) and, in
        draft-model mode, prefill the slot's draft-cache row."""
        if not self._speculates(req):
            return
        slot.history = [int(t) for t in req.prompt[0]] + [int(first_tok)]
        if self._draft_dec is not None:
            self._draft_prefill(slot.row, req)

    def _draft_prefill(self, row, req):
        """Ingest the prompt into the draft model's cache row (same
        bucketed slot-prefill machinery as the target)."""
        Tp = req.prompt.shape[1]
        raw = jnp.asarray(req.prompt, jnp.int32)
        if self._draft_dec._bucket_prefill:  # draft block is dense
            Tb = min(_bucket(Tp), self._max_length)
            if Tb > Tp:
                raw = jnp.pad(raw, ((0, 0), (0, Tb - Tp)))
        _, self._draft_pool = self._draft_dec._run(
            "slot_prefill", self._draft_pool, raw, jnp.int32(row))

    def _spec_extent(self, slot):
        """Hard cache extent of one slot in positions — drafted windows
        clamp so pos + drafts never outruns it (for the paged engine:
        the slot's allocated page chain)."""
        return self._max_length

    def _spec_budget(self, slot):
        """Per-slot draft budget this iteration: never draft past the
        request's remaining tokens (a window emits between 1 and
        drafts+1 tokens) nor the slot/page extent."""
        return min(self._spec_k,
                   slot.req.max_new_tokens - slot.n_emitted - 1,
                   self._spec_extent(slot) - 1 - slot.pos)

    # -- tree speculation (docs/inference.md "Tree speculation") ---------
    def _tree_cfg_for(self, req):
        """Resolved (max_nodes, branch) tree config of one request, or
        None for linear drafting.  Per-request False opts out; a
        per-request tuple overrides the engine default; draft-model
        engines never tree-draft (proposals come from the model)."""
        if not self._spec_on or self._draft_dec is not None:
            return None
        if req.spec_tree is False:
            return None
        if req.spec_tree is not None:
            return req.spec_tree        # validated at submit
        return self._spec_tree

    def _tree_drafter_for(self, cfg):
        """The TreeDrafter for one (max_nodes, branch) config (cached —
        drafters are stateless, one per distinct config ever seen)."""
        d = self._tree_drafters.get(cfg)
        if d is None:
            from ..models.sampler import TreeDrafter
            d = TreeDrafter(max_nodes=cfg[0], branch=cfg[1],
                            max_ngram=self._spec_ngram)
            self._tree_drafters[cfg] = d
        return d

    def _tree_budget(self, slot, nodes):
        """Per-slot tree NODE budget this iteration: the same remaining-
        tokens / cache-extent clamps as _spec_budget (the deepest
        accepted path emits at most depth+1 <= nodes+1 tokens, and the
        widest window lane writes at pos + nodes)."""
        return min(nodes,
                   slot.req.max_new_tokens - slot.n_emitted - 1,
                   self._spec_extent(slot) - 1 - slot.pos)

    def _draft_phase(self, active):
        """Collect draft proposals for every speculating active slot
        ({row: [tokens]}).  The ``serving.draft`` fault site fires per
        slot (keyed by rid) BEFORE its proposal; a raise — or a drafter
        error — quarantines only that slot."""
        if not self._spec_on:
            return {}
        spec_rows = []
        for i in list(active):
            s = self._slots[i]
            if s.history is None:
                continue
            try:
                _inject("serving.draft", key=s.req.rid)
            except Exception as exc:
                self._quarantine(i, exc, "serving.draft")
                active.remove(i)
                continue
            spec_rows.append(i)
        if not spec_rows:
            return {}
        if self._draft_dec is not None:
            return self._propose_model(spec_rows)
        out = {}
        for i in list(spec_rows):
            s = self._slots[i]
            try:
                cfg = self._tree_cfg_for(s.req)
                if cfg is not None:
                    n = self._tree_budget(s, cfg[0])
                    toks, par = [], []
                    if n > 0:
                        toks, par, _ = self._tree_drafter_for(
                            cfg).propose_tree(s.history, n, n)
                    if toks:
                        d = _TreeDraft(toks, par)
                        self._tree_nodes_drafted += len(toks)
                        # leaves = nodes no other node names as parent
                        self._tree_paths += len(toks) - len(
                            {p for p in d.parent if p > 0})
                        out[i] = d
                    continue
                k = self._spec_budget(s)
                d = self._drafter.propose(s.history, k) if k > 0 else []
            except Exception as exc:
                self._quarantine(i, exc, "serving.draft")
                active.remove(i)
                continue
            if d:
                out[i] = d
        return out

    def _propose_model(self, rows):
        """Pooled greedy drafting with the small draft model: j
        proposals per row from j+1 pooled draft decode steps — the
        extra step writes the last draft's K/V so the draft cache never
        gaps when a whole window is accepted.  The draft cache mirrors
        the target row/position-wise; rejections share the host
        position roll-back (stale draft rows are overwritten before any
        validity mask can reach them, the same argument as the target
        cache).  A failure here is pool-level, like the pooled step."""
        B = self._num_slots
        j = max(0, min(self._spec_k,
                       max(self._spec_budget(self._slots[i])
                           for i in rows)))
        pos = onp.zeros((B,), onp.int32)
        for i in rows:
            pos[i] = self._slots[i].pos
        tok = self._last_tokens.reshape(-1, 1)
        proposals = []
        # non-drafting rows flow through with garbage (fixed shapes);
        # their draft rows are dead and absorb the writes
        for w in range(j + 1):
            logits, self._draft_pool = self._draft_dec._run(
                "step_slots", self._draft_pool, tok, jnp.asarray(pos + w))
            if w < j:
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                proposals.append(nxt)
                tok = nxt.reshape(-1, 1)
        if not proposals:
            return {}
        mat = onp.asarray(_host_read(jnp.stack(proposals, axis=1)))
        out = {}
        for i in rows:
            k = self._spec_budget(self._slots[i])
            if k > 0:
                out[i] = [int(t) for t in mat[i, :k]]
        return out

    def _decode_state(self, active):
        """Traced inputs of the pooled decode/verify programs (the slot
        engine needs only the per-row positions; the paged engine adds
        block tables)."""
        pos = onp.zeros((self._num_slots,), onp.int32)
        for i in active:
            pos[i] = self._slots[i].pos
        return pos

    def _run_step(self, state):
        logits, self._pool = self._dec._run(
            "step_slots", self._pool, self._last_tokens.reshape(-1, 1),
            jnp.asarray(state))
        return logits

    def _run_verify(self, state, window, valid_len):
        logits, self._pool = self._dec._run(
            "verify_slots", self._pool, window, jnp.asarray(state),
            jnp.asarray(valid_len))
        return logits

    def _decode_active(self, active):
        """The pooled decode tail shared by both engines: draft, then
        either ONE plain step or ONE batched verify call for every
        active slot."""
        from ..models.sampler import sample_next_token

        drafts = self._draft_phase(active)  # may quarantine members
        if not active:
            return
        tr = _tracer()
        if tr.active and drafts:
            for i, d in sorted(drafts.items()):
                self._emit("engine.draft", self._slots[i].req.rid,
                           proposed=len(d))
        if not drafts:
            self._decode_plain(active, sample_next_token)
        elif any(isinstance(d, _TreeDraft) for d in drafts.values()):
            # one TREE verify serves the whole pool: linear windows
            # ride the same program as degenerate chains
            self._decode_verify_tree(active, drafts, sample_next_token)
        else:
            self._decode_verify(active, drafts, sample_next_token)

    def _decode_plain(self, active, sample_next_token):
        """The non-speculative pooled step (the original decode tail);
        speculating slots still mirror their emitted token into the
        host history so the next iteration can draft."""
        logits = self._run_step(self._decode_state(active))
        last = logits[:, 0]                          # (B, V)
        self._sample_pool(last, active, sample_next_token)
        self._steps += 1
        self._tokens_generated += len(active)
        self._slot_iterations += len(active)
        hist_rows = [i for i in active
                     if self._slots[i].history is not None]
        if hist_rows:
            toks = onp.asarray(_host_read(self._last_tokens))
            for i in hist_rows:
                self._slots[i].history.append(int(toks[i]))
        trace_on = _tracer().active
        for i in active:
            s = self._slots[i]
            s.pos += 1
            s.n_emitted += 1
            s.emitted.append(self._last_tokens)
            if trace_on:
                self._emit("engine.decode", s.req.rid, pos=s.pos,
                           emitted=s.n_emitted)
            try:
                done = self._slot_done(s)
            except Exception as exc:  # per-slot eos host read
                self._quarantine(i, exc, "serving.step")
                continue
            if done:
                self._finish(i, s.req, s.emitted, s.row)

    def _decode_verify(self, active, drafts, sample_next_token):
        """Speculative iteration: ONE compiled verify call scores every
        row's candidate window (last token + drafts) against the cache,
        candidate draws are computed per window position with the SAME
        rule and RNG keys sequential decode would use (keys peeked,
        then advanced by the emitted count), and each row advances by
        its accepted prefix + 1 — so every stream stays bit-identical
        to non-speculative decode while accepted drafts cost one cache
        read instead of k.  The ``serving.verify`` fault site fires per
        participating slot (keyed by rid) before the pooled call."""
        B = self._num_slots
        for i in list(active):
            try:
                _inject("serving.verify", key=self._slots[i].req.rid)
            except Exception as exc:
                self._quarantine(i, exc, "serving.verify")
                active.remove(i)
                drafts.pop(i, None)
        if not active:
            return
        jmax = max((len(d) for d in drafts.values()), default=0)
        if jmax == 0:
            self._decode_plain(active, sample_next_token)
            return
        # window width from the power-of-two ladder: the verify program
        # family stays <= |ladder| (C004-bucketed, never C001)
        W = _bucket(jmax + 1, base=2)
        state = self._decode_state(active)
        dr = onp.zeros((B, W - 1), onp.int32)
        vl = onp.zeros((B,), onp.int32)
        nreal = 0
        for i in active:
            s = self._slots[i]
            d = drafts.get(i, ())[:W - 1]
            vl[i] = 1 + len(d)
            if d:
                dr[i, :len(d)] = d
                nreal += len(d)
        window = jnp.concatenate(
            [self._last_tokens.reshape(-1, 1).astype(jnp.int32),
             jnp.asarray(dr)], axis=1)                # (B, W)
        logits = self._run_verify(state, window, vl)  # (B, W, V)
        M = self._sample_window(logits, active, window, W,
                                sample_next_token)    # (B, W) candidates
        # accepted prefix per row: candidate w must equal draft w+1
        vld = jnp.asarray(vl)
        match = (M[:, :W - 1] == window[:, 1:]) & \
            (jnp.arange(W - 1)[None, :] < (vld - 1)[:, None])
        counts = 1 + jnp.sum(jnp.cumprod(
            match.astype(jnp.int32), axis=1), axis=1)  # (B,) emitted
        self._last_tokens = jnp.take_along_axis(
            M, jnp.clip(counts - 1, 0, W - 1)[:, None],
            axis=1)[:, 0].astype(jnp.int32)
        self._update_seen_window(active, M, counts, W)
        # ONE pooled host sync: accept counts + the emitted candidates
        counts_h, M_h = (onp.asarray(x)
                         for x in _host_read((counts, M)))
        self._steps += 1
        self._verify_calls += 1
        self._drafted_tokens += nreal
        self._slot_iterations += len(active)
        trace_on = _tracer().active
        for i in active:
            s = self._slots[i]
            m = int(counts_h[i])
            toks = M_h[i, :m]
            if s.req.eos_id is not None:
                hits = onp.nonzero(toks == s.req.eos_id)[0]
                if hits.size:  # stop AT eos, exactly like sequential
                    m = int(hits[0]) + 1
                    toks = toks[:m]
            if trace_on:
                self._emit("engine.verify", s.req.rid,
                           drafted=int(vl[i]) - 1, accepted=m - 1)
            self._accepted_tokens += m - 1
            self._tokens_generated += m
            s.pos += m
            s.n_emitted += m
            if s.keys is not None:
                s.keys.advance(m)  # commit exactly the emitted draws
            if s.history is not None:
                s.history.extend(int(t) for t in toks)
            s.emitted.append(_SpecTokens(toks.copy()))
            if (s.n_emitted >= s.req.max_new_tokens
                    or (s.req.eos_id is not None
                        and int(toks[-1]) == s.req.eos_id)):
                self._finish(i, s.req, s.emitted, s.row)

    def _decode_verify_tree(self, active, drafts, sample_next_token):
        """TREE-speculative iteration: ONE compiled verify call scores
        every row's candidate tree — the committed root token on window
        lane 0, draft node j on lane j+1, each lane attending only its
        own root-to-node path (per-lane ancestor sets; the paged kernel
        consumes them as an int32 bitmask).  Candidate draws use
        EXACTLY the key / penalty state sequential decode would use at
        the lane's DEPTH along its own path, and each row advances by
        its deepest fully matched root path + 1.  Sibling tokens are
        distinct (TreeDrafter dedups them), so at most one child of any
        node can match its parent's candidate draw — the accepted lanes
        form a single chain and every stream stays bit-identical to
        non-speculative decode (docs/inference.md "Tree speculation").
        A row whose accepted path took a side branch re-packs those
        lanes' K/V into sequential cache positions with ONE compiled
        gather/scatter fix-up; rejection rollback stays a host position
        fix-up exactly like linear speculation.  Linear drafts ride the
        same call as degenerate chains, so mixed pools share one verify
        program per window bucket.  The ``serving.verify`` fault site
        fires per participating slot (keyed by rid) before the pooled
        call."""
        B = self._num_slots
        for i in list(active):
            try:
                _inject("serving.verify", key=self._slots[i].req.rid)
            except Exception as exc:
                self._quarantine(i, exc, "serving.verify")
                active.remove(i)
                drafts.pop(i, None)
        if not active:
            return
        jmax = max((len(d) for d in drafts.values()), default=0)
        if jmax == 0:
            self._decode_plain(active, sample_next_token)
            return
        # window width from the same power-of-two ladder as the linear
        # verify: the tree program family stays <= |ladder| too
        W = _bucket(jmax + 1, base=2)
        state = self._decode_state(active)
        dr = onp.zeros((B, W - 1), onp.int32)
        vl = onp.zeros((B,), onp.int32)
        # degenerate-chain defaults: padding lanes continue a chain off
        # the previous lane, so every row's table is topologically
        # well-formed however few nodes it drafted (invalid lanes are
        # forced unmatched below and their writes sit behind valid_len)
        parent = onp.maximum(
            onp.arange(W, dtype=onp.int32) - 1, 0) * onp.ones(
            (B, 1), onp.int32)
        nreal = 0
        for i in active:
            s = self._slots[i]
            d = drafts.get(i)
            if d is None:
                vl[i] = 1
                continue
            if isinstance(d, _TreeDraft):
                toks, par = d.toks, d.parent
            else:  # linear draft -> degenerate chain
                toks, par = list(d), list(range(len(d)))
            n = min(len(toks), W - 1)
            vl[i] = 1 + n
            dr[i, :n] = toks[:n]
            parent[i, 1:n + 1] = par[:n]
            nreal += n
        # per-lane path tables from the parent lanes (host, W <= 32):
        # depth[b,w] = |root path| - 1, anc[b,w] = strict-ancestor lane
        # bitmask (the paged kernel's scalar-prefetch operand), and
        # perm[b,w] = the root path in depth order padded with w itself
        # (so gathering window tokens at perm[w] yields "ancestors and
        # self" — idempotent repeats, exactly what the per-lane penalty
        # masks and acceptance test want)
        depth = onp.zeros((B, W), onp.int32)
        anc = onp.zeros((B, W), onp.int32)
        perm = onp.zeros((B, W, W), onp.int32)
        for b in range(B):
            pb, db, ab, qb = parent[b], depth[b], anc[b], perm[b]
            for w in range(1, W):
                p = int(pb[w])
                dw = int(db[p]) + 1
                db[w] = dw
                ab[w] = ab[p] | (1 << p)
                qb[w, :dw] = qb[p, :dw]
                qb[w, dw:] = w
        window = jnp.concatenate(
            [self._last_tokens.reshape(-1, 1).astype(jnp.int32),
             jnp.asarray(dr)], axis=1)                # (B, W)
        logits = self._run_verify_tree(state, window, vl, perm, depth,
                                       anc)           # (B, W, V)
        M = self._sample_window_tree(logits, active, window, W, perm,
                                     depth, sample_next_token)
        # acceptance: lane w matches when its token equals the draw at
        # its PARENT lane; a lane is accepted when its whole root path
        # (ancestors and itself) matched.  perm gathers exactly that
        # set, and path_lane[j] recovers the accepted chain's lane at
        # emit position j (one accepted lane per depth — sibling
        # uniqueness makes the sum a selection, never a collision).
        par_d = jnp.asarray(parent)
        dep_d = jnp.asarray(depth)
        vld = jnp.asarray(vl)
        lane = jnp.arange(W)
        matched = ((window == jnp.take_along_axis(M, par_d, axis=1))
                   & (lane[None, :] < vld[:, None])).at[:, 0].set(True)
        accepted = jnp.all(jnp.take_along_axis(
            matched, jnp.asarray(perm).reshape(B, -1),
            axis=1).reshape(B, W, W), axis=2)         # (B, W)
        counts = jnp.max((dep_d + 1) * accepted.astype(jnp.int32),
                         axis=1)                      # (B,) emitted
        path_lane = jnp.sum(
            ((dep_d[:, :, None] == lane[None, None, :])
             & accepted[:, :, None]) * lane[None, :, None],
            axis=1).astype(jnp.int32)                 # (B, W)
        path_M = jnp.take_along_axis(M, path_lane, axis=1)
        self._last_tokens = jnp.take_along_axis(
            path_M, jnp.clip(counts - 1, 0, W - 1)[:, None],
            axis=1)[:, 0].astype(jnp.int32)
        self._update_seen_window(active, path_M, counts, W)
        # ONE pooled host sync: accept counts + the emitted path tokens
        # AND the lanes they came from (the fix-up source map)
        counts_h, pathM_h, lane_h = (
            onp.asarray(x) for x in _host_read(
                (counts, path_M, path_lane)))
        self._steps += 1
        self._verify_calls += 1
        self._drafted_tokens += nreal
        self._slot_iterations += len(active)
        trace_on = _tracer().active
        src = onp.full((B, W), -1, onp.int32)
        need_fix = False
        finish = []
        for i in active:
            s = self._slots[i]
            m = int(counts_h[i])
            toks = pathM_h[i, :m]
            if s.req.eos_id is not None:
                hits = onp.nonzero(toks == s.req.eos_id)[0]
                if hits.size:  # stop AT eos, exactly like sequential
                    m = int(hits[0]) + 1
                    toks = toks[:m]
            if trace_on:
                self._emit("engine.verify", s.req.rid,
                           drafted=int(vl[i]) - 1, accepted=m - 1,
                           tree=isinstance(drafts.get(i), _TreeDraft))
            self._accepted_tokens += m - 1
            self._tokens_generated += m
            s.pos += m
            s.n_emitted += m
            if s.keys is not None:
                s.keys.advance(m)  # commit exactly the emitted draws
            if s.history is not None:
                s.history.extend(int(t) for t in toks)
            s.emitted.append(_SpecTokens(toks.copy()))
            if (s.n_emitted >= s.req.max_new_tokens
                    or (s.req.eos_id is not None
                        and int(toks[-1]) == s.req.eos_id)):
                finish.append(i)
            elif any(int(lane_h[i, j]) != j for j in range(m)):
                # the accepted path took a side branch: cache position
                # pos+j must hold lane path[j]'s K/V before the next
                # step reads it (finished rows skip the re-pack — their
                # rows/pages are released either way)
                src[i, :m] = lane_h[i, :m]
                need_fix = True
        if need_fix:
            self._run_fixup(state, src)
        for i in finish:
            s = self._slots[i]
            self._finish(i, s.req, s.emitted, s.row)

    def _run_verify_tree(self, state, window, valid_len, perm, depth,
                         anc):
        logits, self._pool = self._dec._run(
            "verify_tree_slots", self._pool, window, jnp.asarray(state),
            jnp.asarray(valid_len), jnp.asarray(perm),
            jnp.asarray(depth))
        return logits

    def _run_fixup(self, state, src_lane):
        self._pool = self._dec._run(
            "fixup_slots", self._pool, jnp.asarray(state),
            jnp.asarray(src_lane))

    def _sample_window_tree(self, logits, active, window, W, perm,
                            depth, sample_next_token):
        """Candidate draws for every TREE lane: lane w of row b samples
        from logits[b, w] with EXACTLY the key / penalty state
        sequential decode would use after emitting the lane's root
        path — key = the slot's depth[b,w]-th future draw; penalty mask
        = base seen + the path's window tokens (gathered at perm[b,w],
        self included — the tree form of "window drafts 1..w"; the root
        token is already in the base mask, so its repeat is
        idempotent).  Degenerate chains reproduce _sample_window's
        masks and keys value-for-value, which is what lets mixed pools
        share this call bit-identically."""
        B = self._num_slots
        V = logits.shape[-1]
        self._ensure_seen(V)
        groups: Dict[Any, List[int]] = {}
        for i in active:
            groups.setdefault(self._slots[i].req.sample_config,
                              []).append(i)
        pen = [i for i in active if self._slots[i].req.penalized]
        seen_w = [self._seen] * W
        if pen:
            pr = onp.zeros((B,), bool)
            pr[pen] = True
            pr = jnp.asarray(pr)
            rows = jnp.arange(B)[:, None]
            perm_d = jnp.asarray(perm)
            seen_w = []
            for w in range(W):
                toks_w = jnp.take_along_axis(window, perm_d[:, w, :],
                                             axis=1)       # (B, W)
                upd = self._seen.at[rows, toks_w].set(True)
                seen_w.append(jnp.where(pr[:, None], upd, self._seen))
        cols: List[Any] = [None] * W
        for (temp, top_k, top_p, rep), members in groups.items():
            mask = onp.zeros((B,), bool)
            mask[members] = True
            mask = jnp.asarray(mask)
            keys_w = None
            if temp > 0.0:
                dummy = jax.random.key(0)
                keys_w = []
                for w in range(W):
                    per_row = [
                        self._slots[i].keys.peek_key(int(depth[i, w]))
                        if i in members and self._slots[i].keys
                        else dummy for i in range(B)]
                    keys_w.append(jax.random.wrap_key_data(jnp.stack(
                        [jax.random.key_data(k) for k in per_row])))
            for w in range(W):
                out = sample_next_token(
                    logits[:, w], keys_w[w] if keys_w else None,
                    temp, top_k, top_p, rep,
                    seen_mask=seen_w[w] if rep != 1.0 else None,
                    active_mask=mask)
                cols[w] = out if cols[w] is None \
                    else jnp.where(mask, out, cols[w])
        return jnp.stack(cols, axis=1).astype(jnp.int32)

    def _sample_window(self, logits, active, window, W,
                       sample_next_token):
        """Candidate draws for every window position: position w of row
        b is sampled from logits[b, w] with EXACTLY the key / penalty
        state sequential decode would use there (key = the slot's w-th
        future draw; penalty mask = base seen + window drafts 1..w),
        grouped by sampling config like _sample_pool.  Rows whose
        prefix rejects discard the later columns unconsumed."""
        B = self._num_slots
        V = logits.shape[-1]
        self._ensure_seen(V)
        groups: Dict[Any, List[int]] = {}
        for i in active:
            groups.setdefault(self._slots[i].req.sample_config,
                              []).append(i)
        pen = [i for i in active if self._slots[i].req.penalized]
        seen_w = [self._seen] * W
        if pen:
            pr = onp.zeros((B,), bool)
            pr[pen] = True
            pr = jnp.asarray(pr)
            rows = jnp.arange(B)
            seen_w = [self._seen]
            cur = self._seen
            for w in range(1, W):
                upd = cur.at[rows, window[:, w]].set(True)
                cur = jnp.where(pr[:, None], upd, cur)
                seen_w.append(cur)
        cols: List[Any] = [None] * W
        for (temp, top_k, top_p, rep), members in groups.items():
            mask = onp.zeros((B,), bool)
            mask[members] = True
            mask = jnp.asarray(mask)
            keys_w = None
            if temp > 0.0:
                dummy = jax.random.key(0)
                keys_w = []
                for w in range(W):
                    per_row = [self._slots[i].keys.peek_key(w)
                               if i in members and self._slots[i].keys
                               else dummy for i in range(B)]
                    keys_w.append(jax.random.wrap_key_data(jnp.stack(
                        [jax.random.key_data(k) for k in per_row])))
            for w in range(W):
                out = sample_next_token(
                    logits[:, w], keys_w[w] if keys_w else None,
                    temp, top_k, top_p, rep,
                    seen_mask=seen_w[w] if rep != 1.0 else None,
                    active_mask=mask)
                cols[w] = out if cols[w] is None \
                    else jnp.where(mask, out, cols[w])
        return jnp.stack(cols, axis=1).astype(jnp.int32)

    def _update_seen_window(self, active, M, counts, W):
        """Persistent penalty bookkeeping: add each penalized row's
        EMITTED window tokens (candidates 0..counts-1) to its seen row
        — the multi-token form of _sample_pool's per-draw scatter."""
        pen = [i for i in active if self._slots[i].req.penalized]
        if not pen:
            return
        B = self._num_slots
        pr = onp.zeros((B,), bool)
        pr[pen] = True
        pr = jnp.asarray(pr)
        rows = jnp.arange(B)
        cur = self._seen
        for w in range(W):
            upd = cur.at[rows, M[:, w]].set(True)
            take = pr & (counts > w)
            cur = jnp.where(take[:, None], upd, cur)
        self._seen = cur

    # -- one scheduler iteration ----------------------------------------
    def step(self):
        """One scheduler iteration (``_step_impl`` docstring has the
        semantics), inside an ``engine.iteration`` boundary span — kept
        tracer on or off, and under a live ``jax.profiler`` session a
        TraceAnnotation; host-side only, zero compiled programs either
        way.  Its phases are child spans (``engine.schedule``,
        ``engine.prefill``, ``engine.decode_step``, and
        ``engine.host_read`` around every blocking read of a device
        value); its end carries the iteration's counts."""
        with _tracer().span("engine.iteration", tag=self._trace_tag,
                            step=self._steps) as span:
            tokens, prefill = self._tokens_generated, self._prefill_tokens
            self._iter_decoding = self._iter_prefilling = 0
            done = self._step_impl()
            span.set(decoding=self._iter_decoding,
                     prefilling=self._iter_prefilling,
                     waiting=len(self._queue),
                     tokens=self._tokens_generated - tokens,
                     prefill_tokens=self._prefill_tokens - prefill)
            return done

    def _step_impl(self):
        """One iteration: evict deadline-expired requests, admit queued
        requests into free slots, then run ONE pooled decode step — or,
        when speculation produced drafts, ONE batched verify call — for
        every active slot.  Returns the list of request ids finished
        this iteration (any terminal status).

        Per-slot failure handling: an exception in a per-slot host path
        (admission prefill, the per-slot fault sites, the eos check)
        quarantines that slot only — the iteration proceeds for every
        other slot with bit-identical results."""
        finished_before = set(self._results)
        with _tracer().span("engine.schedule"):
            self._evict_expired()
            self._maybe_install_adoption()
            if self._queue and self._staged_adoption is None:
                self._ensure_pool(nd_array(self._queue[0].prompt))
            self._admit_queued()
        active = [i for i, s in enumerate(self._slots) if s is not None]
        # hot-swap invariant: every decoding slot rides the weight
        # generation pinned at its admission (installs happen only at
        # empty boundaries, so these can never diverge)
        assert all(self._slots[i].param_gen == self._param_gen
                   for i in active), "slot outlived a weight install"
        # per-slot fault site, consulted at the iteration boundary in
        # slot order (deterministic hit counting): a raise here models a
        # per-request step failure and quarantines exactly that slot
        for i in list(active):
            try:
                _inject("serving.step", key=self._slots[i].req.rid)
            except Exception as exc:
                self._quarantine(i, exc, "serving.step")
                active.remove(i)
        self._decode_step(active)
        return [r for r in self._results if r not in finished_before]

    def _admit_queued(self):
        """Admission at the iteration boundary (Orca-style): joiners
        prefill now and take part in the very next pooled step — gated
        while a staged weight generation awaits its empty boundary (a
        fresh admission would pin the OLD generation and starve the
        install under continuous load)."""
        for i in range(self._num_slots):
            if not self._queue or self._staged_adoption is not None:
                break
            if self._slots[i] is None:
                req = self._queue.pop(0)
                if req.max_new_tokens <= 0:
                    self._finish(None, req, [], 0)
                    continue
                try:
                    self._admit(req, i)
                except Exception as exc:
                    # failed admission never occupied the slot (it is
                    # assigned last in _admit); the shared tail scrubs
                    # the penalty bookkeeping a partial admission may
                    # have touched
                    self._quarantine_request(req, exc, "serving.admit",
                                             row=i)

    def _decode_step(self, active):
        """``_decode_active`` for this iteration's decoding slots, as the
        ``engine.decode_step`` phase; notes the iteration's counts."""
        self._iter_decoding = len(active)
        # an occupied slot that does not decode is still prefilling
        self._iter_prefilling = sum(
            s is not None for s in self._slots) - len(active)
        if active:
            with _tracer().span("engine.decode_step"):
                self._decode_active(active)

    def _sample_pool(self, last, active, sample_next_token):
        """Pooled per-slot sampling: slots sharing a sampling config
        batch into one call with PER-SLOT keys and an active mask, so a
        drawn row is bit-identical to the isolated single-request draw
        and dead lanes never touch the seen-mask bookkeeping.  Updates
        the pooled (B,) last-token vector — the steady state costs ONE
        sampling call and no per-slot dispatches."""
        B = self._num_slots
        groups: Dict[Any, List[int]] = {}
        for i in active:
            groups.setdefault(self._slots[i].req.sample_config,
                              []).append(i)
        next_tokens = None
        for (temp, top_k, top_p, rep), members in groups.items():
            mask = onp.zeros((B,), bool)
            mask[members] = True
            mask = jnp.asarray(mask)
            keys = None
            if temp > 0.0:
                dummy = jax.random.key(0)
                per_row = [self._slots[i].keys.next_key()
                           if i in members and self._slots[i].keys
                           else dummy for i in range(B)]
                keys = jax.random.wrap_key_data(jnp.stack(
                    [jax.random.key_data(k) for k in per_row]))
            out = sample_next_token(
                last, keys, temp, top_k, top_p, rep,
                seen_mask=self._seen if rep != 1.0 else None,
                active_mask=mask)
            next_tokens = out if next_tokens is None \
                else jnp.where(mask, out, next_tokens)
            if rep != 1.0:
                idx = jnp.asarray(members, jnp.int32)
                self._seen = self._seen.at[idx, out[idx]].set(True)
        self._last_tokens = next_tokens.astype(jnp.int32)

    def take_result(self, rid):
        """Pop one finished request's output (step()-driven use; run()
        drains everything at once)."""
        return self._results.pop(rid)

    # -- external control (the multi-replica service layer rides these) --
    def cancel(self, rid) -> bool:
        """Cancel one non-terminal request NOW: a queued request
        finishes immediately with status ``cancelled`` and an empty
        output; an active one is evicted through the same idempotent
        scrub/release path every terminal route uses (the paged engine
        returns its pages to the pool) with its partial output.  Every
        other in-flight stream is untouched — the same locality argument
        as quarantine.  Returns False for unknown/terminal rids.  Used
        by ``mxtpu.serving`` to retire hedge losers and drain dying
        replicas deterministically."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                self._emit("engine.cancel", rid)
                self._finish(None, req, [], 0, status="cancelled")
                return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.rid == rid:
                self._slots[i] = None
                self._scrub_row(slot.row)
                self._emit("engine.cancel", rid)
                self._finish(None, slot.req, slot.emitted, slot.row,
                             status="cancelled")
                return True
        return False

    def prefix_probe(self, prompt_ids) -> int:
        """Locality probe for a multi-replica router: how many of this
        prompt's tokens THIS engine would skip prefilling if the
        request were admitted right now.  The slot engine has no prefix
        reuse, so it always reports 0 (routers fall back to pure load
        balance); the paged engine walks its radix index and host tier
        (read-only — see ``PrefixIndex.probe``)."""
        return 0

    def drop_cache(self) -> int:
        """Release every CACHED page chain this engine holds beyond its
        live requests (the paged engine's pinned tier, host tier, and
        open sessions).  The replica-death drain path: after cancelling
        all requests and dropping the cache, ``blocks_in_use`` must be
        0 — nothing on a dead replica may keep pages.  Returns the
        number of device pages freed (0 on the slot engine, which has
        no cache tiers)."""
        return 0

    # -- live weight hot-swap (docs/serving.md "Elastic serving") --------
    @staticmethod
    def _hotswap_enabled():
        """MXTPU_HOTSWAP kill switch (default enabled): ``0`` refuses
        every ``adopt()`` process-wide, so an operator can freeze a
        fleet's weights without touching call sites."""
        return os.environ.get("MXTPU_HOTSWAP", "1").strip().lower() \
            not in ("0", "false", "off")

    def adopt(self, checkpoint):
        """Stage a guardian-verified checkpoint as the NEXT weight
        generation; it installs at the first iteration boundary with no
        active slots.  Returns the staged generation number.

        The contract (docs/serving.md "Elastic serving"):

        - the checkpoint is CRC-verified host-side
          (:func:`~mxtpu.resilience.checkpoint.verify`) and its params
          validated against this block's tree BEFORE anything changes —
          a corrupt/torn file raises
          :class:`~mxtpu.resilience.CorruptCheckpointError` (a
          mismatched one ``ValueError``) and the replica keeps serving
          the old generation untouched;
        - in-flight streams finish bit-identical on the OLD weights:
          each slot pins its generation at admission and install waits
          for every slot to drain (new admissions are gated while a
          generation is staged, so the boundary arrives);
        - new admissions after install ride the new generation; cached
          prefix state (radix index, pinned/host tiers, sessions) is
          dropped at install — its KV was computed under the old
          weights and must never satisfy a new-generation hit;
        - :meth:`rollback` re-stages the previous generation through
          the same machinery.

        ``checkpoint`` is a path to a guardian pickle blob (the
        ``{"params": {name: array}, ...}`` form) or a raw
        ``{name: array}`` pickle.  The ``serving.adopt`` fault site
        fires FIRST, keyed by the checkpoint's basename — an injected
        raise models an adoption that never started."""
        import pickle

        from ..resilience.checkpoint import (CorruptCheckpointError,
                                             verify as _ckpt_verify)

        if not self._hotswap_enabled():
            raise RuntimeError(
                "live weight hot-swap is disabled (MXTPU_HOTSWAP=0) — "
                "adopt() refused; the serving generation is frozen")
        name = os.path.basename(str(checkpoint))
        try:
            _inject("serving.adopt", key=name)
            with open(checkpoint, "rb") as f:
                payload = f.read()
            _ckpt_verify(str(checkpoint), required=True, data=payload)
            try:
                blob = pickle.loads(payload)
            except Exception as exc:
                raise CorruptCheckpointError(
                    "checkpoint payload failed to unpickle: %s" % exc,
                    path=str(checkpoint))
            named = blob.get("params", blob) if isinstance(blob, dict) \
                else None
            if not isinstance(named, dict):
                raise CorruptCheckpointError(
                    "checkpoint payload is not a params mapping "
                    "(got %s)" % type(blob).__name__,
                    path=str(checkpoint))
            leaves = self._dec.prepare_adoption(named)
        except Exception as exc:
            self._adoption_failures += 1
            _bump("adoption_failures")
            self._emit("serving.adopt", None, stage="failed",
                       checkpoint=name, error=type(exc).__name__,
                       param_generation=self._param_gen)
            self._flight_failure("adoption_failed", checkpoint=name,
                                 error=type(exc).__name__,
                                 param_generation=self._param_gen)
            raise
        return self._stage_leaves(leaves, name)

    def rollback(self):
        """Re-stage the PREVIOUS weight generation (the leaves live on
        until the next successful install, so rollback needs no
        checkpoint file).  Same boundary semantics as :meth:`adopt`;
        raises ``RuntimeError`` when nothing was ever adopted."""
        if self._prev_leaves is None:
            raise RuntimeError(
                "rollback() has no previous weight generation — no "
                "adoption has installed on this engine yet")
        self._rollbacks += 1
        _bump("adoption_rollbacks")
        self._emit("serving.rollback", None,
                   param_generation=self._param_gen)
        return self._stage_leaves(self._prev_leaves, "<rollback>")

    def _stage_leaves(self, leaves, name):
        """Shared adopt/rollback tail: park the placed leaves and gate
        admissions until the pool drains to an empty boundary."""
        self._staged_adoption = leaves
        self._adoption_staged_step = self._steps
        self._emit("serving.adopt", None, stage="staged",
                   checkpoint=name, param_generation=self._param_gen,
                   active_slots=self.active)
        return self._param_gen + 1

    def _maybe_install_adoption(self):
        """Iteration-boundary install: when a generation is staged and
        every slot has drained, swap the decoder's live leaves, bump
        the generation, and drop all cached prefix state (computed
        under the old weights).  Runs FIRST in ``_step_impl`` so the
        admissions that follow in the same iteration already ride the
        new generation."""
        if self._staged_adoption is None:
            return
        if any(s is not None for s in self._slots):
            return                  # streams still pinned to old gen
        self._prev_leaves = self._dec._live_param_leaves()
        self._dec.install_leaves(self._staged_adoption)
        self._staged_adoption = None
        self._param_gen += 1
        self._last_adoption_steps = \
            self._steps - self._adoption_staged_step
        self._adoption_staged_step = None
        self._adoptions += 1
        _bump("adoptions")
        freed = self.drop_cache()
        san = _sanitizer()
        if san is not None and getattr(self, "_bp", None) is not None:
            san.check_drain(self._bp)       # V004: zero pins survive
        self._emit("serving.adopt", None, stage="installed",
                   param_generation=self._param_gen,
                   latency_steps=self._last_adoption_steps,
                   dropped_pages=freed)

    # -- drain -----------------------------------------------------------
    def run(self):
        """Drain the queue and every active slot; returns {request id →
        (1, T_prompt + generated) NDArray}."""
        # non-convergence watchdog, sized ONCE from the total
        # outstanding work (every iteration with any active slot emits
        # at least one token, so a healthy run can never exceed this).
        # A request with retries may restart from scratch up to
        # retries_left more times, so its worst case is (1 + retries)
        # full decodes.
        outstanding = sum(
            (1 + r.retries_left) * r.max_new_tokens
            for r in self._queue) + sum(
            (1 + s.req.retries_left) * s.req.max_new_tokens
            - s.n_emitted
            for s in self._slots if s is not None)
        limit = 4 * (outstanding + len(self._queue)
                     + self._num_slots + 1)
        guard = 0
        while self._queue or any(s is not None for s in self._slots):
            self.step()
            guard += 1
            if guard > limit:
                raise RuntimeError(
                    "continuous-batching run() failed to converge — "
                    "scheduler bug (slots: %r)" % (self._slots,))
        out, self._results = self._results, {}
        return out


class _AdmissionDeferred(Exception):
    """Internal: the page pool is transiently exhausted — the request
    stays at the queue head and retries at the next iteration boundary
    (pages free as in-flight requests finish).  Never user-visible."""


class _PagedSlot(_Slot):
    """Host-side state of one PAGED slot.  ``pos`` is None while the
    prompt is still prefilling (one chunk per engine iteration); the
    slot joins the pooled decode step only once it is not None.  The
    page list itself lives in the engine's per-row table (released on
    every terminal path through one helper)."""

    __slots__ = ("Tp", "chunks", "chunk_i", "cow")

    def __init__(self, req, row, Tp, chunks, cow):
        self.req = req
        self.row = row
        self.pos = None
        self.emitted = []
        self.keys = None
        self.history = None
        self.n_emitted = 0
        self.param_gen = 0
        self.Tp = Tp
        self.chunks = chunks          # [(start, T_actual, T_bucketed)]
        self.chunk_i = 0
        self.cow = cow                # (src_page, dst_page) or None

    @property
    def prefilling(self):
        return self.pos is None


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over a BLOCK-PAGED KV cache with
    cross-request prefix sharing and chunked prefill (vLLM
    PagedAttention / SGLang radix-cache lineage, kept static-shape).

    The slot engine above reserves ``max_length`` cache positions per
    slot no matter what a request needs; at serving scale, cache bytes
    ARE concurrency, so that stranding is the capacity ceiling.  This
    engine replaces the per-slot rows with ONE pool of ``num_blocks``
    fixed-size pages:

    - **Paged pool** — per-layer (num_blocks+1, KV, block_size, D)
      caches (page 0 reserved as the null page that absorbs dead-lane
      writes).  Each slot holds a padded int32 block table threaded
      through the compiled step; the cache forms ``step_pages`` /
      ``prefill_pages`` gather/scatter through the table, reproducing
      the contiguous cache bit-for-bit.  A request holds
      ceil(need/block_size) pages instead of max_length positions.
    - **Prefix sharing** — a host-side radix index maps full prompt
      pages to their holders; a request whose prompt prefix matches
      references the SAME immutable pages (refcounted) and skips
      recomputing them entirely.  At the divergence point the partially
      matching page is cloned copy-on-write (``src == dst`` folds the
      no-COW case into the same compiled program).  Valid because the
      prefix K/V is a pure function of the prefix tokens (asserted
      bit-exact in tests) — which is also why MoE blocks opt OUT of
      sharing: their expert capacity budgets from the FULL prompt
      length, so a prefix's K/V is not donor-independent.
    - **Chunked prefill** — long prompts ingest ``prefill_chunk``
      tokens per engine iteration, interleaved with the pooled decode
      step, so a long admission never stalls in-flight token streams.
      Chunk lengths come from the same power-of-two buckets as the
      slot engine, so compiled programs stay ≤ (#chunk buckets + 1).

    Everything the slot engine guarantees carries over: per-request
    streams bit-identical to isolated ``ShardedDecoder.generate``
    (greedy, seeded-sampled, penalized — including under fault plans),
    quarantine/deadline/shed semantics, O(log T) compiled programs.
    New fault sites: ``serving.prefix_lookup`` and
    ``serving.block_alloc`` (docs/resilience.md); pool exhaustion a
    request can NEVER satisfy sheds at submit() with
    :class:`~mxtpu.resilience.LoadShedError`, transient exhaustion
    defers admission at the queue head until pages free.

    Parameters (beyond ContinuousBatchingEngine's)
    ----------------------------------------------
    block_size : tokens per page (16 default — the vLLM sweet spot:
        smaller pages waste less tail but cost more table/gather
        overhead and shorter shareable units).
    num_blocks : pool capacity in pages.  Default
        ``num_slots * ceil(max_length / block_size)`` — byte parity
        with the slot engine, at which point right-sized allocation +
        sharing turn the saved bytes into extra resident requests.
    prefill_chunk : tokens ingested per iteration during admission
        (power of two >= 8; prompts shorter than one chunk admit in a
        single iteration, exactly like the slot engine).
    pin_bytes : device-tier budget of the HIERARCHICAL prefix cache
        (docs/inference.md "Hierarchical prefix cache"): finished
        requests' full-page chains stay pinned in HBM under an LRU
        policy holding at most ``pin_bytes // bytes_per_block`` distinct
        pages, so a popular prompt survives traffic lulls instead of
        recomputing.  Accepts an int or a "16MiB"-style string; None
        reads ``MXTPU_PIN_BYTES`` (default 0 = off).  Session chains
        pin regardless of this budget (they are explicit handles).
    host_cache_bytes : host-RAM tier budget — chains evicted from the
        pinned tier spill to host arrays (``serving.swap_out``) and
        re-admit on a radix hit (``serving.swap_in``) through ONE
        bounded copy program.  Same forms; None reads
        ``MXTPU_HOST_CACHE_BYTES`` (default 0 = off).
    overlap_swaps : defer host-tier RESTORES to the iteration boundary
        (default False = restore synchronously inside admission): a
        cold-chain admission whose prompt matches the host tier defers
        one iteration, the pooled decode step runs first, and the
        ``serving.swap_in`` copies land only after it — so in-flight
        token streams never gap behind a restore (the copies overlap
        the decode dispatch instead of preceding it).  Streams are
        bit-identical either way; only the iteration the restore pays
        in moves.
    """

    _supports_sessions = True

    def __init__(self, block, mesh: DeviceMesh,
                 rules: Optional[ShardingRules] = None,
                 num_slots: int = 4, max_length: int = 256,
                 cache_dtype: Optional[str] = None,
                 cache_spec: P = P(None, "tp", None, None),
                 bucket_prefill: bool = True,
                 max_pending: Optional[int] = None, clock=None,
                 history: int = 1024, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: int = 64, spec_k: int = 0,
                 spec_ngram: int = 3, draft_block=None,
                 draft_rules: Optional[ShardingRules] = None,
                 pin_bytes=None, host_cache_bytes=None,
                 overlap_swaps: bool = False,
                 ledger_tag: Optional[str] = None, spec_tree=None):
        super().__init__(block, mesh, rules, num_slots, max_length,
                         cache_dtype, cache_spec, bucket_prefill,
                         max_pending, clock, history, spec_k,
                         spec_ngram, draft_block, draft_rules,
                         ledger_tag=ledger_tag, spec_tree=spec_tree)
        bs = int(block_size)
        chunk = int(prefill_chunk)
        if bs < 1:
            raise ValueError("block_size must be >= 1, got %d" % bs)
        if chunk < 8 or (chunk & (chunk - 1)):
            raise ValueError(
                "prefill_chunk must be a power of two >= 8 (it is a "
                "compiled-program shape), got %d" % chunk)
        self._bs = bs
        self._chunk = chunk
        # table width: every request's pages plus headroom for the last
        # chunk's bucket padding (padded writes must stay inside the
        # request's own pages; positions past the prompt are overwritten
        # by decode or sit beyond every validity mask)
        self._M = -(-(self._max_length + chunk) // bs)
        if num_blocks is None:
            num_blocks = self._num_slots * (-(-self._max_length // bs))
        self._prefix = PrefixIndex(bs)
        self._bp = BlockPool(int(num_blocks), bs,
                             on_free=self._prefix.evict)
        self._slot_pages: List[Optional[List[int]]] = \
            [None] * self._num_slots
        self._prefix_hits = 0
        self._cow_copies = 0
        # -- hierarchical prefix cache (docs/inference.md) ---------------
        self._pin_bytes = self._budget_bytes(pin_bytes,
                                             "MXTPU_PIN_BYTES")
        self._host_bytes = self._budget_bytes(host_cache_bytes,
                                              "MXTPU_HOST_CACHE_BYTES")
        self._hc: Optional[HierarchicalCache] = None  # built with pool
        self._bytes_per_block = None
        self._swap_zero = None          # content template, built lazily
        self._sessions: Dict[Any, int] = {}   # sid -> turns submitted
        self._swap_ins = 0              # pages restored host -> device
        self._swap_outs = 0             # pages spilled device -> host
        self._session_hits = 0
        self._prefill_tokens_avoided = 0
        # -- overlapped swap-ins (docs/inference.md) ---------------------
        self._overlap_swaps = bool(overlap_swaps)
        self._swap_pending: Optional[Request] = None
        self._swap_attempted: set = set()   # rids already deferred once
        self._deferred_swap_ins = 0

    # -- introspection ---------------------------------------------------
    @property
    def stats(self):
        out = dict(super().stats)
        out.update({
            "blocks_in_use": self._bp.in_use,
            "blocks_free": self._bp.free_count,
            "blocks_shared": self._bp.shared_count,
            "shared_extra_refs": self._bp.shared_extra_refs,
            "prefix_hit_requests": self._prefix_hits,
            "cow_copied_blocks": self._cow_copies,
            "block_size": self._bs,
            "num_blocks": self._bp.capacity,
            # hierarchical prefix cache (0s while disabled)
            "pinned_blocks": (self._hc.pinned_blocks
                              if self._hc is not None else 0),
            "spilled_blocks": (self._hc.spilled_blocks
                               if self._hc is not None else 0),
            "swapped_in_blocks": self._swap_ins,
            "swapped_out_blocks": self._swap_outs,
            "deferred_swap_in_requests": self._deferred_swap_ins,
            "session_hit_requests": self._session_hits,
            "sessions_open": len(self._sessions),
            "prefill_tokens_avoided": self._prefill_tokens_avoided,
            # which attention path each paged program family resolved
            # to when it was traced, and why (ops/pallas/paged_attention
            # .resolve_path): ``paged_attention[...]`` is the
            # step_pages/verify_pages programs' cache read,
            # ``paged_prefill[...,T=..]`` the page_prefill program of
            # that chunk bucket; ``pallas: ...`` or ``xla: <K-rule>``
            "attention_paths": dict(sorted(
                self._dec.attention_paths.items())),
        })
        return out

    # -- paged pool plumbing ---------------------------------------------
    def _ensure_pool(self, sample_prompt):
        self._dec._ensure_staged(sample_prompt)
        self._ensure_draft_pool(sample_prompt)
        if self._pool is not None:
            return
        self._pool = self._dec._place_cache(self._block.init_block_pool(
            self._bp.capacity + 1, self._bs, self._cache_dtype))
        self._init_hierarchy()

    # -- hierarchical prefix cache (docs/inference.md) -------------------
    @staticmethod
    def _budget_bytes(value, env):
        """Resolve one tier budget: an explicit int / "16MiB"-style
        string, else the env var, else 0 (tier off)."""
        import os

        from ..analysis.memory_estimate import parse_bytes

        if value is None:
            value = os.environ.get(env, 0)
        return int(parse_bytes(value))

    def _init_hierarchy(self):
        """Price a page from the ACTUAL placed pool (int8 caches halve
        bytes_per_block, which doubles both tier budgets for free) and
        build the policy object.  The two budgets price DIFFERENT
        memories: ``pin_bytes`` is per-device HBM, so a tp-sharded
        pool's pages divide by their shard count, while
        ``host_cache_bytes`` prices the host copies the swap program
        replicates — full unsharded pages (matching
        ``paged_kv_cache_residency``'s bytes_per_block vs
        bytes_per_block_host split).  MoE blocks opt out entirely —
        they opt out of prefix sharing, and a chain that cannot be
        shared cannot be reused."""
        def _device_nbytes(leaf):
            # per-device bytes of one sharded leaf (all shards of the
            # pool are even: kv-head divisibility is validated at
            # construction); fall back to global bytes when the
            # backend exposes no addressable shards
            shards = getattr(leaf, "addressable_shards", None)
            return shards[0].data.nbytes if shards else leaf.nbytes

        leaves = jax.tree_util.tree_leaves(self._pool)
        per_block_host = sum(l.nbytes // l.shape[0] for l in leaves)
        per_block_dev = sum(
            _device_nbytes(l) // l.shape[0] for l in leaves)
        self._bytes_per_block = per_block_host
        if self._dec._block_has_moe():
            return
        self._hc = HierarchicalCache(
            self._bp, self._prefix,
            pin_blocks=self._pin_bytes // per_block_dev,
            host_blocks=self._host_bytes // per_block_host)

    def _hierarchy_on(self):
        """Whether finished chains are worth pinning at all: an auto-pin
        budget, a host tier to spill into, or at least one live
        session."""
        return self._hc is not None and (
            self._hc.pin_blocks > 0 or self._hc.host_blocks > 0
            or bool(self._sessions))

    def _swap_template(self):
        """Zero content template for swap-out calls (the copy program
        takes a content arg in both directions; write=0 ignores it)."""
        if self._swap_zero is None:
            self._swap_zero = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape[1:], l.dtype), self._pool)
        return self._swap_zero

    def _read_page(self, bid):
        """Device→host copy of one page through the bounded copy
        program (the swap tier's ONLY compiled program; ledger site
        ``serving.swap``); returns a host pytree of numpy arrays."""
        san = _sanitizer()
        if san is not None:
            san.check_use(self._bp, bid)           # V002 gate
        content, self._pool = self._dec._swap_page_jitted(
            self._pool, self._swap_template(), bid, 0)
        return jax.tree_util.tree_map(onp.asarray, _host_read(content))

    def _write_page(self, bid, content):
        """Host→device restore of one page (same program, write=1)."""
        san = _sanitizer()
        if san is not None:
            san.check_use(self._bp, bid, write=True)  # V002/V003 gate
        _, self._pool = self._dec._swap_page_jitted(
            self._pool, content, bid, 1)

    def _spill_chain(self, chain):
        """Evict one pinned chain from the device tier: copy its pages
        to host (budget permitting) then unpin.  The ``serving.swap_out``
        fault site fires once per spill; a raise — or any copy failure —
        degrades to dropping the chain WITHOUT a host copy (a cache
        loss costs recompute, never correctness), so the spill path can
        never poison the request that triggered the eviction."""
        content = None
        if self._hc.host_blocks >= len(chain.pages):
            try:
                _inject("serving.swap_out")
                content = [self._read_page(bid) for bid in chain.pages]
            except Exception:
                content = None
        if content is not None:
            self._hc.spill(chain, content)
            self._emit("engine.swap_out", None,
                       pages=len(chain.pages), dropped=False)
            self._swap_outs += len(chain.pages)
        else:
            self._emit("engine.swap_out", None,
                       pages=len(chain.pages), dropped=True)
            self._hc.drop_chain(chain)

    def _enforce_pin_budget(self):
        while self._hc is not None:
            victim = self._hc.pick_budget_victim()
            if victim is None:
                return
            self._spill_chain(victim)

    def _reclaim(self, short):
        """Pool pressure: spill pinned chains (non-session LRU first,
        sessions last) until ``short`` pages freed or nothing evictable
        remains — live admissions always beat cached prefixes, so a
        request only defers once the pinned tier cannot help."""
        while short > 0 and self._hc is not None:
            victim = self._hc.pick_pressure_victim()
            if victim is None:
                return
            before = self._bp.free_count
            self._spill_chain(victim)
            short -= self._bp.free_count - before

    def _try_swap_in(self, req, full):
        """Host-tier lookup at admission: when a spilled chain matches
        MORE of the prompt than the device radix walk did, restore the
        missing pages (alloc + the bounded copy program per page),
        stitch them into the device index, and re-pin the chain —
        the caller then re-runs the device lookup and shares them like
        any other prefix hit.  Returns True whenever the pool was
        TOUCHED (pages restored, or a reclaim ran for a restore that
        then could not fit) — the caller must re-walk the index in
        either case, since a reclaim may have freed pages the first
        walk returned.  The ``serving.swap_in`` fault site fires before
        the restore; a raise releases every restore-allocated page and
        propagates through the admission quarantine path (retries
        restart the request bit-identically)."""
        if self._hc is None or not self._hc.host_chains:
            return False
        Tp = req.prompt.shape[1]
        match = self._hc.host_match(req.prompt[0], limit=Tp - 1)
        if match is None or match[1] <= len(full):
            return False
        chain, npages = match
        extra = npages - len(full)
        # hold the device-matched prefix across the reclaim below: a
        # spill may otherwise free (and recycle) exactly these pages
        for bid in full:
            self._bp.retain(bid)
        try:
            if extra > self._bp.free_count:
                self._reclaim(extra - self._bp.free_count)
            if extra > self._bp.free_count:
                return True         # pool too hot to restore — but the
                #                     reclaim mutated it: caller re-walks
            _inject("serving.swap_in", key=req.rid)
            fresh = self._bp.alloc(extra)
            try:
                for bid, content in zip(fresh,
                                        chain.content[len(full):npages]):
                    self._write_page(bid, content)
            except Exception:
                for bid in fresh:
                    self._bp.release(bid)
                raise
            san = _sanitizer()
            if san is not None:
                san.note_restore(self._bp, fresh)
            tokens = chain.tokens[:npages * self._bs]
            self._prefix.register(tokens, list(full) + fresh)
            pages, _ = self._prefix.lookup(tokens, limit=len(tokens))
            self._hc.pin_chain(tokens, pages, sid=chain.sid)
            if npages == len(chain.content):
                self._hc.drop_host(chain)
            # else: a PARTIAL restore (this prompt matched only a
            # prefix of the spilled chain) keeps the host copy — a
            # session transcript's unrestored tail must stay
            # recoverable for the conversation's next turn
            # the alloc reference hands over to the pin: restored pages
            # are owned by the chain (and whoever shares them), not by
            # this admission
            for bid in fresh:
                self._bp.release(bid)
        finally:
            for bid in full:
                self._bp.release(bid)
        self._emit("engine.swap_in", req.rid, pages=len(fresh))
        self._swap_ins += len(fresh)
        return True

    def _offer_chain(self, row, req):
        """Finish-time tail of a successful request: register the FULL
        written pages of its final sequence (prompt + emitted — K/V at
        position i is a pure function of tokens[:i+1], so a finished
        transcript's pages are as immutable and shareable as prompt
        pages) and pin the chain in the device tier.  Non-session
        chains need an auto-pin budget OR a host tier (with
        ``pin_bytes=0`` the pin is transient: the budget sweep spills
        the chain straight through to host RAM); session chains always
        pin (the session handle is the release)."""
        sid = req.session
        if sid is not None and sid not in self._sessions:
            # the session closed while this request was in flight — a
            # sid-tagged pin now would leak (no future close_session
            # releases it); degrade to an ordinary budget-governed pin
            sid = None
        if self._hc is None or (sid is None
                                and self._hc.pin_blocks <= 0
                                and self._hc.host_blocks <= 0):
            return
        pages = self._slot_pages[row]
        res = self._results.get(req.rid)
        if not pages or res is None:
            return
        seq = [int(t) for t in onp.asarray(res.asnumpy())[0]]
        # the LAST token's K/V may be unwritten (it is never fed back),
        # so only pages fully below len(seq)-1 are complete
        fullp = min((len(seq) - 1) // self._bs, len(pages))
        if fullp <= 0:
            return
        self._prefix.register(seq, pages[:fullp])
        tokens = tuple(seq[:fullp * self._bs])
        chain_pages, _ = self._prefix.lookup(tokens, limit=len(tokens))
        if len(chain_pages) < fullp:
            return                      # raced an eviction: nothing to pin
        self._hc.pin_chain(tokens, chain_pages, sid=sid)
        self._enforce_pin_budget()

    def close_session(self, sid) -> int:
        """Release one conversation's pinned chain from BOTH tiers
        (device pins unpin — pages free unless shared — and host
        copies drop).  Unknown sids are a no-op; in-flight requests of
        the session keep their own page references and are unaffected.
        Returns the number of device pages freed."""
        self._sessions.pop(sid, None)
        if self._hc is None:
            return 0
        return self._hc.close_session(sid)

    def prefix_probe(self, prompt_ids) -> int:
        """Paged locality probe (base docstring): the radix walk's hit
        length plus — when a spilled chain would beat it — the host
        tier's page-aligned match.  Read-only: no refcounts, no LRU
        ticks, no restores; a router may call it on every replica per
        dispatch."""
        arr = prompt_ids.asnumpy() if isinstance(prompt_ids, NDArray) \
            else onp.asarray(prompt_ids)
        if arr.ndim != 2 or arr.shape[0] != 1:
            raise ValueError("prefix_probe takes ONE prompt: (1, T), "
                             "got %r" % (arr.shape,))
        if self._dec._block_has_moe():
            return 0            # MoE opts out of sharing entirely
        Tp = arr.shape[1]
        n = self._prefix.probe(arr[0], limit=Tp - 1)
        if self._hc is not None and self._hc.host_chains:
            m = self._hc.host_match(arr[0], limit=Tp - 1)
            if m is not None:
                n = max(n, m[1] * self._bs)
        return n

    def drop_cache(self) -> int:
        """Release BOTH cache tiers and every open session (base
        docstring — the replica-death drain path).  Pinned chains drop
        without a host copy (a dead replica's host arrays die with it),
        sessions close, and the prefix index entries evict through the
        pool's on_free hook as the pages return."""
        self._sessions.clear()
        self._swap_pending = None
        self._swap_attempted.clear()
        if self._hc is None:
            return 0
        freed = 0
        for chain in list(self._hc._chains.values()):
            before = self._bp.free_count
            self._hc.drop_chain(chain)
            freed += self._bp.free_count - before
        for host in list(self._hc._host.values()):
            self._hc.drop_host(host)
        return freed

    def _release_row(self, row):
        """Drop row's page references (idempotent — every terminal path
        funnels here); last-reference pages return to the free list and
        evict their prefix-index entries via the pool's on_free hook."""
        pages = self._slot_pages[row]
        if pages is None:
            return
        self._slot_pages[row] = None
        for bid in pages:
            self._bp.release(bid)

    def _scrub_row(self, row):
        super()._scrub_row(row)
        self._release_row(row)

    def _finish(self, slot_idx_or_none, req, emitted, row, status="ok"):
        super()._finish(slot_idx_or_none, req, emitted, row, status)
        # every terminal path funnels here: a deferred-swap rid that
        # ends (cancel, deadline, shed-fail) must not pin the
        # attempted-set forever
        self._swap_attempted.discard(req.rid)
        if slot_idx_or_none is not None:
            if status == "ok" and self._hierarchy_on():
                # pin BEFORE the release below so the chain's pages
                # never transiently free
                self._offer_chain(row, req)
            self._release_row(row)

    def _table_row(self, row):
        t = onp.full((self._M,), NULL_PAGE, onp.int32)
        pages = self._slot_pages[row]
        if pages:
            t[:len(pages)] = pages
        return t

    # -- admission -------------------------------------------------------
    def _plan_chunks(self, start, Tp, bucketing):
        """Chunk schedule over prompt positions [start, Tp): compiled
        chunk shapes stay on the power-of-two ladder (≤ prefill_chunk),
        and a shape whose bucket padding would spill past the slot
        extent (ceil(max_length / bs) pages — the slot engine's
        reservation) descends the ladder instead, ingesting fewer
        tokens that round: padding never inflates a request's page
        need beyond slot parity, so anything the slot engine admits at
        this max_length fits the pool too (only a mid-prefix shared
        start can still spill, by at most one page — the 8-token
        bucket floor).  Returns the schedule and the padded extent
        (the last position any chunk's padding writes — allocation
        must cover it)."""
        cap = -(-self._max_length // self._bs) * self._bs
        chunks, extent = [], 0
        while start < Tp:
            rem = Tp - start
            if bucketing:
                Tb = min(_bucket(rem), self._chunk)
                while Tb > 8 and start + Tb > cap:
                    Tb //= 2
                Tact = min(rem, Tb)
            else:
                Tact = Tb = min(rem, self._chunk)
            chunks.append((start, Tact, Tb))
            extent = max(extent, start + Tb)
            start += Tact
        return chunks, extent

    def _pages_needed(self, Tp, max_new):
        """Worst-case (share-nothing) page count for one request —
        the submit()-time feasibility bound."""
        _, extent = self._plan_chunks(
            0, Tp, self._dec._bucket_prefill
            and not self._dec._block_has_moe())
        return -(-max(Tp + max_new, extent) // self._bs)

    def submit(self, prompt_ids, max_new_tokens, temperature=0.0,
               top_k=0, top_p=0.0, repetition_penalty=1.0, seed=None,
               eos_id=None, deadline_s=None, retries=0,
               speculative=None, session=None, spec_tree=None) -> int:
        """Same contract as the slot engine's submit(); additionally a
        request whose worst-case page need exceeds the WHOLE pool can
        never be admitted and sheds immediately with LoadShedError
        (transient exhaustion — pages held by live requests — defers
        admission instead, it never sheds).

        ``session``: a conversation handle (any hashable).  The
        finished request's full-page chain stays PINNED so the next
        turn — whose prompt is this turn's transcript plus the new
        message — prefills only the new suffix; ``close_session``
        releases it (docs/inference.md "Hierarchical prefix cache").
        Pinning requires prefix sharing, so MoE blocks reject the
        knob (their prefix K/V is not donor-independent)."""
        if session is not None and self._dec._block_has_moe():
            raise ValueError(
                "submit(session=...) is unsupported for MoE blocks: "
                "they opt out of prefix sharing (expert capacity "
                "budgets from the FULL prompt length), and a chain "
                "that cannot be shared cannot be reused across turns")
        pids = prompt_ids if isinstance(prompt_ids, NDArray) \
            else nd_array(prompt_ids)
        if pids.ndim == 2 and pids.shape[0] == 1:
            need = self._pages_needed(pids.shape[1],
                                      int(max_new_tokens))
            if need > self._bp.capacity:
                self._shed += 1
                _bump("shed_requests")
                self._emit("engine.shed", None, pages_needed=need,
                           pool_capacity=self._bp.capacity)
                self._flight_failure("shed", pages_needed=need,
                                     pool_capacity=self._bp.capacity)
                raise LoadShedError(
                    "request needs %d page(s) > pool capacity %d "
                    "(block_size=%d): can never be admitted — shed"
                    % (need, self._bp.capacity, self._bs),
                    queue_depth=len(self._queue), limit=self._bp.capacity,
                    retry_after_ticks=None, permanent=True)
        rid = super().submit(pids, max_new_tokens, temperature, top_k,
                             top_p, repetition_penalty, seed, eos_id,
                             deadline_s, retries, speculative,
                             session=session, spec_tree=spec_tree)
        if session is not None:
            self._sessions[session] = \
                self._sessions.get(session, 0) + 1
        return rid

    def _admit(self, req, slot_idx):
        """Paged admission: prefix lookup + page allocation + chunk
        schedule; the FIRST chunk (with the copy-on-write fold) runs
        immediately, so a prompt no longer than one chunk completes
        admission in this iteration exactly like the slot engine."""
        _inject("serving.admit", key=req.rid)
        Tp = req.prompt.shape[1]
        self._emit("engine.admit", req.rid, prompt_tokens=Tp)
        moe = self._dec._block_has_moe()
        bucketing = self._dec._bucket_prefill and not moe
        full, partial = [], None
        if not moe:
            # MoE prefixes are not donor-independent (expert capacity
            # budgets from the FULL prompt length) — no sharing
            _inject("serving.prefix_lookup", key=req.rid)
            full, partial = self._prefix.lookup(req.prompt[0],
                                                limit=Tp - 1)
            if self._overlap_swaps:
                # overlapped mode: restores run ONLY at the iteration
                # boundary (_service_pending_swap) — a cold-chain
                # admission defers once, the decode step runs first,
                # and the next iteration's lookup sees the restored
                # pages in the device index like any other hit
                if (req.rid not in self._swap_attempted
                        and self._hc is not None
                        and self._hc.host_chains):
                    m = self._hc.host_match(req.prompt[0], limit=Tp - 1)
                    if m is not None and m[1] > len(full):
                        self._swap_pending = req
                        raise _AdmissionDeferred()
            elif self._try_swap_in(req, full):
                # re-walk the index whenever the swap-in path touched
                # the pool: a restore ADDS pages, and the reclaim
                # inside a restore attempt (even a failed one) may have
                # FREED pages the first walk returned — the stale list
                # must never reach retain()
                full, partial = self._prefix.lookup(req.prompt[0],
                                                    limit=Tp - 1)
        n_shared = len(full) * self._bs + (partial[1] if partial else 0)
        chunks, extent = self._plan_chunks(n_shared, Tp, bucketing)
        n_pages = -(-max(Tp + req.max_new_tokens, extent) // self._bs)
        need = n_pages - len(full)
        _inject("serving.block_alloc", key=req.rid)
        # hold the matched pages (and the COW donor) across the pinned-
        # tier reclaim: spilling a chain frees pages whose only ref is
        # its pin, and the lookup results above must not be among them
        held = list(full) + ([partial[0]] if partial else [])
        for bid in held:
            self._bp.retain(bid)
        try:
            if need > self._bp.free_count:
                self._reclaim(need - self._bp.free_count)
            if need > self._bp.free_count:
                raise _AdmissionDeferred()
            fresh = self._bp.alloc(need)
        except BaseException:
            for bid in held:
                self._bp.release(bid)
            raise
        if partial:
            # the donor hold only had to span the reclaim — the COW
            # copy runs inside this admission's first chunk, before any
            # other request could release it
            self._bp.release(partial[0])
        pages = list(full) + fresh
        # the holds on `full` stay: they ARE this table's references
        self._slot_pages[slot_idx] = pages   # release path armed NOW
        if full or partial:
            self._prefix_hits += 1
        # hit accounting only AFTER a successful allocation: a deferred
        # admission retries this whole path every iteration and must
        # not re-count the same hit (the bench's headline metric)
        if n_shared:
            self._emit("engine.prefix_hit", req.rid, tokens=n_shared,
                       pages=len(full),
                       session=req.session is not None)
            self._prefill_tokens_avoided += n_shared
            if self._hc is not None:
                self._hc.touch_prefix(req.prompt[0], Tp - 1)
            if req.session is not None:
                self._session_hits += 1
        cow = None
        if partial:
            cow = (partial[0], pages[len(full)])
            self._emit("engine.cow", req.rid, src=int(partial[0]),
                       dst=int(pages[len(full)]))
            self._cow_copies += 1
        slot = _PagedSlot(req, slot_idx, Tp, chunks, cow)
        slot.param_gen = self._param_gen
        self._slots[slot_idx] = slot
        self._status[req.rid] = "active"
        self._swap_attempted.discard(req.rid)   # bounded bookkeeping
        try:
            # the prompt's first chunk: prefill work inside the
            # admission's engine.schedule, so it is its child
            with _tracer().span("engine.prefill"):
                self._advance_prefill(slot_idx)
        except Exception:
            # the caller's quarantine path expects a FAILED admission
            # never to occupy the slot (the slot-engine invariant)
            self._slots[slot_idx] = None
            raise

    def _advance_prefill(self, slot_idx):
        """Run ONE prefill chunk for a prefilling slot; the final chunk
        samples the first token (mirroring the slot engine's admission
        tail bit-for-bit: seed applied AFTER prefill, first draw from
        the prompt's last real logit row) and registers the prompt's
        full pages in the prefix index."""
        from ..models.sampler import sample_next_token

        slot = self._slots[slot_idx]
        req = slot.req
        start, Tact, Tb = slot.chunks[slot.chunk_i]
        self._emit("engine.prefill_chunk", req.rid, index=slot.chunk_i,
                   start=start, tokens=Tact)
        raw = jnp.asarray(req.prompt[:, start:start + Tact], jnp.int32)
        if Tb > Tact:
            raw = jnp.pad(raw, ((0, 0), (0, Tb - Tact)))
        if slot.cow is not None:
            san = _sanitizer()
            if san is not None:              # V002/V003 COW gate
                san.note_cow(self._bp, slot.cow[0], slot.cow[1])
        src, dst = slot.cow if slot.cow is not None else (0, 0)
        slot.cow = None                      # COW runs exactly once
        moe = self._dec._block_has_moe()
        logits, self._pool = self._dec._run(
            "page_prefill", self._pool, raw,
            jnp.asarray(self._table_row(slot_idx)),
            jnp.int32(start), jnp.int32(src), jnp.int32(dst),
            total_len=(slot.Tp if moe else None))
        self._prefill_tokens += Tact
        slot.chunk_i += 1
        if slot.chunk_i < len(slot.chunks):
            return                           # more chunks next iteration
        # -- prefill complete: the slot-engine admission tail ------------
        Tp = slot.Tp
        last = logits[:, Tp - 1 - start]               # (1, V)
        keys = None
        if req.seed is not None and req.sampled:
            # seed AFTER prefill — the ordering generate() guarantees
            keys = _slot_keys(req.seed)
        elif req.sampled:
            keys = _slot_keys(onp.random.randint(0, 2**31 - 1))
        self._ensure_seen(last.shape[-1])
        if req.penalized:
            row = jnp.zeros((last.shape[-1],), bool).at[
                jnp.asarray(req.prompt[0], jnp.int32)].set(True)
            self._seen = self._seen.at[slot_idx].set(row)
        tok = sample_next_token(
            last, keys.next_key() if req.sampled else None,
            req.temperature, req.top_k, req.top_p,
            req.repetition_penalty,
            seen_mask=self._seen[slot_idx:slot_idx + 1]
            if req.penalized else None)
        tok = tok.astype(jnp.int32)                    # (1,)
        if req.penalized:
            self._seen = self._seen.at[slot_idx, tok[0]].set(True)
        if self._last_tokens is None:
            self._last_tokens = jnp.zeros((self._num_slots,), jnp.int32)
        self._last_tokens = self._last_tokens.at[slot_idx].set(tok[0])
        slot.pos = Tp
        slot.keys = keys
        slot.emitted = [self._last_tokens]
        slot.n_emitted = 1
        self._arm_speculation(slot, req, tok[0])
        if not moe:
            # prompt pages fully below Tp are now immutable: decode
            # writes land at >= Tp, chunk padding past Tp never touches
            # them — future prompts may share them
            self._prefix.register(req.prompt[0],
                                  self._slot_pages[slot_idx][:Tp
                                                             // self._bs])
        if self._slot_done(slot):
            self._finish(slot_idx, req, slot.emitted, slot_idx)

    # -- speculative decoding hooks (paged forms) ------------------------
    def _spec_extent(self, slot):
        """Token capacity of the slot's allocated page chain — drafted
        windows clamp here, so a verify write can NEVER need a page the
        slot does not already own (rollback stays a position fix-up)."""
        pages = self._slot_pages[slot.row]
        return len(pages) * self._bs if pages else 0

    def _decode_state(self, active):
        pos = onp.zeros((self._num_slots,), onp.int32)
        tables = onp.zeros((self._num_slots, self._M), onp.int32)
        for i in active:
            pos[i] = self._slots[i].pos
            tables[i] = self._table_row(i)
        return pos, tables

    def _run_step(self, state):
        pos, tables = state
        logits, self._pool = self._dec._run(
            "step_pages", self._pool, self._last_tokens.reshape(-1, 1),
            jnp.asarray(tables), jnp.asarray(pos))
        return logits

    def _run_verify(self, state, window, valid_len):
        pos, tables = state
        logits, self._pool = self._dec._run(
            "verify_pages", self._pool, window, jnp.asarray(tables),
            jnp.asarray(pos), jnp.asarray(valid_len))
        return logits

    def _run_verify_tree(self, state, window, valid_len, perm, depth,
                         anc):
        pos, tables = state
        logits, self._pool = self._dec._run(
            "verify_tree_pages", self._pool, window, jnp.asarray(tables),
            jnp.asarray(pos), jnp.asarray(valid_len), jnp.asarray(perm),
            jnp.asarray(depth), jnp.asarray(anc))
        return logits

    def _run_fixup(self, state, src_lane):
        pos, tables = state
        self._pool = self._dec._run(
            "fixup_pages", self._pool, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(src_lane))

    # -- one scheduler iteration ----------------------------------------
    def _step_impl(self):
        """One iteration: deadline sweep, admissions (deferring at the
        queue head on transient page exhaustion), ONE prefill chunk per
        prefilling slot, then ONE pooled paged decode step — or batched
        verify call — over every DECODING slot.  Same per-slot failure
        containment as the slot engine; chunk-prefill faults quarantine
        under the admission site.  (``step()`` wraps this in the
        ``engine.iteration`` trace span — base class.)"""
        finished_before = set(self._results)
        tr = _tracer()
        with tr.span("engine.schedule"):
            self._evict_expired()
            self._maybe_install_adoption()
        # chunked prefill FIRST: slots already prefilling advance one
        # chunk per iteration, interleaved with (never stalling) the
        # decode step below; slots admitted later this iteration ran
        # their first chunk inside _admit and wait for the next one
        prefilling = [i for i, s in enumerate(self._slots)
                      if s is not None and s.prefilling]
        if prefilling:
            with tr.span("engine.prefill"):
                for i in prefilling:
                    try:
                        self._advance_prefill(i)
                    except Exception as exc:
                        self._quarantine(i, exc, "serving.admit")
        # (the second engine.schedule of the iteration: admissions, each
        # running its prompt's first chunk inside _admit)
        with tr.span("engine.schedule"):
            if self._queue and self._staged_adoption is None:
                self._ensure_pool(nd_array(self._queue[0].prompt))
            self._admit_queued()

        active = [i for i, s in enumerate(self._slots)
                  if s is not None and not s.prefilling]
        # hot-swap invariant (base _step_impl docstring): decoding
        # slots ride their admission-pinned weight generation
        assert all(self._slots[i].param_gen == self._param_gen
                   for i in active), "slot outlived a weight install"
        for i in list(active):
            try:
                _inject("serving.step", key=self._slots[i].req.rid)
            except Exception as exc:
                self._quarantine(i, exc, "serving.step")
                active.remove(i)
        self._decode_step(active)
        self._service_pending_swap()
        return [r for r in self._results if r not in finished_before]

    def _admit_queued(self):
        """The base engine's admission loop, deferring at the queue head
        on transient page exhaustion."""
        deferred = False
        for i in range(self._num_slots):
            if not self._queue or deferred \
                    or self._staged_adoption is not None:
                break
            if self._slots[i] is None:
                req = self._queue.pop(0)
                if req.max_new_tokens <= 0:
                    self._finish(None, req, [], 0)
                    continue
                try:
                    self._admit(req, i)
                except _AdmissionDeferred:
                    # FIFO preserved: the request stays at the head and
                    # no later request jumps it into the freed pages
                    self._emit("engine.defer", req.rid,
                               free_pages=self._bp.free_count)
                    self._queue.insert(0, req)
                    deferred = True
                except Exception as exc:
                    self._quarantine_request(req, exc, "serving.admit",
                                             row=i)

    def _service_pending_swap(self):
        """Iteration-boundary tail of ``overlap_swaps=True``: run the
        host-tier restore a cold-chain admission deferred — AFTER the
        pooled decode step above, so in-flight streams already emitted
        this iteration's tokens (no token gap; asserted by counters in
        tests).  The deferred request sits back at the queue head; the
        next iteration's admission re-walks the device index and shares
        the restored pages like any other prefix hit.  A
        ``serving.swap_in`` fault here quarantines only the deferred
        request (retries re-defer and re-attempt the restore,
        bit-identically); each rid defers at most once per attempt, so
        run()'s convergence guard holds."""
        req = self._swap_pending
        if req is None:
            return
        self._swap_pending = None
        self._swap_attempted.add(req.rid)
        if all(q.rid != req.rid for q in self._queue):
            return      # evicted (deadline/cancel) while deferred
        full, _ = self._prefix.lookup(req.prompt[0],
                                      limit=req.prompt.shape[1] - 1)
        try:
            if self._try_swap_in(req, full):
                self._deferred_swap_ins += 1
        except Exception as exc:
            # the admission-fault contract, minus the row scrub —
            # nothing was allocated to a row yet (the request never
            # left the queue)
            self._queue = [q for q in self._queue if q.rid != req.rid]
            self._swap_attempted.discard(req.rid)  # retries re-attempt
            self._quarantined += 1
            _bump("quarantined_slots")
            self._requeue_or_fail(req, exc, "serving.admit")

    # -- drain -----------------------------------------------------------
    def run(self):
        """Drain the queue and every active slot; returns {request id →
        (1, T_prompt + generated) NDArray}.  The non-convergence guard
        additionally budgets the prefill-chunk iterations and the
        page-exhaustion admission deferrals (bounded: a deferred
        request waits only on in-flight requests, which emit every
        iteration)."""
        def iters(req, emitted_n=0):
            chunks = -(-req.prompt.shape[1] // self._chunk)
            return (1 + req.retries_left) * (
                req.max_new_tokens + chunks) - emitted_n

        outstanding = sum(iters(r) for r in self._queue) + sum(
            iters(s.req, s.n_emitted)
            for s in self._slots if s is not None)
        limit = 4 * (outstanding + len(self._queue)
                     + self._num_slots + 1) + \
            2 * self._bp.capacity
        guard = 0
        while self._queue or any(s is not None for s in self._slots):
            self.step()
            guard += 1
            if guard > limit:
                raise RuntimeError(
                    "paged continuous-batching run() failed to "
                    "converge — scheduler bug (slots: %r, free pages: "
                    "%d)" % (self._slots, self._bp.free_count))
        out, self._results = self._results, {}
        return out
