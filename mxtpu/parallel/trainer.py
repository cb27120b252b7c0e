"""SPMDTrainer: the whole training step as ONE compiled SPMD program.

Parity map (SURVEY §3.3): the reference's Trainer.step pipeline —
allreduce_grads through KVStore (engine ops → NCCL/ps-lite) then per-param
optimizer update ops — becomes a single jitted function over the device
mesh: forward + backward + gradient sync (XLA-inserted collectives over the
"dp" axis) + optimizer update, with parameter/optimizer-state shardings
given by ShardingRules (tp) and batch sharding over dp/sp.  The
`update_on_kvstore` question dissolves: the update happens wherever XLA
placed the shard (ZeRO-flavored when states are sharded).

This is the TPU-native training path; gluon.Trainer + KVStore remains for
API parity and single-chip use.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import autograd, ndarray as nd, optimizer as opt_mod
from .. import random as _random
from ..gluon.block import remat_scope
from ..ndarray import NDArray
from ..observability.trace import get_tracer as _tracer
from ..ops.pallas.partition import head_sharding_scope
from .mesh import DeviceMesh
from .sharding import ShardingRules

__all__ = ["SPMDTrainer", "TrainWindow"]


class TrainWindow(NamedTuple):
    """Result of one fused N-step window (:meth:`SPMDTrainer.step_window`).

    losses : (N,) device NDArray of per-step scalar losses — still
        async; ``.asnumpy()`` blocks.  A skipped step's loss is the
        non-finite value that triggered the skip (same as the per-step
        path returns).
    ok : host bool ndarray (N,) of per-step finiteness verdicts for
        guarded trainers (reading it is the window's ONE host sync);
        None when unguarded.
    num_good : steps whose update actually applied (== N unguarded).
    """

    losses: Any
    ok: Any
    num_good: int


class SPMDTrainer:
    """Compiles (block, loss, optimizer) into a sharded train step.

    Parameters
    ----------
    block : gluon.Block — initialized (params must have shapes; run one
        forward on a sample batch first if any shape is deferred).
    loss_fn : gluon.loss.Loss or callable(NDArray pred, NDArray label) →
        per-sample NDArray loss.
    optimizer : str or mxtpu Optimizer.
    mesh : DeviceMesh.
    rules : ShardingRules for parameters (default: replicate everything —
        pure data parallel).
    batch_spec / label_spec : PartitionSpec for the data arrays (default
        shard batch dim over "dp"; add "sp" on the sequence dim for
        sequence parallelism).
    remat : recompute each unit of the model in the backward pass
        (``jax.checkpoint`` around every block that sets ``remat_unit``:
        the model zoo's encoder and decoder layers, halves of a layer in
        ``KimiLinearLM``), keeping the unit's input and nothing inside
        it: FLOPs for HBM.  A model without such a block is unchanged.
    donate : donate old param/state buffers (in-place update on device).
    clip_gradient_norm : optional global-norm gradient clip fused into
        the compiled step (parity: gluon.utils.clip_global_norm); the
        norm reduces over ALL parameter shards on-device.
    guard : in-step divergence containment (docs/guardian.md): the
        compiled step additionally reduces an on-device finiteness check
        over loss + every gradient shard and applies the update under a
        ``lax.cond`` gate — a non-finite step leaves params and optimizer
        state bit-identical to not having run it, in the SAME compiled
        program (no recompile on the skip path).  Costs one small host
        sync per step (the ``ok`` scalar, read into
        ``self.last_step_ok``).  Default: the ``MXTPU_GUARDIAN`` env
        var.
    dynamic_loss_scale : fp16-style dynamic loss scaling fused into the
        guarded step (implies ``guard``): the loss is scaled by a traced
        device scalar, grads unscaled before clip/update, and the
        grow/backoff automaton (x ``loss_scale_factor`` after
        ``loss_scale_window`` clean steps, / on overflow, floor 1.0)
        runs on device inside the same program — replacing the
        reference's per-param host ``asnumpy()`` overflow loop.
    """

    def __init__(self, block, loss_fn, optimizer, mesh: DeviceMesh,
                 rules: Optional[ShardingRules] = None,
                 optimizer_params: Optional[dict] = None,
                 batch_spec: P = P("dp"), label_spec: P = P("dp"),
                 remat: bool = False, donate: bool = True,
                 clip_gradient_norm: Optional[float] = None,
                 guard: Optional[bool] = None,
                 dynamic_loss_scale: bool = False,
                 loss_scale_init: float = 2.0 ** 16,
                 loss_scale_factor: float = 2.0,
                 loss_scale_window: int = 2000):
        self._block = block
        self._loss_fn = loss_fn
        self._mesh = mesh
        self._rules = rules or ShardingRules()
        self._batch_spec = batch_spec
        self._label_spec = label_spec
        self._remat = remat
        self._donate = donate
        self._clip_norm = (float(clip_gradient_norm)
                           if clip_gradient_norm is not None else None)
        if guard is None:
            from ..resilience.guardian import guard_enabled_default
            guard = dynamic_loss_scale or guard_enabled_default()
        self._guard = bool(guard) or bool(dynamic_loss_scale)
        self._dyn_scale = bool(dynamic_loss_scale)
        self._scale_cfg = (float(loss_scale_init), float(loss_scale_factor),
                           int(loss_scale_window))
        self._scale_state = None  # (scale f32, clean-step count i32) device
        self.last_step_ok = True  # verdict of the most recent guarded step
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        cls = type(optimizer)
        if (cls._step is opt_mod.Optimizer._step
                and cls._step_t is opt_mod.Optimizer._step_t):
            raise ValueError(
                "SPMDTrainer requires an optimizer with a pure _step/_step_t "
                "(sgd/adam/adamw/lamb/...); %s updates statefully — use "
                "gluon.Trainer for it" % cls.__name__)
        self._optimizer = optimizer
        self._num_update = 0
        self._params_sharded = False
        self._input_shardings = None  # cached in step()
        self._window_input_shardings = None  # cached in step_window()
        self._diff_params: List = []
        self._aux_params: List = []
        self._opt_states: List = []
        self._jit_cache: Dict[Any, Any] = {}

    # -- parameter staging ----------------------------------------------
    def _stage_params(self):
        """Collect block params, device_put per sharding rules, create
        optimizer state with matching sharding."""
        params = sorted(self._block.collect_params().values(),
                        key=lambda p: p.name)
        self._diff_params = [p for p in params if p.grad_req != "null"]
        self._aux_params = [p for p in params if p.grad_req == "null"]
        jm = self._mesh.jax_mesh
        for p in self._diff_params:
            # the step takes its gradients inside the compiled program:
            # the eager buffers would be one more copy of the model on
            # the device that nothing reads
            p.release_grad()
        for p in self._diff_params + self._aux_params:
            holder = p.data()
            sh = self._rules.sharding_for(p.name, holder.ndim, self._mesh) \
                if p in self._diff_params else NamedSharding(jm, P())
            holder._rebind(jax.device_put(holder._data, sh))
        self._opt_states = []
        for i, p in enumerate(self._diff_params):
            st = self._optimizer.create_state(i, p.data())
            # start every state leaf in the dtype the update rule hands
            # it back in (the f32 learning rate promotes a bf16 momentum
            # to f32): a state whose dtype changed after step 1 would
            # compile the whole step program a second time.  Widening
            # the initial value is exact.
            after = jax.eval_shape(
                lambda w, s, _i=i: self._optimizer._step_t(
                    w, w, s, jnp.float32(0.0), self._optimizer._get_wd(_i),
                    jnp.float32(1.0))[1], p.data()._data, st)
            st = jax.tree_util.tree_map(
                lambda a, to: a.astype(to.dtype), st, after)
            st = jax.tree_util.tree_map(
                lambda a, _p=p: jax.device_put(
                    a, NamedSharding(jm, self._rules.spec_for(
                        _p.name, getattr(a, "ndim", 0)))), st)
            self._opt_states.append(st)
        self._params_sharded = True

    # -- the compiled step ----------------------------------------------
    def _make_step_fns(self):
        """The pure step bodies shared by the per-step program
        (:meth:`_build_step`) and the fused N-step scan program
        (:meth:`_build_multi_step`) — built once per compile so both
        capture captures (wds, clip norm, guard flags) identically."""
        block = self._block
        loss_fn = self._loss_fn
        diff_params = self._diff_params
        aux_params = self._aux_params
        optimizer = self._optimizer
        clip_norm = self._clip_norm
        mesh = self._mesh
        remat = self._remat
        batch_axes = self._batch_spec[0] if len(self._batch_spec) else ()
        batch_axes = ((batch_axes,) if isinstance(batch_axes, str)
                      else tuple(batch_axes or ()))
        # attention heads follow the QKV projections: split over the
        # axes the rules shard parameters over (never a batch axis)
        heads_axes = tuple(a for a in self._rules.axes()
                           if a not in batch_axes)
        wds = [self._optimizer._get_wd(i)
               for i in range(len(diff_params))]

        def forward(diff_leaves, aux_leaves, key, batch, label):
            saved = []
            for p, leaf in list(zip(diff_params, diff_leaves)) + list(
                    zip(aux_params, aux_leaves)):
                holder = p.data()
                saved.append((holder, holder._data))
                holder._data = leaf
            _random.push_trace_key(key)
            try:
                # Pallas attention kernels split themselves over the
                # batch axes and the heads axes (GSPMD cannot partition
                # a Mosaic kernel — ops/pallas/partition.py)
                with autograd.pause(train_mode=True), \
                        head_sharding_scope(mesh, heads_axes, batch_axes), \
                        remat_scope(remat):
                    out = block(NDArray(batch))
                    # multi-output blocks: by default the loss sees the
                    # FIRST output; a loss with accepts_full_output=True
                    # receives the whole tuple (e.g. MoE auxiliary
                    # load-balancing terms threaded through outputs)
                    if isinstance(out, tuple) and not getattr(
                            loss_fn, "accepts_full_output", False):
                        out = out[0]
                    loss = loss_fn(out, NDArray(label))
                    loss_scalar = loss.mean()._data
                new_aux = tuple(p.data()._data for p in aux_params)
            finally:
                _random.pop_trace_key()
                for holder, data in saved:
                    holder._data = data
            return loss_scalar, new_aux

        guard = self._guard
        dyn_scale = self._dyn_scale
        _, scale_factor, scale_window = self._scale_cfg

        def clip(grads):
            if clip_norm is None:
                return grads
            # global-norm clipping fused into the step (parity:
            # gluon.utils.clip_global_norm, but on-device over the
            # sharded grads — XLA reduces across the mesh for free)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in grads))
            scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))
            return [g * scale.astype(g.dtype) for g in grads]

        def update(diff_leaves, grads, opt_states, lr, t):
            new_leaves = []
            new_states = []
            for leaf, g, st, wd in zip(diff_leaves, grads, opt_states, wds):
                # _step_t: step count traced on device, so t-dependent rules
                # (Adam bias correction, LAMB) need no host special-casing
                w, s = optimizer._step_t(leaf, g, st, lr, wd, t)
                new_leaves.append(w.astype(leaf.dtype))
                new_states.append(s)
            return new_leaves, new_states

        def step(diff_leaves, aux_leaves, opt_states, lr, t, batch, label,
                 key):
            def loss_of(dl):
                return forward(dl, aux_leaves, key, batch, label)

            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_leaves)
            grads = clip(grads)
            new_leaves, new_states = update(diff_leaves, grads, opt_states,
                                            lr, t)
            return tuple(new_leaves), new_aux, tuple(new_states), loss

        def guarded_step(diff_leaves, aux_leaves, opt_states, lr, t, batch,
                         label, key, scale_state):
            scale, clean = scale_state

            def loss_of(dl):
                loss, aux = forward(dl, aux_leaves, key, batch, label)
                scaled = loss * scale.astype(loss.dtype) if dyn_scale \
                    else loss
                return scaled, (loss, aux)

            (_, (loss, aux_out)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_leaves)
            # fused finiteness reduction over loss + EVERY gradient shard
            # (the multi_all_finite rule, on the scaled grads so fp16
            # overflow is caught before unscaling) — ONE device scalar,
            # one host sync, instead of a per-param asnumpy() loop
            ok = jnp.isfinite(loss.astype(jnp.float32))
            for g in grads:
                ok = ok & jnp.all(jnp.isfinite(g.astype(jnp.float32)))
            if dyn_scale:
                inv = jnp.float32(1.0) / scale
                grads = [(g.astype(jnp.float32) * inv).astype(g.dtype)
                         for g in grads]

            # the containment gate: lax.cond, not where — XLA executes
            # only the taken branch, so a healthy step pays no extra
            # parameter traffic and a non-finite step passes the OLD
            # buffers through everywhere — params, optimizer state, aux
            # (running stats) — bit-identical to not having stepped, in
            # this same program (no recompile on the skip path)
            def take(_):
                cg = clip(grads)
                nl, ns = update(diff_leaves, cg, opt_states, lr, t)
                return tuple(nl), tuple(aux_out), tuple(ns)

            def keep(_):
                return (tuple(diff_leaves), tuple(aux_leaves),
                        tuple(opt_states))

            new_leaves, new_aux, new_states = jax.lax.cond(
                ok, take, keep, None)
            if dyn_scale:
                # grow/backoff automaton, on device: clean steps count up
                # to the window then double the scale; overflow halves it
                # (floor 1.0) and resets the count
                grown = clean + 1
                do_grow = grown >= scale_window
                new_scale = jnp.where(
                    ok, jnp.where(do_grow, scale * scale_factor, scale),
                    jnp.maximum(jnp.float32(1.0), scale / scale_factor))
                new_clean = jnp.where(
                    ok, jnp.where(do_grow, 0, grown), 0)
            else:
                new_scale, new_clean = scale, clean
            return (tuple(new_leaves), new_aux, tuple(new_states), loss,
                    ok, (new_scale, new_clean))

        return step, guarded_step

    def _shardings(self):
        """(diff, aux, opt-state, replicated) NamedSharding tuples for
        the staged parameters — the common part of both programs'
        in/out_shardings."""
        jm = self._mesh.jax_mesh
        rep = NamedSharding(jm, P())
        diff_sh = tuple(self._rules.sharding_for(p.name, p.data().ndim,
                                                 self._mesh)
                        for p in self._diff_params)
        aux_sh = tuple(rep for _ in self._aux_params)
        state_sh = tuple(
            jax.tree_util.tree_map(
                lambda a: NamedSharding(jm, self._rules.spec_for(
                    p.name, getattr(a, "ndim", 0))), st)
            for p, st in zip(self._diff_params, self._opt_states))
        return diff_sh, aux_sh, state_sh, rep

    def _build_step(self, batch_shape, batch_dtype, label_shape,
                    label_dtype):
        step, guarded_step = self._make_step_fns()
        guard = self._guard
        jm = self._mesh.jax_mesh
        diff_sh, aux_sh, state_sh, rep = self._shardings()
        in_sh = (diff_sh, aux_sh, state_sh, rep, rep,
                 NamedSharding(jm, self._batch_spec),
                 NamedSharding(jm, self._label_spec), rep)
        out_sh = (diff_sh, aux_sh, state_sh, rep)
        if guard:
            in_sh = in_sh + ((rep, rep),)
            out_sh = out_sh + (rep, (rep, rep))
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(guarded_step if guard else step,
                       in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    def _build_multi_step(self, n, batch_shape, batch_dtype, label_shape,
                          label_dtype):
        """Compile N steps as ONE ``lax.scan`` program (docs/training.md).

        The scan body is the SAME guarded/unguarded step closure the
        per-step program compiles, so a window's per-step math — the
        finiteness gate, loss scaling, clipping, optimizer rule — is the
        per-step math by construction.  The loop state carries params,
        aux (running stats), optimizer state, the loss-scale automaton
        and a ``good`` update counter; skipped iterations pass every
        carry leaf through untouched via the same ``lax.cond`` gate.

        Per-step host bookkeeping becomes traced state:

        - ``t`` (the optimizer's traced step count) advances only on OK
          iterations: ``t0 + good + 1`` — a mid-window skip leaves the
          next iteration's bias correction exactly where the per-step
          path would.
        - the learning rate is precomputed on host for every possible
          update count in the window (``lrs[j]`` = schedule at
          ``num_update0 + j + 1``) and indexed by the carried ``good``
          counter, so lr schedules stay bit-identical under skips.

        Params, aux and optimizer state are donated (argnums 0-2):
        XLA aliases the window's inputs to its outputs and the carry
        updates in place across all N fused steps
        (``check_trainer_donation(..., n_steps=N)`` proves it)."""
        step, guarded_step = self._make_step_fns()
        guard = self._guard

        if guard:
            def multi(diff_leaves, aux_leaves, opt_states, scale_state,
                      lrs, t0, batches, labels, keys):
                def body(carry, xs):
                    diff, aux, states, sstate, good = carry
                    batch, label, key = xs
                    lr = lrs[good]
                    t = t0 + (good + 1).astype(jnp.float32)
                    nd_, na, ns, loss, ok, nss = guarded_step(
                        diff, aux, states, lr, t, batch, label, key,
                        sstate)
                    return ((nd_, na, ns, nss,
                             good + ok.astype(jnp.int32)), (loss, ok))

                init = (tuple(diff_leaves), tuple(aux_leaves),
                        tuple(opt_states), scale_state, jnp.int32(0))
                (fd, fa, fs, sstate, good), (losses, oks) = jax.lax.scan(
                    body, init, (batches, labels, keys))
                return fd, fa, fs, losses, oks, sstate, good
        else:
            def multi(diff_leaves, aux_leaves, opt_states, lrs, ts,
                      batches, labels, keys):
                def body(carry, xs):
                    diff, aux, states = carry
                    batch, label, key, lr, t = xs
                    nd_, na, ns, loss = step(diff, aux, states, lr, t,
                                             batch, label, key)
                    return (nd_, na, ns), loss

                init = (tuple(diff_leaves), tuple(aux_leaves),
                        tuple(opt_states))
                (fd, fa, fs), losses = jax.lax.scan(
                    body, init, (batches, labels, keys, lrs, ts))
                return fd, fa, fs, losses

        jm = self._mesh.jax_mesh
        diff_sh, aux_sh, state_sh, rep = self._shardings()
        stacked_b = NamedSharding(
            jm, P(*((None,) + tuple(self._batch_spec))))
        stacked_l = NamedSharding(
            jm, P(*((None,) + tuple(self._label_spec))))
        if guard:
            in_sh = (diff_sh, aux_sh, state_sh, (rep, rep), rep, rep,
                     stacked_b, stacked_l, rep)
            out_sh = (diff_sh, aux_sh, state_sh, rep, rep, (rep, rep),
                      rep)
        else:
            in_sh = (diff_sh, aux_sh, state_sh, rep, rep,
                     stacked_b, stacked_l, rep)
            out_sh = (diff_sh, aux_sh, state_sh, rep)
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(multi, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    # -- public API ------------------------------------------------------
    def _ensure_staged(self, data):
        """Stage params/optimizer state onto the mesh (idempotent).
        Where a parameter's shape is still deferred, one imperative
        forward resolves it first; a model whose shapes are all known is
        not run eagerly (op by op, every op a program of its own: 133 s
        of a cold start at 8,192 tokens through five Kimi-Linear layers,
        PERF.md PR 29)."""
        if not self._params_sharded:
            with _tracer().span("trainer.stage") as span:
                if any(p._deferred_init for p in
                       self._block.collect_params().values()):
                    with autograd.pause(train_mode=False):
                        self._block(data if isinstance(data, NDArray)
                                    else nd.array(data))
                self._stage_params()
                span.set_noise(**self._device_memory())

    def _device_memory(self):
        """``bytes_in_use`` and ``peak_bytes_in_use`` of the mesh's first
        device in this process as its allocator counts them now, for the
        end of a set-up span (``trainer.stage``, a ``trainer.step`` that
        is ``first``); nothing where the platform gives no statistics
        (the CPU)."""
        stats = self._mesh.jax_mesh.local_devices[0].memory_stats() or {}
        return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
                if k in stats}

    def step(self, data, label):
        """One optimization step on a global batch. Returns the (device)
        scalar loss NDArray; no host sync — call .asnumpy() to block.
        (Guarded trainers additionally sync the one ``ok`` scalar.)

        The call is one ``trainer.step`` boundary span (host dispatch,
        not device time): ``step`` is the update count it made, ``first``
        whether the batch signature was new (the call traced, lowered
        and compiled or fetched), ``tokens`` the batch's elements.  A
        ``first`` call also reads the device's memory at its end
        (:meth:`_device_memory`); no other call does."""
        with _tracer().span("trainer.step") as span:
            return self._step(data, label, span)

    def _step(self, data, label, span):
        self._ensure_staged(data)

        data = data if isinstance(data, NDArray) else nd.array(data)
        label = label if isinstance(label, NDArray) else nd.array(label)
        # cached input shardings: building NamedSharding objects per step
        # showed up in the round-2 blocked-latency gap (VERDICT weak #2)
        in_sh = self._input_shardings
        if in_sh is None:
            jm = self._mesh.jax_mesh
            in_sh = (NamedSharding(jm, self._batch_spec),
                     NamedSharding(jm, self._label_spec))
            self._input_shardings = in_sh
        batch = jax.device_put(data._data, in_sh[0])
        lab = jax.device_put(label._data, in_sh[1])

        sig = (tuple(batch.shape), str(batch.dtype), tuple(lab.shape),
               str(lab.dtype))
        jitted = self._jit_cache.get(sig)
        # compile-ledger report (docs/analysis.md): the compiled train
        # step is a jit site the discipline checker audits — a growing
        # batch-signature set here means data-pipeline shape churn
        from ..analysis.compile_ledger import (Signature, ledger_enabled,
                                               record)
        if ledger_enabled():
            record("spmd_trainer.step", Signature(
                shapes=(sig[0], sig[2]), dtypes=(sig[1], sig[3]),
                weak=(), static=(self._guard, self._dyn_scale)),
                hit=jitted is not None)
        first = jitted is None
        span.set(first=first, tokens=int(batch.size))
        if first:
            jitted = self._build_step(*sig)
            self._jit_cache[sig] = jitted

        self._num_update += 1
        # per-index counts only matter to the legacy Updater path; one
        # shared count dict mutated in place beats rebuilding an
        # O(n_params) dict every step
        iuc = self._optimizer._index_update_count
        for i in range(len(self._diff_params)):
            iuc[i] = self._num_update
        self._optimizer.num_update = self._num_update
        lr = jnp.float32(self._effective_lr())
        t = jnp.float32(self._num_update)

        diff_leaves = tuple(p.data()._data for p in self._diff_params)
        aux_leaves = tuple(p.data()._data for p in self._aux_params)
        if self._guard:
            if self._scale_state is None:
                self._scale_state = self._init_scale_state()
            new_leaves, new_aux, new_states, loss, ok, scale_state = \
                jitted(diff_leaves, aux_leaves, tuple(self._opt_states),
                       lr, t, batch, lab, _random.next_key(),
                       self._scale_state)
            self._scale_state = scale_state
            okb = bool(ok)  # the ONE host sync of the guarded step
            self.last_step_ok = okb
            if not okb:
                # the gate selected the old values — undo the step-count
                # advance so state is indistinguishable from not stepping
                from ..resilience.counters import bump
                bump("guardian_skips")
                self._num_update -= 1
                for i in range(len(self._diff_params)):
                    iuc[i] = self._num_update
                self._optimizer.num_update = self._num_update
        else:
            new_leaves, new_aux, new_states, loss = jitted(
                diff_leaves, aux_leaves, tuple(self._opt_states), lr, t,
                batch, lab, _random.next_key())
        for p, leaf in zip(self._diff_params, new_leaves):
            p.data()._rebind(leaf)
        for p, leaf in zip(self._aux_params, new_aux):
            p.data()._rebind(leaf)
        self._opt_states = list(new_states)
        span.set(step=self._num_update)
        if first:
            span.set_noise(**self._device_memory())
        return NDArray(loss)

    def step_program(self, data, label):
        """``(jitted, args)``: the per-step program for this batch and
        the arguments the next :meth:`step` would call it with — built
        without running anything, donating a buffer, drawing from the
        RNG ring or advancing the step count.  For looking at the
        program (``lower_step``, ``analysis.check_trainer_donation``)."""
        self._ensure_staged(data)
        data = data if isinstance(data, NDArray) else nd.array(data)
        label = label if isinstance(label, NDArray) else nd.array(label)
        sig = (tuple(data.shape), str(data._data.dtype),
               tuple(label.shape), str(label._data.dtype))
        args = [tuple(p.data()._data for p in self._diff_params),
                tuple(p.data()._data for p in self._aux_params),
                tuple(self._opt_states),
                jnp.float32(self._effective_lr()),
                jnp.float32(self._num_update + 1), data._data,
                label._data, _random.generator().peek_key()]
        if self._guard:
            args.append(self._scale_state if self._scale_state is not None
                        else self._init_scale_state())
        return self._build_step(*sig), args

    def lower_step(self, data, label):
        """The per-step program lowered for this batch
        (``jax.stages.Lowered``) — e.g. ``"tpu_custom_call" in
        trainer.lower_step(X, y).as_text()`` says a Pallas kernel is in
        the program the next :meth:`step` would run."""
        jitted, args = self.step_program(data, label)
        return jitted.lower(*args)

    def _init_scale_state(self):
        """Lazy initial (scale, clean) automaton state — the ONE
        spelling shared by step, step_window and the donation checker,
        so the window/analysis paths can never initialize a different
        automaton than the per-step path."""
        return (jnp.float32(self._scale_cfg[0] if self._dyn_scale
                            else 1.0), jnp.int32(0))

    def step_window(self, data, label, count_skips: bool = True):
        """Run N optimization steps as ONE fused ``lax.scan`` program
        (docs/training.md "Multi-step capture").

        ``data``/``label`` carry a leading window axis: shape
        ``(N,) + per_step_shape``.  The window compiles once per
        (N, shapes, dtypes) signature — ledger site
        ``spmd_trainer.step_multi`` — with params, aux and optimizer
        state donated so the carry updates in place across all N steps;
        the host dispatches one program and, for guarded trainers,
        synchronizes once per window (the per-step ``ok`` vector) instead
        of once per step.  Loss/param trajectories are bit-identical to
        N calls of :meth:`step`, including guardian skip semantics when a
        non-finite step lands mid-window (the finiteness gate folds per
        scan iteration; skipped iterations advance neither the update
        count nor the lr/bias-correction schedule).

        ``count_skips=False`` suppresses the per-skip bump of the
        process-wide ``guardian_skips`` counter: the windowed guardian
        drive passes it and counts only the skips its policy actually
        processes, so a mid-window rollback's discarded tail cannot
        drift the counter vs the per-step drive.

        Returns a :class:`TrainWindow`; ``losses`` stays async (one more
        transfer — no extra compute wait — to read).  One
        ``trainer.step`` boundary span for the whole window (``steps`` =
        N, ``step`` = the update count after it)."""
        with _tracer().span("trainer.step") as span:
            return self._step_window(data, label, count_skips, span)

    def _step_window(self, data, label, count_skips, span):
        from ..resilience.counters import bump

        data = data if isinstance(data, NDArray) else nd.array(data)
        label = label if isinstance(label, NDArray) else nd.array(label)
        if data.ndim < 1 or data.shape[0] < 1:
            raise ValueError(
                "step_window expects data with a leading window axis "
                "(N, *batch_shape) with N >= 1; got shape %r"
                % (tuple(data.shape),))
        n = int(data.shape[0])
        if label.ndim < 1 or int(label.shape[0]) != n:
            raise ValueError(
                "step_window: label window %r does not match data "
                "window %d" % (tuple(label.shape), n))
        self._ensure_staged(data[0])

        # cached stacked input shardings (same rationale as step()'s
        # _input_shardings: per-call NamedSharding construction is
        # measurable host overhead, and this is the dispatch-overhead-
        # elimination path)
        in_sh = self._window_input_shardings
        if in_sh is None:
            jm = self._mesh.jax_mesh
            in_sh = (NamedSharding(
                jm, P(*((None,) + tuple(self._batch_spec)))),
                NamedSharding(
                jm, P(*((None,) + tuple(self._label_spec)))))
            self._window_input_shardings = in_sh
        batch = jax.device_put(data._data, in_sh[0])
        lab = jax.device_put(label._data, in_sh[1])

        sig = ("multi", n, tuple(batch.shape), str(batch.dtype),
               tuple(lab.shape), str(lab.dtype))
        jitted = self._jit_cache.get(sig)
        from ..analysis.compile_ledger import (Signature, ledger_enabled,
                                               record)
        if ledger_enabled():
            record("spmd_trainer.step_multi", Signature(
                shapes=(sig[2], sig[4]), dtypes=(sig[3], sig[5]),
                weak=(), static=(n, self._guard, self._dyn_scale)),
                hit=jitted is not None)
        first = jitted is None
        span.set(first=first, tokens=int(batch.size), steps=n)
        if first:
            jitted = self._build_multi_step(n, *sig[2:])
            self._jit_cache[sig] = jitted

        # per-iteration lr ladder: lrs[j] = what _effective_lr would
        # return after the (j+1)-th successful update of this window —
        # indexed on device by the carried good-step counter so
        # schedules stay bit-identical under mid-window skips
        nu0 = self._num_update
        opt = self._optimizer
        saved_nu = opt.num_update
        lrs = []
        try:
            for j in range(n):
                opt.num_update = nu0 + j + 1
                lrs.append(float(self._effective_lr()))
        finally:
            opt.num_update = saved_nu
        lrs = jnp.asarray(lrs, jnp.float32)
        # one RNG key per step, drawn in ring order — the stream is
        # bit-identical to N per-step draws (a contained skip still
        # consumes its key, exactly like the per-step path)
        keys = jnp.stack([_random.next_key() for _ in range(n)])

        diff_leaves = tuple(p.data()._data for p in self._diff_params)
        aux_leaves = tuple(p.data()._data for p in self._aux_params)
        if self._guard:
            if self._scale_state is None:
                self._scale_state = self._init_scale_state()
            (new_leaves, new_aux, new_states, losses, oks, scale_state,
             _good) = jitted(diff_leaves, aux_leaves,
                             tuple(self._opt_states), self._scale_state,
                             lrs, jnp.float32(nu0), batch, lab, keys)
            self._scale_state = scale_state
            import numpy as onp
            ok_host = onp.asarray(jax.device_get(oks))
            bump("train_window_syncs")  # the ONE host sync of the window
            num_good = int(ok_host.sum())
            if count_skips and num_good < n:
                bump("guardian_skips", n - num_good)
            self.last_step_ok = bool(ok_host[-1])
        else:
            ts = jnp.float32(nu0) + jnp.arange(1, n + 1,
                                               dtype=jnp.float32)
            new_leaves, new_aux, new_states, losses = jitted(
                diff_leaves, aux_leaves, tuple(self._opt_states), lrs,
                ts, batch, lab, keys)
            ok_host = None
            num_good = n

        self._num_update += num_good
        span.set(step=self._num_update)
        if first:
            span.set_noise(**self._device_memory())
        iuc = self._optimizer._index_update_count
        for i in range(len(self._diff_params)):
            iuc[i] = self._num_update
        self._optimizer.num_update = self._num_update
        for p, leaf in zip(self._diff_params, new_leaves):
            p.data()._rebind(leaf)
        for p, leaf in zip(self._aux_params, new_aux):
            p.data()._rebind(leaf)
        self._opt_states = list(new_states)
        return TrainWindow(NDArray(losses), ok_host, num_good)

    def _effective_lr(self):
        """Per-step scalar lr from schedules only (recompile-free: passed
        as a device scalar).  Step-count-dependent corrections (Adam bias
        correction, LAMB) live in the optimizer's pure _step_t, with t
        passed as a traced device scalar."""
        return self._optimizer._get_lr(0)

    @property
    def learning_rate(self):
        return self._optimizer._get_lr(0)

    def set_learning_rate(self, lr):
        self._optimizer.lr = lr

    @property
    def loss_scale(self):
        """Current dynamic loss scale (host float; syncs the device
        scalar).  1.0 when guarding without dynamic scaling; None when
        unguarded."""
        if self._scale_state is None:
            if not self._guard:
                return None
            return self._scale_cfg[0] if self._dyn_scale else 1.0
        return float(jax.device_get(self._scale_state[0]))

    # -- checkpoint/resume (parity: gluon.Trainer.save_states /
    # load_states; required by the preemption-restart story, SURVEY §5) --
    def save_states(self, fname):
        """Serialize optimizer state + step count (+ dynamic loss-scale
        state) to fname.  State leaves are gathered to host numpy — the
        file is mesh-layout independent, so a restart may use a
        different device topology.  The write is atomic with a CRC32
        manifest sidecar (docs/guardian.md): a crash mid-save leaves the
        previous file intact, and ``load_states`` verifies before
        parsing."""
        import pickle

        import numpy as onp

        states = jax.tree_util.tree_map(lambda a: onp.asarray(a),
                                        tuple(self._opt_states))
        scale_state = self._scale_state
        if scale_state is not None:
            scale_state = tuple(onp.asarray(s) for s in scale_state)
        blob = pickle.dumps({"num_update": self._num_update,
                             "opt_states": states,
                             "scale_state": scale_state})
        from ..resilience import checkpoint as _ckpt
        _ckpt.write_verified(fname, blob)

    def _restore_host_state(self, num_update, opt_states, scale_state):
        """Re-place host-side (numpy) optimizer state + step count +
        loss-scale state onto the CURRENT shardings.  The single restore
        path shared by :meth:`load_states` and the guardian's rollback
        (step() re-derives per-index update counts from ``_num_update``,
        so nothing else needs touching).  A None ``scale_state`` resets
        the scale to its lazy initial value — a drifted scale surviving
        a restore would break bit-exact replay."""
        if not self._params_sharded:
            raise ValueError(
                "state restore: run one step first (or stage parameters) "
                "so optimizer state shardings exist to place the load "
                "onto")
        if len(opt_states) != len(self._opt_states):
            raise ValueError(
                "state restore: checkpoint has %d optimizer-state "
                "entries but this trainer has %d parameters — "
                "architecture mismatch or truncated file"
                % (len(opt_states), len(self._opt_states)))
        self._num_update = int(num_update)
        self._optimizer.num_update = self._num_update
        restored = []
        for cur, saved in zip(self._opt_states, opt_states):
            restored.append(jax.tree_util.tree_map(
                lambda c, s: jax.device_put(jnp.asarray(s), c.sharding),
                cur, saved))
        self._opt_states = restored
        if scale_state is None:
            self._scale_state = None
        else:
            s, clean = scale_state
            self._scale_state = (jnp.float32(s), jnp.int32(clean))

    def load_states(self, fname):
        """Restore optimizer state saved by save_states.  Must be called
        after the first step (or after parameters are staged) so the
        sharding layout to re-place the state onto is known.  Verifies
        the CRC manifest when present and raises a typed
        :class:`~mxtpu.resilience.CorruptCheckpointError` on damaged or
        unparseable files."""
        import pickle

        from ..resilience import checkpoint as _ckpt

        with open(fname, "rb") as f:
            raw = f.read()
        _ckpt.verify(fname, data=raw)
        try:
            blob = pickle.loads(raw)
        except (pickle.UnpicklingError, EOFError, AttributeError,
                ValueError) as e:
            raise _ckpt.CorruptCheckpointError(
                "trainer state unparseable (%s: %s)"
                % (type(e).__name__, e), path=fname) from None
        self._restore_host_state(blob["num_update"], blob["opt_states"],
                                 blob.get("scale_state"))
