"""Kimi Delta Attention: the chunked op (its four Pallas kernels in
interpret mode) against the recurrence it stands for, computed here one
position at a time."""

import collections
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend.core import Literal

import mxtpu as mx
from mxtpu.analysis import check_kernels

kda = importlib.import_module("mxtpu.ops.pallas.kda")
HI = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta):
    """S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T; o_t = S_t^T q_t."""
    B, T, H, K = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        kS = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=HI)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - kS)[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=HI)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def inputs(T, decay, B=2, H=5, K=16, V=8, seed=0):
    """``decay`` = (least, most) of -g per position and channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, K),
                                    minval=np.log(decay[0]),
                                    maxval=np.log(decay[1])))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, T, H, V))


CASES = [
    # T, chunk, (least, most) decay a step
    (128, 64, (1e-3, 1e-1)),    # two whole chunks, the model's decays
    (100, 32, (1e-3, 3.0)),     # no multiple of the chunk
    (64, 16, (1.0, 12.0)),      # decay near 0: a = exp(-12) a step
    (48, 16, (1e-6, 1e-5)),     # decay near 1
    (40, 64, (1e-2, 1.0)),      # shorter than one chunk
    # G down to -768 inside a chunk, through all six halvings: a positive
    # exponent anywhere overflows to inf
    (128, 64, (3.0, 12.0)),
]
IDS = ["T128_c64", "T100_c32_ragged", "T64_c16_decay_near_0",
       "T48_c16_decay_near_1", "T40_c64_short", "T128_c64_decay_near_0"]


@pytest.mark.parametrize("T,chunk,decay", CASES, ids=IDS)
def test_forward_matches_the_recurrence(T, chunk, decay):
    args, _ = inputs(T, decay)
    got = np.asarray(kda.kda(*args, chunk=chunk))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(recurrence(*args)),
                               rtol=2e-5, atol=2e-6)


@pytest.fixture(scope="module", params=list(zip(CASES, IDS)),
                ids=lambda p: p[1])
def gradients(request):
    (T, chunk, decay), _ = request.param
    args, ct = inputs(T, decay, seed=1)
    got = jax.grad(lambda *a: jnp.sum(kda.kda(*a, chunk=chunk) * ct),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * ct),
                    argnums=(0, 1, 2, 3, 4))(*args)
    return dict(zip(("q", "k", "v", "g", "beta"), zip(got, want)))


@pytest.mark.parametrize("name", ["q", "k", "v", "g", "beta"])
def test_every_inputs_gradient_matches_the_recurrence(gradients, name):
    got, want = gradients[name]
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-6 * max(scale, 1.0))


# ------------------- the chunk kernels against jax.vjp of the forward's body

def _chunk_rows(C, decay, BH=2, N=3, K=16, V=8):
    """Rows of BH x N chunks as the kernels take them, and a cotangent for
    each of the six operands."""
    (q, k, v, g, beta), _ = inputs(N * C, decay, B=1, H=BH, K=K, V=V, seed=C)
    chunks = lambda a: jnp.moveaxis(a, 1, 2).reshape(    # noqa: E731
        (BH, N, C) + a.shape[3:])
    b = beta[..., None]
    rows = tuple(map(chunks, (q, k, b * k, b * v, g)))
    keys = jax.random.split(jax.random.PRNGKey(C + 1), 6)
    cts = tuple(jax.random.normal(key, shape) for key, shape in
                zip(keys, kda._operand_shapes(BH, N, C, K, V)))
    return rows, cts


@pytest.mark.parametrize("decay", [c[2] for c in CASES[:5]],
                         ids=["decay_%g_%g" % c[2] for c in CASES[:5]])
@pytest.mark.parametrize("C", [16, 32, 64])
def test_the_handwritten_backward_matches_the_vjp_of_the_forward(C, decay):
    """``kda_chunk_fwd_inverse`` and ``kda_chunk_bwd`` (interpret mode)
    against ``jax.vjp(_chunk_math)`` chunk by chunk: the operands, and
    each of the five cotangents to 1e-5 of its tile's norm."""
    rows, cts = _chunk_rows(C, decay)
    (BH, N, _, K), V = rows[0].shape, rows[3].shape[-1]
    shapes = kda._operand_shapes(BH, N, C, K, V)
    *ops, t_t = kda._chunk_call(
        kda._chunk_fwd_kernel, kda.CHUNK_FWD_INVERSE_NAME, rows,
        shapes + [(BH, N, C, C)], True)
    got = kda._chunk_call(
        kda._chunk_bwd_kernel, kda.CHUNK_BWD_NAME, rows + (t_t,) + cts,
        [x.shape for x in rows], True)

    def reference(chunk):       # one chunk at a time, as the kernels go
        rows, cts = chunk
        ops, back = jax.vjp(lambda *a: kda._chunk_math(*a)[:6], *rows)
        return ops, back(cts)

    flat = lambda a: a.reshape((BH * N,) + a.shape[2:])    # noqa: E731
    want_ops, want = jax.tree_util.tree_map(
        lambda a: a.reshape((BH, N) + a.shape[1:]),
        jax.lax.map(reference, jax.tree_util.tree_map(flat, (rows, cts))))
    tiles = lambda a: np.sqrt(np.sum(                   # noqa: E731
        np.square(np.asarray(a, dtype="float64")), axis=(2, 3)))
    for a, b in zip(ops, want_ops):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    for name, a, b in zip(("q", "k", "bk", "bv", "g"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert (tiles(a - b) <= 1e-5 * tiles(b)).all(), \
            (name, float((tiles(a - b) / tiles(b)).max()))


def _products(jaxpr, computed):
    """Every ``dot_general`` under ``jaxpr`` with, per operand, whether
    an input of the kernel reaches it (a 0/1 matrix made of iotas is
    reached by none)."""
    computed = set(computed)
    for eqn in jaxpr.eqns:
        reached = [not isinstance(v, Literal) and v in computed
                   for v in eqn.invars]
        if eqn.primitive.name == "dot_general":
            yield eqn, reached
        for sub in jax.core.jaxprs_in_params(eqn.params):
            assert len(sub.invars) == len(eqn.invars)
            yield from _products(
                sub, [v for v, r in zip(sub.invars, reached) if r])
        if any(reached):
            computed.update(eqn.outvars)


def _kernel_body(kernel, ins, outs):
    text = jax.make_jaxpr(lambda *a: kda._chunk_call(
        kernel, "body", a, outs, True))(*[jnp.zeros(s) for s in ins])
    (call,) = [e for e in text.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return call.params["jaxpr"]


@pytest.mark.parametrize("kernel, most_products, most_units",
                         [("fwd", 35, 29), ("bwd", 48, 40)])
def test_chunk_kernels_stay_inside_their_product_budget(
        kernel, most_products, most_units):
    """The ``dot_general`` equations in the body of a chunk kernel at C =
    64, K = V = 128, and their cost in units of a 64 x 64 x 128 product at
    six bfloat16 passes (``Precision.HIGHEST`` on float32; a product of
    bfloat16 operands is one pass).  PR 32 brought the forward from 35
    products of 29 units to 18 of 23, the backward from 95 of 78 to 18 of
    37.  No product of two computed operands runs below HIGHEST."""
    C, K = 64, 128
    rows = [(1, 1, C, K)] * 5
    operands = kda._operand_shapes(1, 1, C, K, K)
    inverse = [(1, 1, C, C)]
    body = _kernel_body(kda._chunk_fwd_kernel, rows, operands + inverse) \
        if kernel == "fwd" else \
        _kernel_body(kda._chunk_bwd_kernel, rows + inverse + operands, rows)
    count = units = 0
    for eqn, reached in _products(body, body.invars):
        a, b = (v.aval for v in eqn.invars)
        (contracted, _), _ = eqn.params["dimension_numbers"]
        macs = np.prod(eqn.outvars[0].aval.shape) * np.prod(
            [a.shape[d] for d in contracted])
        if eqn.params["precision"] in (HI, (HI, HI)):
            assert a.dtype == b.dtype == jnp.float32
            passes = 6
        else:   # exact all the same: one factor is a 0/1 matrix
            assert a.dtype == b.dtype == jnp.bfloat16
            assert not all(reached), eqn
            passes = 1
        count += 1
        units += macs * passes / (6 * 64 * 64 * 128)
    assert count <= most_products and units <= most_units, (count, units)


def test_heads_go_through_in_groups_and_give_the_same(monkeypatch):
    args, _ = inputs(64, (1e-3, 1e-1), H=6)
    monkeypatch.setattr(kda, "HEADS_AT_A_TIME", 6)
    whole = kda.kda(*args, chunk=32)
    monkeypatch.setattr(kda, "HEADS_AT_A_TIME", 4)      # 6 heads: 2 x 3
    assert kda.heads_per_call(6) == 3
    np.testing.assert_allclose(np.asarray(kda.kda(*args, chunk=32)),
                               np.asarray(whole), rtol=1e-5, atol=1e-7)


def test_chunk_must_be_a_power_of_two():
    args, _ = inputs(32, (1e-3, 1e-1))
    with pytest.raises(ValueError, match="power of two"):
        kda.kda(*args, chunk=48)


def test_registered_op_and_counters():
    from mxtpu.ops.pallas import counters

    (q, k, v, g, beta), _ = inputs(32, (1e-3, 1e-1))
    before = counters.count(kda.FWD_NAME)
    out = mx.nd.kda(*(mx.nd.array(np.asarray(a)) for a in (q, k, v, g, beta)),
                    chunk=16)
    assert out.shape == v.shape
    assert counters.count(kda.FWD_NAME) > before
    from mxtpu.observability.metrics import default_registry
    snap = default_registry().snapshot()
    assert snap["kernel_invocations." + kda.FWD_NAME] >= 1


def test_bfloat16_model_gets_bfloat16_back():
    args, _ = inputs(32, (1e-3, 1e-1))
    out = kda.kda(*(a.astype(jnp.bfloat16) for a in args), chunk=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype="float32"),
                               np.asarray(recurrence(*args)), atol=3e-2)


@pytest.mark.parametrize("T", [8192, 96], ids=["cell", "toy"])
def test_kernel_specs_pass_the_static_check(T):
    specs = kda.kernel_specs(B=1, H=8, T=T, K=128)
    assert [s.name.split("[")[0] for s in specs] == \
        [kda.CHUNK_FWD_INVERSE_NAME, kda.FWD_STATES_NAME, kda.BWD_NAME,
         kda.CHUNK_BWD_NAME]
    report = check_kernels(specs)
    assert not report.errors, [str(d) for d in report.errors]


def test_kernel_specs_describe_the_real_calls(monkeypatch):
    """kernel_specs == the four pallas_calls a backward pass issues after
    it has run the forward again: the chunks' operands with their
    inverses, the state pass that writes the chunks' states, its backward,
    the operands' backward."""
    calls = []
    real = kda.pl.pallas_call

    def spy(kernel, **kw):
        calls.append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(kda.pl, "pallas_call", spy)
    for cached in (kda._make_state_pass, kda._make_chunk_operands):
        cached.cache_clear()
    args, _ = inputs(96, (1e-3, 1e-1), B=1, H=2, K=16, V=16)
    jax.grad(lambda *a: kda.kda(*a, chunk=32).sum())(*args)
    for cached in (kda._make_state_pass, kda._make_chunk_operands):
        cached.cache_clear()
    # traced calls: the forward pass, then the backward pass's own forward
    # (the kernel that writes no inverse is traced once more for the pass
    # that needs no residuals) and its two backward kernels
    assert collections.Counter(c["name"] for c in calls) == {
        kda.CHUNK_FWD_NAME: 2, kda.FWD_NAME: 2,
        kda.CHUNK_FWD_INVERSE_NAME: 1, kda.FWD_STATES_NAME: 1,
        kda.BWD_NAME: 1, kda.CHUNK_BWD_NAME: 1}
    specs = kda.kernel_specs(B=1, H=2, T=96, K=16, chunk=32, interpret=True)
    by_name = {c["name"]: c for c in calls}
    issued = [by_name[spec.name.split("[")[0]] for spec in specs]
    for call, spec in zip(issued, specs):
        assert tuple(call["grid"]) == spec.grid
        for kind, key in (("in", "in_specs"), ("out", "out_specs")):
            assert [tuple(b.block_shape) for b in call[key]] == \
                [op.block_shape for op in spec.operands
                 if op.kind == kind], (spec.name, kind)
        assert [tuple(sc.shape) for sc in call.get("scratch_shapes", ())] \
            == [sc.shape for sc in spec.scratch]


# ---------------------------- under a unit of recomputation (ops/remat)

def _under_a_unit(op, args, w, policy):
    """Gradient of ``sum(tanh(op(x) @ w))`` through a checkpoint as a
    unit of recomputation has it, with the unit's policy or none: the
    jaxpr's kernels by name, what the policy kept, the gradients."""
    import re
    from mxtpu.ops import remat

    def unit(args, w):
        scaled = tuple(a * 1.0 for a in args)       # a projection's stead
        return jnp.tanh(jnp.einsum("bthv,vu->bthu", op(*scaled), w)).sum()

    grad = jax.grad(jax.checkpoint(unit, policy=policy), argnums=(0, 1))
    remat.reset()
    names = re.findall(r"name=(kda_\w+)", str(jax.make_jaxpr(grad)(args, w)))
    return collections.Counter(names), remat.counts(), grad(args, w)


@pytest.mark.parametrize("op", ["kda", "kda_mixer"])
def test_a_unit_keeps_the_output_and_runs_the_forward_once_less(op):
    from mxtpu.ops import remat

    (q, k, v, g, beta), _ = inputs(48, (1e-3, 1e-1), H=6, V=16)
    B, T, H, K = q.shape                            # 6 heads: 2 groups of 3
    if op == "kda":
        fn, args = (lambda *a: kda.kda(*a, chunk=16)), (q, k, v, g, beta)
    else:
        conv = jnp.full((H * K, 4), 0.25)
        fn = lambda q, k, v, f, gate, beta: kda.kda_mixer(   # noqa: E731
            q, k, v, f, gate, beta, conv, conv, conv, jnp.zeros(H),
            jnp.zeros(H * K), jnp.ones(K), chunk=16)
        args = (q, k, v, g, q + k, beta)
    w = jax.random.normal(jax.random.PRNGKey(7), (16, 4))
    kept, counts, grads = _under_a_unit(fn, args, w, remat.policy)
    alone, nothing, want = _under_a_unit(fn, args, w, None)
    # one of the runs is the groups' own, which also writes the inverses
    assert (kept[kda.CHUNK_FWD_NAME], alone[kda.CHUNK_FWD_NAME]) == (1, 2)
    assert (kept[kda.CHUNK_FWD_INVERSE_NAME],
            alone[kda.CHUNK_FWD_INVERSE_NAME]) == (1, 1)
    # the groups' own recomputation reads the state pass's output only in
    # the mixer (its norm and gate); the bare op's backward does not
    states = (2, 3) if op == "kda_mixer" else (1, 2)
    assert (kept[kda.FWD_NAME], alone[kda.FWD_NAME]) == states
    for name in (kda.CHUNK_BWD_NAME, kda.BWD_NAME, kda.FWD_STATES_NAME):
        assert (kept[name], alone[name]) == (1, 1)
    assert counts == {"kept_outputs": 1, "kept_bytes": B * T * H * 16 * 4}
    assert nothing == {"kept_outputs": 0, "kept_bytes": 0}
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_outside_a_checkpoint_the_mark_leaves_the_program_alone():
    (q, k, v, g, beta), _ = inputs(32, (1e-3, 1e-1))
    text = jax.jit(lambda *a: kda.kda(*a, chunk=16)).lower(
        q, k, v, g, beta).compile().as_text()
    assert "mxtpu_kept" not in text
