"""ops/kda_ops.py: the chunked delta-rule scan (its `xla` and
`interpret` impls) against the recurrence run TOKEN BY TOKEN, forward
and all five input gradients; the state crossing chunk, block and
sub-block edges; a run at the decay's bound; the registered grad op on
the forward's saved block states and chunk inverses, alone and inside a
recompute segment; the saved inverse against a triangular solve, and
the backward that reads it against the one that formed it again, bit
for bit; the gate, the per-head L2 norm and the head-wise gated norm.

The decay is drawn wide on purpose (-g log-uniform in [1e-3, 2] a
channel: a channel keeps from 97% down to 1e-14 of itself over a
16-token sub-block), and beta in (0.1, 0.9), so that a scan that lost
the state between chunks, or formed a decay across a sub-block edge
wrongly, would be far outside the tolerance:
`test_the_state_crosses_chunks` holds the reference with the state
zeroed at every chunk start to the same bound and requires that it
FAILS it.

Tolerance: float32 against float32 in another order of summation, with
a triangular system inverted by products: 5e-5 of each array's largest
entry (3e-6 seen).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import pallas_kernels as pk

SLOTS = ("Q", "K", "V", "G", "Beta")
TOL = 5e-5


def token_by_token(q, k, v, g, beta, reset_every=0, erase=True):
    """q, k, v, g [B, T, H*D], beta [B, T, H] -> o [B, T, H*D];
    reset_every zeroes the state every so many tokens, erase False
    drops the delta rule's erase term (wrong scans on purpose)."""
    b, t, width = q.shape
    h = beta.shape[-1]

    def heads(x):
        return x.reshape(b, t, h, width // h).transpose(1, 0, 2, 3)

    def step(s, inp):
        i, qt, kt, vt, gt, bt = inp
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        s = jnp.exp(gt)[..., None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", kt, s) if erase else 0.0
        s = s + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    d = width // h
    _, o = lax.scan(
        step, jnp.zeros((b, h, d, d), q.dtype),
        (jnp.arange(t), heads(q), heads(k), heads(v), heads(g),
         beta.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(b, t, width)


def operands(t, h=2, d=128, b=2, seed=0, g_fixed=None):
    r = np.random.RandomState(seed)

    def unit(x):
        x = x.reshape(b, t, h, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            b, t, h * d)

    f = lambda *s: r.randn(*s).astype(np.float32)  # noqa: E731
    g = -np.exp(r.uniform(np.log(1e-3), np.log(2.0), (b, t, h * d)))
    if g_fixed is not None:
        g = np.full_like(g, g_fixed)
    args = (unit(f(b, t, h * d)) * d ** -0.5, unit(f(b, t, h * d)),
            f(b, t, h * d), g.astype(np.float32),
            r.uniform(0.1, 0.9, (b, t, h)).astype(np.float32))
    return tuple(jnp.asarray(a) for a in args), jnp.asarray(f(b, t, h * d))


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def reference(args, go, **wrong):
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(lambda *a: token_by_token(*a, **wrong), *args)
        return o, vjp(go)


def scan_and_grads(args, go, attrs):
    ins = dict(zip(SLOTS, args))
    outs = get_op_def("kda_scan").compute(ins, attrs)
    grads = get_op_def("kda_scan_grad").compute(
        dict(ins, **outs, **{"O@GRAD": go}), attrs)
    return outs, grads


# (tokens, chunk, chunks a block): one block of one chunk; a chunk of
# two sub-blocks; blocks of several chunks; the cell's 4 x 64
SHAPES = [(16, 16, 1), (64, 32, 1), (128, 32, 2), (256, 64, 2),
          (256, 64, 4)]


@pytest.mark.parametrize("t,chunk,block_chunks", SHAPES)
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_forward_and_five_gradients_against_the_recurrence(
        impl, t, chunk, block_chunks):
    args, go = operands(t, b=1 if t > 128 else 2)
    want_o, want_g = reference(args, go)
    attrs = {"chunk_size": chunk, "block_chunks": block_chunks,
             "impl": impl}
    outs, grads = scan_and_grads(args, go, attrs)
    b = args[0].shape[0]
    assert outs["States"].shape == (b, t // (chunk * block_chunks),
                                    2 * 128, 128)
    assert outs["States"].dtype == jnp.float32
    assert rel(outs["O"], want_o) <= TOL
    errors = {s: rel(grads[s + "@GRAD"], g) for s, g in zip(SLOTS, want_g)}
    assert all(e <= TOL for e in errors.values()), errors
    assert all(grads[s + "@GRAD"].dtype == v.dtype
               and grads[s + "@GRAD"].shape == v.shape
               for s, v in zip(SLOTS, args))
    # every gradient is there to be compared
    assert all(float(jnp.abs(g).max()) > 0 for g in want_g)


def test_the_state_crosses_chunks():
    """The recurrence with its state zeroed at every chunk start, or
    without the erase term, is NOT the scan: it misses the tolerance by
    orders of magnitude, in the output and in every gradient."""
    args, go = operands(64)
    want_o, want_g = reference(args, go)
    for wrong in ({"reset_every": 16}, {"erase": False}):
        lost_o, lost_g = reference(args, go, **wrong)
        assert rel(lost_o, want_o) > 1000 * TOL, wrong
        assert all(rel(lo, w) > 1000 * TOL
                   for lo, w in zip(lost_g, want_g)), wrong
    # the first chunk, which starts from zero either way, agrees
    lost_o, _ = reference(args, go, reset_every=16)
    assert rel(lost_o[:, :16], want_o[:, :16]) <= TOL


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_a_run_at_the_decays_bound_is_finite_and_right(impl):
    """128 tokens at g = -5 a channel throughout (two chunks of 64,
    eight sub-blocks): e^(-G) over a chunk would be e^320.  Nothing
    overflows, the state a token leaves is not lost to the next (e^-5
    of it is kept, 0.7% of the output), and a mixed run, which sits at
    the bound for 70 tokens and then keeps everything, is right too."""
    for g_of in (lambda g: g, lambda g: g.at[:, 70:].set(-1e-4)):
        args, go = operands(128, b=1, g_fixed=-5.0)
        args = args[:3] + (g_of(args[3]),) + args[4:]
        want_o, want_g = reference(args, go)
        outs, grads = scan_and_grads(
            args, go, {"chunk_size": 64, "block_chunks": 2, "impl": impl})
        assert bool(jnp.isfinite(outs["O"]).all())
        assert all(bool(jnp.isfinite(g).all()) for g in grads.values())
        assert rel(outs["O"], want_o) <= TOL
        errors = {s: rel(grads[s + "@GRAD"], g)
                  for s, g in zip(SLOTS, want_g)}
        # at the bound throughout, d G is e^-5-small itself (6e-4 at
        # its largest) and what is left of the end-of-chunk terms'
        # cancellation in float32 (4e-6) shows: held to 1e-5 absolute
        errors["G"] = float(jnp.abs(grads["G@GRAD"] - want_g[3]).max()) \
            / max(float(jnp.abs(want_g[3]).max()), 0.2)
        assert all(e <= TOL for e in errors.values()), errors
    # what the previous token left is in the output: without it the
    # output is another by far more than the tolerance
    lost_o, _ = reference(args, go, reset_every=1)
    assert rel(lost_o, want_o) > 100 * TOL


def _unbounded_case(case):
    """128 tokens, two chunks of 64: g = -30 a token throughout (a
    sub-block's reference row is 450 away: e^450 if anything formed
    e^(G_ref - G_s) there), or each channel of each token drawn from
    {-1e-4, -40}, so that steps that keep everything and steps that
    keep nothing lie inside ONE 16-row sub-block; beta at the ends of
    (0, 2)."""
    args, go = operands(128, b=1)
    r = np.random.RandomState(7)
    q, k, v, g, beta = args
    if case == "g=-30":
        g = jnp.full_like(g, -30.0)
    else:
        g = jnp.asarray(np.where(r.rand(*g.shape) < 0.5, -1e-4, -40.0),
                        jnp.float32)
    beta = jnp.asarray(np.where(r.rand(*beta.shape) < 0.5, 0.01, 1.99),
                       jnp.float32)
    return (q, k, v, g, beta), go


# a decay is a difference of a chunk's running sums of g: its exponent
# carries the rounding of |G|, half an ulp of up to 64 x 40 = 2,560
# (1.2e-4) in the mixed case, where the recurrence multiplies e^g of
# one token exactly.  3e-5 to 7e-5 seen, the same under every impl
UNBOUNDED_TOL = {"g=-30": TOL, "mixed": 4 * TOL}


@pytest.mark.parametrize("case", ["g=-30", "mixed"])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_an_unbounded_decay_is_finite_and_the_recurrence(impl, case):
    """decay "unbounded": forward and the five gradients equal the
    token-by-token recurrence for g far below the other path's bound
    and for write strengths near 0 and near 2."""
    args, go = _unbounded_case(case)
    want_o, want_g = reference(args, go)
    outs, grads = scan_and_grads(
        args, go, {"chunk_size": 64, "block_chunks": 2, "impl": impl,
                   "decay": "unbounded"})
    assert bool(jnp.isfinite(outs["O"]).all())
    assert all(bool(jnp.isfinite(g).all()) for g in grads.values())
    tol = UNBOUNDED_TOL[case]
    assert rel(outs["O"], want_o) <= tol
    errors = {s: rel(grads[s + "@GRAD"], g) for s, g in zip(SLOTS, want_g)}
    # at -30 a token d G is e^-30-small itself: held absolutely, as at
    # the other path's bound
    errors["G"] = float(jnp.abs(grads["G@GRAD"] - want_g[3]).max()) \
        / max(float(jnp.abs(want_g[3]).max()), 0.2)
    assert all(e <= tol for e in errors.values()), errors
    # the state crosses tokens in the mixed run: the steps that keep
    # everything carry it
    if case == "mixed":
        lost_o, _ = reference(args, go, reset_every=1)
        assert rel(lost_o, want_o) > 100 * tol


@pytest.mark.parametrize("case", ["g=-30", "mixed"])
def test_the_plain_references_recurrence_is_the_same_one(case):
    """benchmarks/reference/solar_open2.py's token-by-token scan, the
    third impl a cell's `correct` rests on, on the same operands."""
    from conftest import load_reference

    args, go = _unbounded_case(case)
    want_o, _ = reference(args, go)
    ref = load_reference("solar_open2")
    q, k, v, g, beta = (a[0] for a in args)
    with jax.default_matmul_precision("highest"):
        o = ref.delta_recurrence(*(x.reshape(128, 2, 128)
                                   for x in (q, k, v, g)), beta)
    assert bool(jnp.isfinite(o).all())
    assert rel(o.reshape(1, 128, 256), want_o) <= TOL


def test_the_bounded_path_is_wrong_past_its_bound():
    """What the attr is for: at g = -30 the kernels' bounded path
    (`ling3`'s, sound for g >= -5.33) clamps a column's factor at e^80
    while the row's has underflowed: a wrong output, by far more than
    any tolerance.  The XLA form takes each pair's own difference and
    is right under either promise."""
    from paddle_tpu.ops import pallas_kda

    assert pallas_kda.BOUNDED_G_MIN == pytest.approx(-80.0 / 15)
    args, go = _unbounded_case("g=-30")
    want_o, _ = reference(args, go)
    attrs = {"chunk_size": 64, "block_chunks": 2, "decay": "bounded"}
    wrong, _ = scan_and_grads(args, go, dict(attrs, impl="interpret"))
    assert rel(wrong["O"], want_o) > 1000 * TOL
    right, _ = scan_and_grads(args, go, dict(attrs, impl="xla"))
    assert rel(right["O"], want_o) <= TOL
    with pytest.raises(ValueError, match="neither bounded"):
        scan_and_grads(args, go, dict(attrs, impl="xla", decay="some"))


@pytest.mark.parametrize("t,chunk,block_chunks", SHAPES)
def test_the_unbounded_path_on_every_shape(t, chunk, block_chunks):
    """The levels inside a sub-block at every chunking the kernels
    take (16, 32 and 64-row chunks: one to four sub-blocks), on the
    wide draw of g the bounded path is tested on: the two paths agree
    where both are sound."""
    args, go = operands(t, b=1)
    want_o, want_g = reference(args, go)
    outs, grads = scan_and_grads(
        args, go, {"chunk_size": chunk, "block_chunks": block_chunks,
                   "impl": "interpret", "decay": "unbounded"})
    assert rel(outs["O"], want_o) <= TOL
    errors = {s: rel(grads[s + "@GRAD"], g) for s, g in zip(SLOTS, want_g)}
    assert all(e <= TOL for e in errors.values()), errors


def _kernel_ops(fn, *avals, **static):
    """{primitive: count} of a kernel entry's jaxpr, its body's
    included."""
    import collections
    import re

    text = str(jax.make_jaxpr(lambda *a: fn(*a, **static))(*avals))
    found = collections.Counter()
    for a, b in re.findall(r"= (\w+)\[|= (\w+) ", text):
        found[a or b] += 1
    return found


def test_the_bounded_kernels_are_the_ones_from_before_the_attr():
    """`ling3`'s path: the bounded kernels' bodies hold what they held
    at the parent commit (PR 48: 20 and 32 products, 11 and 22
    exponentials a chunk walk, no roll), so that cell's compiled
    kernels are the parent's; the unbounded bodies add the four levels
    (4 products forward; 4 in the backward's replay and 2 x 4 in its
    walk) and the references' rolls."""
    from paddle_tpu.ops import pallas_kda

    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = jax.ShapeDtypeStruct
    x = sds((1, 512, 256), bf16)
    ins = (x, x, x, sds((1, 512, 256), f32), sds((1, 512, 2), f32))
    kept = (sds((1, 2, 256, 128), f32), sds((1, 2, 2, 64, 256), f32), x)
    sizes = dict(chunk=64, block_chunks=4)
    fwd = _kernel_ops(pallas_kda.kda_fwd_pallas, *ins, **sizes)
    bwd = _kernel_ops(pallas_kda.kda_bwd_pallas, *ins, *kept, **sizes)
    assert (fwd["dot_general"], fwd["exp"], fwd["roll"]) == (20, 11, 0)
    assert (bwd["dot_general"], bwd["exp"], bwd["roll"]) == (32, 22, 0)
    # bounded is the default, and asking for it changes nothing
    assert fwd == _kernel_ops(pallas_kda.kda_fwd_pallas, *ins, **sizes,
                              bounded=True)
    fwd_u = _kernel_ops(pallas_kda.kda_fwd_pallas, *ins, **sizes,
                        bounded=False)
    bwd_u = _kernel_ops(pallas_kda.kda_bwd_pallas, *ins, *kept, **sizes,
                        bounded=False)
    assert fwd_u["dot_general"] == 20 + 4 and fwd_u["roll"] > 0
    assert bwd_u["dot_general"] == 32 + 4 + 2 * 4 and bwd_u["roll"] > 0


def test_the_gate_that_made_g_chooses_the_scans_path():
    """layers.kda_scan reads what layers.kda_gate promises: the sigmoid
    form at a bound the kernels' one product a sub-block is sound for
    -> "bounded"; the softplus form, a lower bound below -5.33, or a g
    of any other origin -> "unbounded"."""
    _fresh()
    x = layers.data("x", shape=[64, 256], dtype="float32")
    b = layers.data("b", shape=[64, 2], dtype="float32")

    def decay_of(g):
        layers.kda_scan(x, x, x, g, b, chunk_size=16, block_chunks=2)
        return fluid.default_main_program().global_block().ops[-1].attrs[
            "decay"]

    assert decay_of(layers.kda_gate(x, 2, name="g1")) == "bounded"
    assert decay_of(layers.kda_gate(x, 2, lower_bound=-5.3,
                                    name="g2")) == "bounded"
    assert decay_of(layers.kda_gate(x, 2, lower_bound=-8.0,
                                    name="g3")) == "unbounded"
    assert decay_of(layers.kda_gate(x, 2, form="softplus",
                                    name="g4")) == "unbounded"
    assert decay_of(x) == "unbounded"
    with pytest.raises(ValueError, match="form"):
        layers.kda_gate(x, 2, form="relu", name="g5")
    # a desc from before the attr is the bounded one it was built as
    assert get_op_def("kda_scan").canonical_attrs({})["decay"] == "bounded"


def test_states_are_the_transposed_state_each_block_starts_from():
    args, _ = operands(64, b=1)
    outs = get_op_def("kda_scan").compute(
        dict(zip(SLOTS, args)),
        {"chunk_size": 16, "block_chunks": 2, "impl": "interpret"})
    q, k, v, g, beta = (a[0] for a in args)
    s = jnp.zeros((2, 128, 128), jnp.float32)
    for t in range(64):
        if t % 32 == 0:
            got = outs["States"][0, t // 32].reshape(2, 128, 128)
            assert rel(got + 1.0, s.transpose(0, 2, 1) + 1.0) <= TOL
        kt, vt = k[t].reshape(2, 128), v[t].reshape(2, 128)
        s = jnp.exp(g[t].reshape(2, 128))[..., None] * s
        s = s + (beta[t][:, None] * kt)[..., None] * (
            vt - jnp.einsum("hk,hkv->hv", kt, s))[:, None, :]


def chunk_inverses(inverse, chunk):
    """Inverse [B, H, T/block, C, block], a block's side by side ->
    [B, H, T/C, C, C], a chunk's alone."""
    b, h, nb, _, block = inverse.shape
    return inverse.reshape(b, h, nb, chunk, block // chunk, chunk).transpose(
        0, 1, 2, 4, 3, 5).reshape(b, h, -1, chunk, chunk)


def solved_inverses(k, g, beta, chunk):
    """(I + diag(beta) M)^-1 of every chunk by a triangular solve
    against the identity, M[r, s] = sum_c k_r[c] k_s[c] e^(G_r[c] -
    G_s[c]) for s < r, G the chunk's running sum of g: [B, H, T/C, C,
    C] float32."""
    from jax.scipy.linalg import solve_triangular

    b, t, width = k.shape
    h = beta.shape[-1]

    def chunks(x):                      # -> [B, H, T/C, C, D]
        return x.reshape(b, t // chunk, chunk, h, -1).transpose(
            0, 3, 1, 2, 4)

    kc, gc = chunks(k), jnp.cumsum(chunks(g), axis=3)
    bc = chunks(beta)[..., 0]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.exp(jnp.where(
        strict[..., None], gc[..., :, None, :] - gc[..., None, :, :], 0.0))
    m = jnp.where(strict, jnp.einsum(
        "...rc,...sc,...rsc->...rs", kc, kc, decay,
        precision=lax.Precision.HIGHEST), 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    return solve_triangular(eye + bc[..., None] * m,
                            jnp.broadcast_to(eye, m.shape), lower=True,
                            unit_diagonal=True)


@pytest.mark.parametrize("t,chunk,block_chunks", SHAPES)
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_inverse_is_each_chunks_triangular_solve(impl, t, chunk,
                                                 block_chunks):
    args, _ = operands(t, b=1 if t > 128 else 2)
    outs = get_op_def("kda_scan").compute(
        dict(zip(SLOTS, args)),
        {"chunk_size": chunk, "block_chunks": block_chunks, "impl": impl})
    inverse = outs["Inverse"]
    block = chunk * block_chunks
    assert inverse.shape == (args[0].shape[0], 2, t // block, chunk, block)
    assert inverse.dtype == jnp.float32
    got = chunk_inverses(inverse, chunk)
    want = solved_inverses(args[1], args[3], args[4], chunk)
    assert rel(got, want) <= TOL
    # unit lower triangular, and no chunk's is the identity alone
    upper = jnp.triu(jnp.ones((chunk, chunk), bool), 1)
    assert float(jnp.abs(jnp.where(upper, got, 0.0)).max()) == 0.0
    below = jnp.abs(jnp.where(upper.T, got, 0.0)).max(axis=(-1, -2))
    assert float(below.min()) > 100 * TOL


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_inverse_at_the_decays_bound(impl):
    args, _ = operands(128, b=1, g_fixed=-5.0)
    outs = get_op_def("kda_scan").compute(
        dict(zip(SLOTS, args)),
        {"chunk_size": 64, "block_chunks": 2, "impl": impl})
    got = chunk_inverses(outs["Inverse"], 64)
    assert bool(jnp.isfinite(got).all())
    assert rel(got, solved_inverses(args[1], args[3], args[4], 64)) <= TOL


def _bwd_on(args, outs, go, chunk, block_chunks, jitted=True):
    from paddle_tpu.ops import pallas_kda

    fn = pallas_kda.kda_bwd_pallas
    return (fn if jitted else fn.__wrapped__)(
        *args, outs["States"], outs["Inverse"], go, chunk=chunk,
        block_chunks=block_chunks, interpret=True)


@pytest.mark.parametrize("t,chunk,block_chunks,g_fixed",
                         [(128, 32, 2, None), (256, 64, 4, None),
                          (128, 64, 2, -5.0)])
def test_backward_on_the_saved_inverse_is_the_one_that_formed_it_again(
        monkeypatch, t, chunk, block_chunks, g_fixed):
    """pt_kda_bwd reads the forward's inverse; before, its `again` loop
    called `_inverse` on the block's chunks a second time.  The same
    ten products of the same operands: the five gradients agree bit
    for bit (interpret mode), at the decay's bound too."""
    from paddle_tpu.ops import pallas_kda

    args, go = operands(t, b=1, g_fixed=g_fixed)
    outs = get_op_def("kda_scan").compute(
        dict(zip(SLOTS, args)),
        {"chunk_size": chunk, "block_chunks": block_chunks,
         "impl": "interpret"})
    reads = _bwd_on(args, outs, go, chunk, block_chunks)

    real, formed = pallas_kda._chunk_forward, []

    def forms_it_again(*a, t_inv=None):
        out = real(*a)
        formed.append(t_inv is not None)
        return out

    monkeypatch.setattr(pallas_kda, "_chunk_forward", forms_it_again)
    again = _bwd_on(args, outs, go, chunk, block_chunks, jitted=False)
    assert formed == [True]             # the backward's loop, traced once
    for slot, a, b in zip(SLOTS, reads, again):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b)), slot
    # and a backward that reads ANOTHER inverse is another backward
    monkeypatch.undo()
    other = dict(outs, Inverse=outs["Inverse"] * 1.001)
    moved = _bwd_on(args, other, go, chunk, block_chunks)
    assert all(rel(m, r) > TOL for m, r in zip(moved, reads))


def test_a_length_that_is_no_multiple_of_the_block_raises():
    args, _ = operands(48)
    for impl in ("xla", "interpret"):
        with pytest.raises(ValueError, match="nothing is padded"):
            get_op_def("kda_scan").compute(
                dict(zip(SLOTS, args)),
                {"chunk_size": 16, "block_chunks": 2, "impl": impl})
    # and at build time, in the layer
    _fresh()
    x = layers.data("x", shape=[48, 256], dtype="float32")
    b = layers.data("b", shape=[48, 2], dtype="float32")
    with pytest.raises(ValueError, match="nothing is padded"):
        layers.kda_scan(x, x, x, x, b, chunk_size=16, block_chunks=2)
    layers.kda_scan(x, x, x, x, b, chunk_size=16, block_chunks=3)
    # the inverse's series covers four 16-row blocks: no longer chunk
    long_args, _ = operands(128)
    with pytest.raises(ValueError, match="up to 64"):
        get_op_def("kda_scan").compute(
            dict(zip(SLOTS, long_args)),
            {"chunk_size": 128, "block_chunks": 1, "impl": "xla"})


def test_sizes_the_kernels_cannot_tile_run_the_xla_form():
    args, go = operands(32, h=4, d=32)
    want_o, want_g = reference(args, go)
    before = _counts()
    outs, grads = scan_and_grads(
        args, go, {"chunk_size": 16, "block_chunks": 1,
                   "impl": "interpret"})
    assert _since(before) == {("kda_scan", "xla"): 2,
                              ("kda_scan_decay", "bounded"): 2,
                              ("kda_scan_grad", "recompute"): 1}
    assert rel(outs["O"], want_o) <= TOL
    assert all(rel(grads[s + "@GRAD"], g) <= TOL
               for s, g in zip(SLOTS, want_g))


def _counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


# -- through the IR: the grad op reads the saved states ---------------------

T, H, D, C = 64, 2, 128, 32


def _mixer(x, impl=None):
    """The scan with what a KDA mixer puts round it, every operand a
    projection of x: (o, the decay projection's gate)."""
    def fc(width, name):
        return layers.fc(x, width, num_flatten_dims=2, bias_attr=False,
                         name=name)

    def branch(name):
        return layers.causal_conv1d(fc(H * D, name), 4, bias_attr=False,
                                    name=name + "_conv")

    q = layers.head_l2_norm(branch("q"), H, scale=D ** -0.5)
    k = layers.head_l2_norm(branch("k"), H)
    g = layers.kda_gate(fc(H * D, "a"), H, name="decay")
    beta = layers.sigmoid(layers.cast(fc(H, "beta"), "float32"))
    o = layers.kda_scan(q, k, branch("v"), g, beta, chunk_size=C,
                        block_chunks=1, impl=impl, name="kda")
    return layers.head_gated_rms_norm(o, fc(H, "gate"), name="norm")


def _net(recompute, impl):
    x = layers.data("x", shape=[T, 64], dtype="float32")
    u = layers.fc(x, 64, num_flatten_dims=2, bias_attr=False)
    out = layers.fc(_mixer(u, impl), 8, num_flatten_dims=2,
                    bias_attr=False)
    loss = layers.mean(layers.square(out))
    opt = optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints([u, out])
    return loss, opt.backward(loss)


def _run_net(recompute, impl, edit=None):
    _fresh()
    np.random.seed(0)
    loss, pg = _net(recompute, impl)
    if edit:
        edit(fluid.default_main_program())
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _counts()
    feed = {"x": np.random.RandomState(0).randn(2, T, 64).astype(
        np.float32)}
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed=feed, fetch_list=[loss] + [g for _, g in pg])
    return ({p.name: np.asarray(o) for (p, _), o in zip(pg, outs[1:])},
            float(np.asarray(outs[0]).reshape(-1)[0]), _since(before),
            fluid.default_main_program())


def test_grad_op_in_a_recompute_segment_reads_the_saved_residuals():
    want, want_loss, _, _ = _run_net(False, "xla")
    assert {"decay_A_log.w", "decay_dt_bias.w", "norm.w", "q_conv.w"} \
        <= set(want)
    for recompute in (False, True):
        got, loss, used, prog = _run_net(recompute, "interpret")
        # the forward kernel once, the backward on the saved states:
        # never a second forward for the grad op, nor a third for the
        # segment's replay
        assert used[("kda_scan", "interpret")] == 1, used
        assert used[("kda_scan_grad", "saved")] == 1, used
        assert ("kda_scan_grad", "recompute") not in used
        assert abs(loss - want_loss) <= 1e-5 * abs(want_loss)
        errors = {n: float(np.abs(got[n] - w).max() / np.abs(w).max())
                  for n, w in want.items()}
        assert all(e <= 1e-4 for e in errors.values()), errors
        if recompute:
            seg = [op for op in prog.global_block().ops
                   if op.type == "recompute_segment_grad"
                   and op.inputs.get("Saved")]
            scan, = [op for op in prog.global_block().ops
                     if op.type == "kda_scan"]
            assert len(seg) == 1 and seg[0].inputs["Saved"] == [
                scan.outputs[s][0] for s in ("O", "States", "Inverse")]
        else:
            gop, = [op for op in prog.global_block().ops
                    if op.type == "kda_scan_grad"]
            assert all(gop.inputs.get(s) for s in ("O", "States",
                                                   "Inverse"))


def test_unbound_states_differentiate_the_forward_again():
    args, go = operands(32)
    _, want_g = reference(args, go)
    before = _counts()
    grads = get_op_def("kda_scan_grad").compute(
        dict(zip(SLOTS, args), **{"O@GRAD": go}),
        {"chunk_size": 16, "block_chunks": 2, "impl": "interpret"})
    assert _since(before)[("kda_scan_grad", "recompute")] == 1
    assert all(rel(grads[s + "@GRAD"], g) <= TOL
               for s, g in zip(SLOTS, want_g))


@pytest.mark.parametrize("unbound", [("Inverse",), ("States",),
                                     ("O",), ("States", "Inverse")])
def test_any_residual_unbound_runs_the_forward_again_for_both(unbound):
    """A grad op with O and States but no Inverse is what a desc from
    before the slot holds: the forward kernel again for the states AND
    the inverses, counted `recompute`, and the gradients those of the
    saved path bit for bit."""
    attrs = {"chunk_size": 32, "block_chunks": 2, "impl": "interpret"}
    args, go = operands(128, b=1)
    ins = dict(zip(SLOTS, args))
    outs = get_op_def("kda_scan").compute(ins, attrs)
    grad = get_op_def("kda_scan_grad")
    bound = dict(ins, **outs, **{"O@GRAD": go})
    assert grad.reads_saved(bound, attrs)
    before = _counts()
    want = grad.compute(bound, attrs)
    assert _since(before) == {("kda_scan_grad", "saved"): 1}
    for slot in unbound:
        del bound[slot]
    assert not grad.reads_saved(bound, attrs)
    before = _counts()
    got = grad.compute(bound, attrs)
    assert _since(before) == {("kda_scan_grad", "recompute"): 1,
                              ("kda_scan", "interpret"): 1,
                              ("kda_scan_decay", "bounded"): 1}
    assert all(bool(jnp.array_equal(got[k], want[k])) for k in want)


@pytest.mark.parametrize("recompute", [False, True])
def test_a_desc_from_before_the_inverse_runs_the_forward_again(recompute):
    """append_backward binds Inverse on the grad op, or on the segment
    that holds it; with the slot taken off again (a training program
    saved before it existed) the step runs the forward kernel a second
    time, and gives the same gradients.  The grad op counts
    `recompute`; a segment differentiates its replay, and the grad op
    never runs."""
    want, want_loss, used, _ = _run_net(recompute, "interpret")
    assert used[("kda_scan_grad", "saved")] == 1

    def before_the_slot(prog):
        ops = prog.global_block().ops
        if not recompute:
            gop, = [op for op in ops if op.type == "kda_scan_grad"]
            assert gop.inputs.pop("Inverse")
            return
        seg, = [op for op in ops if op.type == "recompute_segment_grad"
                and op.inputs.get("Saved")]
        scan, = [op for op in ops if op.type == "kda_scan"]
        name, = scan.outputs["Inverse"]
        seg.inputs["Saved"].remove(name)
        seg.attrs["saved_names"] = [n for n in seg.attrs["saved_names"]
                                    if n != name]

    got, loss, used, _ = _run_net(recompute, "interpret", before_the_slot)
    assert ("kda_scan_grad", "saved") not in used
    assert used.get(("kda_scan_grad", "recompute"), 0) == (not recompute)
    assert used[("kda_scan", "interpret")] == 2
    assert abs(loss - want_loss) <= 1e-6 * abs(want_loss)
    errors = {n: float(np.abs(got[n] - w).max() / np.abs(w).max())
              for n, w in want.items()}
    assert all(e <= 1e-5 for e in errors.values()), errors


def test_a_desc_has_the_same_slots_under_every_impl():
    """The impl is an attr: the scan op, its grad op and a recompute
    segment's saved list name the same slots whichever computes them,
    and the vars behind them are declared alike."""
    def slots(recompute, impl):
        _fresh()
        np.random.seed(0)
        _net(recompute, impl)
        block = fluid.default_main_program().global_block()
        return [(op.type,
                 {s: [(n, block.var(n).dtype) for n in names]
                  for s, names in op.inputs.items()},
                 {s: [(n, block.var(n).dtype) for n in names]
                  for s, names in op.outputs.items()})
                for op in block.ops
                if op.type in ("kda_scan", "kda_scan_grad",
                               "recompute_segment_grad")]

    for recompute in (False, True):
        want = slots(recompute, "xla")
        assert slots(recompute, "interpret") == want
        assert slots(recompute, None) == want
        scan = want[0]
        assert scan[0] == "kda_scan" and set(scan[2]) == {
            "O", "States", "Inverse"}
        assert scan[2]["Inverse"][0][1] == scan[2]["States"][0][1]


def test_amp_keeps_the_decays_beta_the_states_and_the_inverse_float32():
    from paddle_tpu.contrib.mixed_precision import decorate

    _fresh()
    np.random.seed(0)
    x = layers.data("x", shape=[T, 64], dtype="float32")
    loss = layers.mean(layers.fc(_mixer(x), 8, num_flatten_dims=2))
    decorate(optimizer.SGD(0.0), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(loss)
    block = fluid.default_main_program().global_block()
    scan, = [op for op in block.ops if op.type == "kda_scan"]
    gate, = [op for op in block.ops if op.type == "kda_gate"]
    # the rates and the bias are read as they are: float32
    assert gate.inputs["ALog"] == ["decay_A_log.w"]
    assert gate.inputs["DtBias"] == ["decay_dt_bias.w"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(0).randn(2, T, 64).astype(
        np.float32)}
    o, states, inverse, g, beta, q, k, v = exe.run(
        fluid.CompiledProgram(fluid.default_main_program()), feed=feed,
        fetch_list=[scan.outputs[s][0] for s in ("O", "States", "Inverse")]
        + [scan.inputs[s][0] for s in ("G", "Beta", "Q", "K", "V")],
        return_numpy=False)
    assert o.dtype == q.dtype == k.dtype == v.dtype == jnp.bfloat16
    assert states.dtype == g.dtype == beta.dtype == jnp.float32
    # the inverse is float32 where it is kept, and is the solve of the
    # bfloat16 K the scan read
    assert inverse.dtype == jnp.float32
    assert inverse.shape == (2, H, T // C, C, C)
    want = solved_inverses(k.astype(jnp.float32), g, beta, C)
    assert rel(chunk_inverses(inverse, C), want) <= TOL
    gop, = [op for op in block.ops if op.type == "kda_scan_grad"]
    assert gop.inputs["Inverse"] == scan.outputs["Inverse"]
    assert float(g.min()) > -5.0 and float(g.max()) < 0.0


# -- the gate, the L2 norm, the gated norm -----------------------------------

def test_kda_gate_is_bounded_and_is_the_formula():
    r = np.random.RandomState(1)
    x = jnp.asarray(30 * r.randn(2, 8, 4 * 16), jnp.float32)
    a_log = jnp.asarray(r.uniform(0, 1.4, 4), jnp.float32)
    bias = jnp.asarray(r.randn(64), jnp.float32)
    op = get_op_def("kda_gate")
    g = op.compute({"X": x, "ALog": a_log, "DtBias": bias},
                   {"lower_bound": -5.0})["G"]
    want = -5.0 / (1 + np.exp(-np.repeat(np.exp(a_log), 16)
                              * (np.asarray(x, np.float64) + bias)))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    assert g.dtype == jnp.float32
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    # float32 whatever the projection's dtype
    assert op.compute({"X": x.astype(jnp.bfloat16), "ALog": a_log,
                       "DtBias": bias}, {"lower_bound": -5.0}
                      )["G"].dtype == jnp.float32
    with pytest.raises(ValueError, match="not negative"):
        op.compute({"X": x, "ALog": a_log, "DtBias": bias},
                   {"lower_bound": 5.0})


def test_kda_gate_softplus_form_has_no_bound_and_is_the_formula():
    r = np.random.RandomState(1)
    x = jnp.asarray(30 * r.randn(2, 8, 4 * 16), jnp.float32)
    a_log = jnp.asarray(r.uniform(0, 1.4, 4), jnp.float32)
    bias = jnp.asarray(r.randn(64), jnp.float32)
    op = get_op_def("kda_gate")
    before = _counts()
    g = op.compute({"X": x, "ALog": a_log, "DtBias": bias},
                   {"form": "softplus"})["G"]
    assert _since(before) == {("kda_gate_form", "softplus"): 1}
    z = np.asarray(x, np.float64) + np.asarray(bias, np.float64)
    want = -np.repeat(np.exp(np.asarray(a_log, np.float64)), 16) \
        * np.logaddexp(0.0, z)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    assert g.dtype == jnp.float32 and float(g.max()) <= 0.0
    # far below the other form's bound
    assert float(g.min()) < -100.0
    assert op.compute({"X": x.astype(jnp.bfloat16), "ALog": a_log,
                       "DtBias": bias}, {"form": "softplus"}
                      )["G"].dtype == jnp.float32
    before = _counts()
    op.compute({"X": x, "ALog": a_log, "DtBias": bias}, {})
    assert _since(before) == {("kda_gate_form", "sigmoid_bound"): 1}
    with pytest.raises(ValueError, match="form"):
        op.compute({"X": x, "ALog": a_log, "DtBias": bias},
                   {"form": "relu"})


def test_kda_gate_softplus_starts_with_channels_that_keep_their_state():
    """The softplus form starts where the sigmoid form does: -g
    log-uniform over [1e-4, 1e-1] a channel at input 0."""
    _fresh()
    np.random.seed(3)
    x = layers.data("x", shape=[4, 8 * 128], dtype="float32")
    g = layers.kda_gate(x, 8, name="decay", form="softplus")
    assert g.kda_decay_bound is None
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got, = exe.run(feed={"x": np.zeros((1, 4, 1024), np.float32)},
                   fetch_list=[g])
    g0 = -np.asarray(got)[0, 0]
    assert 0.9e-4 < g0.min() < 2e-4 and 0.5e-1 < g0.max() < 1.1e-1
    assert np.mean(np.exp(-2000 * g0) > 1 / 3) > 0.1


def test_kda_gate_starts_with_channels_that_keep_their_state():
    """At input 0 the layer's initial gate spreads -g log-uniformly
    over [1e-4, 1e-1] a channel: a tenth of the channels keep more than
    a third of their state over 2,000 tokens (PR 38's finding: with a
    state that forgets in a few tokens no check sees a broken pass
    between chunks)."""
    _fresh()
    np.random.seed(3)
    x = layers.data("x", shape=[4, 8 * 128], dtype="float32")
    g = layers.kda_gate(x, 8, name="decay")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got, = exe.run(feed={"x": np.zeros((1, 4, 1024), np.float32)},
                   fetch_list=[g])
    g0 = -np.asarray(got)[0, 0]
    assert 0.9e-4 < g0.min() < 2e-4 and 0.5e-1 < g0.max() < 1.1e-1
    assert np.mean(np.exp(-2000 * g0) > 1 / 3) > 0.1


def test_head_l2_norm_is_a_norm_a_head():
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(2, 5, 3 * 8), jnp.float32)
    y = get_op_def("head_l2_norm").compute(
        {"X": x}, {"n_head": 3, "scale": 0.5, "epsilon": 1e-6})["Y"]
    xh = np.asarray(x).reshape(2, 5, 3, 8)
    want = 0.5 * xh / np.sqrt((xh ** 2).sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(y, want.reshape(2, 5, 24), rtol=1e-5)
    got = get_op_def("head_l2_norm").compute(
        {"X": x.astype(jnp.bfloat16)},
        {"n_head": 3, "scale": 0.5, "epsilon": 1e-6})["Y"]
    assert got.dtype == jnp.bfloat16


def test_head_gated_rms_norm_gates_a_head_after_the_norm():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(2, 5, 3 * 8), jnp.float32)
    gate = jnp.asarray(r.randn(2, 5, 3), jnp.float32)
    scale = jnp.asarray(r.uniform(0.5, 1.5, 8), jnp.float32)
    op = get_op_def("head_gated_rms_norm")
    y = op.compute({"X": x, "Gate": gate, "Scale": scale},
                   {"epsilon": 1e-6})["Y"]
    xh = np.asarray(x).reshape(2, 5, 3, 8)
    normed = xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-6) * scale
    sig = 1 / (1 + np.exp(-np.asarray(gate)))[..., None]
    np.testing.assert_allclose(y, (sig * normed).reshape(2, 5, 24),
                               rtol=1e-5, atol=1e-6)
    # without a scale: the gate alone (latent attention's output gate)
    y = op.compute({"X": x, "Gate": gate}, {"epsilon": 1e-6})["Y"]
    np.testing.assert_allclose(y, (sig * xh).reshape(2, 5, 24),
                               rtol=1e-5, atol=1e-6)
    # the gate is NOT inside the norm: a head's gate scales its output
    doubled = op.compute({"X": x, "Gate": gate + 1.0, "Scale": scale},
                         {"epsilon": 1e-6})["Y"]
    ratio = (1 / (1 + np.exp(-np.asarray(gate) - 1.0)))[..., None] / sig
    np.testing.assert_allclose(
        np.asarray(doubled).reshape(2, 5, 3, 8), sig * normed * ratio,
        rtol=1e-5, atol=1e-6)


def test_head_gated_rms_norm_gates_a_channel():
    """A gate as wide as X: one logit a CHANNEL, after the norm a
    head; without a scale the gate alone (a grouped-KV attention
    layer's output gate)."""
    r = np.random.RandomState(4)
    x = jnp.asarray(r.randn(2, 5, 3 * 8), jnp.float32)
    gate = jnp.asarray(r.randn(2, 5, 3 * 8), jnp.float32)
    scale = jnp.asarray(r.uniform(0.5, 1.5, 8), jnp.float32)
    op = get_op_def("head_gated_rms_norm")
    y = op.compute({"X": x, "Gate": gate, "Scale": scale},
                   {"epsilon": 1e-6, "n_head": 3})["Y"]
    xh = np.asarray(x).reshape(2, 5, 3, 8)
    normed = (xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-6)
              * scale).reshape(2, 5, 24)
    sig = 1 / (1 + np.exp(-np.asarray(gate)))
    np.testing.assert_allclose(y, sig * normed, rtol=1e-5, atol=1e-6)
    # a gate a channel is not a gate a head: the head's mean logit
    # gives another output
    a_head = op.compute(
        {"X": x, "Gate": gate.reshape(2, 5, 3, 8).mean(-1),
         "Scale": scale}, {"epsilon": 1e-6})["Y"]
    assert float(jnp.abs(a_head - y).max()) > 0.1
    y = op.compute({"X": x, "Gate": gate}, {"epsilon": 1e-6})["Y"]
    np.testing.assert_allclose(y, sig * np.asarray(x), rtol=1e-5,
                               atol=1e-6)
    got = op.compute({"X": x.astype(jnp.bfloat16), "Gate": gate,
                      "Scale": scale}, {"epsilon": 1e-6, "n_head": 3})["Y"]
    assert got.dtype == jnp.bfloat16
    # the layer: a gate a channel takes the heads beside it
    _fresh()
    xv = layers.data("x", shape=[5, 24], dtype="float32")
    gv = layers.data("g", shape=[5, 24], dtype="float32")
    hv = layers.data("h", shape=[5, 3], dtype="float32")
    layers.head_gated_rms_norm(xv, gv, n_head=3, name="n1")
    block = fluid.default_main_program().global_block()
    assert block.var("n1.w").shape == (8,)
    layers.head_gated_rms_norm(xv, gv, norm=False)
    assert block.ops[-1].attrs["n_head"] == 0 \
        and "Scale" not in block.ops[-1].inputs
    with pytest.raises(ValueError, match="one of a gate and n_head"):
        layers.head_gated_rms_norm(xv, hv, n_head=3)
    with pytest.raises(ValueError, match="one of a gate and n_head"):
        layers.head_gated_rms_norm(xv, None, n_head=3, norm=False)


# -- tools/kda_price.py, on no chip ------------------------------------------

def test_the_price_tool_rehearses_on_a_cpu(tmp_path):
    """`--tiny`, this checkout on both sides: a row a side, dtype and
    variant, no time read off a CPU, and the two sides' outputs and
    gradients alike in every element (exit 0)."""
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "kda_price", os.path.join(repo, "tools", "kda_price.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = str(tmp_path / "price.json")
    assert tool.main(["--tiny", "--parent", repo, "--out", out]) == 0
    rows = json.load(open(out))["rows"]
    priced = [r for r in rows if "side" in r]
    assert {(r["side"], r["dtype"], r["variant"]) for r in priced} == {
        (s, d, v) for s in ("parent", "change")
        for d in ("bfloat16", "float32")
        for v in ("whole", "inverse_as_I-N")}
    assert all(r["fwd_ms"] is None and r["bwd_ms"] is None for r in priced)
    diffs = [r["diff"] for r in rows if "diff" in r]
    assert len(diffs) == 2 and not any(v for d in diffs for v in d.values())

    # a change whose gradients moved shows in the row main() exits 1 on
    change = tool.load(repo, "kda_moved")
    bwd = change.kda_bwd_pallas.__wrapped__
    change.kda_bwd_pallas.__wrapped__ = lambda *a, **k: tuple(
        g * 1.5 for g in bwd(*a, **k))
    rows = tool.price({"parent": tool.load(repo, "kda_parent"),
                       "change": change}, jnp.float32, 64, 1, 128, (32, 2),
                      True)
    diff = rows[-1]["diff"]
    assert diff["o"] == diff["states"] == 0.0
    assert all(diff[g] > 0 for g in ("dq", "dk", "dv", "dg", "dbeta"))


# -- tools/kda_unbounded_chip.py, on no chip ----------------------------------

def test_the_unbounded_chip_tool_rehearses_on_a_cpu(tmp_path):
    """`--tiny` (interpret mode): the unbounded path equals the tool's
    own token-by-token recurrence in the three cases and both operand
    dtypes (exit 0: every float32 case inside this file's tolerances),
    and the bounded path at g = -30 is shown wrong by the same
    comparison."""
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "kda_unbounded_chip",
        os.path.join(repo, "tools", "kda_unbounded_chip.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert (tool.TOL["g=-30"], tool.TOL["mixed"]) == (
        TOL, UNBOUNDED_TOL["mixed"])
    out = str(tmp_path / "rows.json")
    assert tool.main(["--tiny", "--out", out]) == 0
    rows = json.load(open(out))["rows"]
    held = [r for r in rows if r["decay"] == "unbounded"]
    assert {(r["case"], r["dtype"]) for r in held} == {
        (c, d) for c in ("g=-30", "mixed", "cell")
        for d in ("float32", "bfloat16")}
    assert all(r["finite"] for r in rows)
    assert all(r["worst"] <= tool.TOL[r["case"]] for r in held
               if r["dtype"] == "float32")
    bounded = {r["case"]: r for r in rows if r["decay"] == "bounded"}
    assert bounded["g=-30"]["errors"]["O"] > 100 * TOL
    assert bounded["cell"]["worst"] <= TOL
    # the tool's recurrence is the tests' own
    args, _ = _unbounded_case("mixed")
    with jax.default_matmul_precision("highest"):
        assert rel(tool.recurrence(*(jnp.tile(a, (1, 2, 1))
                                     for a in args)),
                   token_by_token(*(jnp.tile(a, (1, 2, 1))
                                    for a in args))) <= 1e-6
