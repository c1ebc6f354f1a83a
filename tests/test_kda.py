"""``dl.pallas_kda``: the chunked form of a window and the decode step
compute the rows and the state of the plain per-channel recurrence, at
decays where a naive factorisation overflows; the Pallas kernels
(interpreter) are their lax twins; keys that point one way keep the
recurrence; the scalar rule with the decay averaged over a head's channels
is a different function."""

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.dl.pallas_gated_delta import gated_delta_rule
from mmlspark_tpu.dl.pallas_kda import CHUNK, SUB, kda_rule, lower_inverse


def _inputs(seed, S, w, H, dk, dv, dtype, *, A=(1e-3, 1.0), rows=5):
    """Normalised q and k, ``g = -A softplus(a + 1)`` a channel with ``A``
    log-uniform over the range given a head (the family's init draws it
    from [1, 16])."""
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((S, w, H, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((S, w, H, dk)))
    v = rng.standard_normal((S, w, H, dv))
    a = np.exp(rng.uniform(np.log(A[0]), np.log(A[1]), (H, 1)))
    g = -a * np.log1p(np.exp(rng.standard_normal((S, w, H, dk)) + 1.0))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((S, w, H))))
    state = rng.standard_normal((rows, H, dk, dv)).astype(np.float32)
    cast = lambda x: jnp.asarray(x, jnp.float32).astype(dtype)
    return (cast(q), cast(k), cast(v), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32), jnp.asarray(state))


def _recurrence(q, k, v, g, beta, s, n):
    """The rule as written, one token at a time, float64."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s = np.asarray(s, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(n):
        s = np.exp(g[t])[:, :, None] * s
        d = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + np.einsum("hk,hv->hkv", k[t], d)
        out[t] = np.einsum("hkv,hk->hv", s, q[t])
    return out, s


@pytest.mark.parametrize("impl,interpret,A", [
    ("lax", None, (1e-3, 1.0)), ("lax", None, (1.0, 16.0)),
    ("pallas", True, (1.0, 16.0))])
def test_a_window_is_the_recurrence_with_state_carried(impl, interpret, A):
    """float32 operands, so rounding to the serving type is out of the
    picture: a window of 70 rows (two chunks, the second mostly padding)
    on a carried state, one on a fresh sequence, one cut short by
    ``lens``; at slow decays and at the family's (``exp(A_log)`` in [1,
    16]: a chunk's running decay falls to about -1,000): outputs and state
    within 2e-4 of the float64 recurrence."""
    S, w, H, dk, dv = 3, 70, 2, 16, 16
    q, k, v, g, beta, state = _inputs(0, S, w, H, dk, dv, jnp.float32, A=A)
    srows = jnp.asarray([2, 4, 1])
    pos = jnp.asarray([40, 0, 8])
    lens = jnp.asarray([70, 70, 37])
    o, new = kda_rule(q, k, v, g, beta, state, srows, pos, lens, impl=impl,
                      interpret=interpret)
    assert np.all(np.isfinite(np.asarray(o)))
    for i in range(S):
        s0 = np.asarray(state[srows[i]]) if int(pos[i]) else \
            np.zeros((H, dk, dv))
        n = int(lens[i])
        want_o, want_s = _recurrence(q[i], k[i], v[i], g[i], beta[i], s0, n)
        np.testing.assert_allclose(np.asarray(o[i, :n]), want_o[:n],
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(new[srows[i]]), want_s,
                                   atol=2e-4)
    # rows nobody holds are untouched
    np.testing.assert_array_equal(np.asarray(new[3]), np.asarray(state[3]))


def test_the_naive_factorisation_overflows_where_the_folded_one_does_not():
    """At the family's decays one chunk's running decay reaches hundreds:
    ``(k exp(gamma)) (k exp(-gamma))^T`` is not finite, the sub-chunked
    fold is (the test above holds it to the recurrence)."""
    _, k, _, g, _, _ = _inputs(0, 1, CHUNK, 2, 16, 16, jnp.float32,
                               A=(1.0, 16.0))
    gamma = np.cumsum(np.asarray(g[0], np.float64), axis=0)
    assert gamma.min() < -200 and SUB < CHUNK
    kk = np.asarray(k[0], np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        naive = np.einsum("thd,shd->hts", kk * np.exp(gamma).astype(
            np.float32), kk * np.exp(-gamma).astype(np.float32))
    assert not np.all(np.isfinite(naive))


@pytest.mark.parametrize("impl,interpret", [("lax", None),
                                            ("pallas", True)])
def test_keys_that_point_one_way_keep_the_recurrence(impl, interpret):
    """Keys that share most of their direction, at slow decays, as a layer
    past a long attention's output feeds them: ``L`` of a chunk is dense
    with entries near ``beta``. Two chunks within 2e-4 of the float64
    recurrence; the inverse by the product ``(I - L)(I + L^2)(I + L^4)...``
    of the same ``L`` is off by far more than the state's size."""
    S, w, H, dk = 1, 2 * CHUNK, 2, 32
    rng = np.random.default_rng(3)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    common = rng.standard_normal((1, 1, H, dk))
    k = unit(common + 0.1 * rng.standard_normal((S, w, H, dk)))
    q = unit(common + 0.1 * rng.standard_normal((S, w, H, dk))) * dk ** -0.5
    v = rng.standard_normal((S, w, H, dk))
    g = -1e-3 * np.log1p(np.exp(rng.standard_normal((S, w, H, dk)) + 1.0))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((S, w, H)) - 1.0))
    state = np.zeros((2, H, dk, dk), np.float32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, new = kda_rule(f32(q), f32(k), f32(v), f32(g), f32(beta), f32(state),
                      jnp.asarray([1]), jnp.asarray([0]), jnp.asarray([w]),
                      impl=impl, interpret=interpret)
    want_o, want_s = _recurrence(q[0], k[0], v[0], g[0], beta[0], state[1],
                                 w)
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new[1]), want_s, atol=2e-4)
    # the first chunk's L and its inverse as products of its powers
    kk = k[0, :CHUNK, 0].astype(np.float32)
    lower = np.tril(beta[0, :CHUNK, 0, None] * (kk @ kk.T), -1) \
        .astype(np.float32)
    exact = np.linalg.inv(np.eye(CHUNK) + lower.astype(np.float64))
    inv, power = np.eye(CHUNK, dtype=np.float32) - lower, -lower
    for _ in range(5):
        power = power @ power
        inv = inv + inv @ power
    assert np.abs(inv - exact).max() > 1.0 > 1e-4 > np.abs(
        np.asarray(lower_inverse(jnp.asarray(lower))) - exact).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windows_cut_anywhere_and_the_step_give_one_state(dtype):
    """A window fed whole (lax), the same rows as windows of 23, 1 and 26
    rows (interpreter: the chunk kernel, the step kernel, the chunk kernel)
    and the step kernel a row at a time end in the same state and give the
    same rows: float32 operands 2e-4; bfloat16 operands 3e-2 on rows and
    states of size 1 (each form rounds the state and what it writes at its
    own points: 2^-9 a product, over 50 tokens)."""
    S, w, H, dk, dv = 2, 50, 2, 16, 16
    q, k, v, g, beta, state = _inputs(1, S, w, H, dk, dv, dtype)
    srows = jnp.asarray([1, 3])
    pos = jnp.asarray([12, 0])
    want_o, want_s = kda_rule(q, k, v, g, beta, state, srows, pos,
                              jnp.full((S,), w), impl="lax")
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    for cuts in ((0, 23, 24, 50), tuple(range(0, 51, 25 if dtype ==
                                              jnp.float32 else 1))):
        s, outs = state, []
        for a, b in zip(cuts[:-1], cuts[1:]):
            o, s = kda_rule(q[:, a:b], k[:, a:b], v[:, a:b], g[:, a:b],
                            beta[:, a:b], s, srows, pos + a,
                            jnp.full((S,), b - a), impl="pallas",
                            interpret=True)
            outs.append(o)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                                   np.asarray(want_o), atol=tol)
        np.testing.assert_allclose(np.asarray(s[srows]),
                                   np.asarray(want_s[srows]), atol=tol)


def test_the_step_kernel_is_its_lax_twin_and_skips_absent_slots():
    """Same formulation, same rounding points: the interpreter's step and
    the lax step agree to float32 rounding on bfloat16 operands; a slot
    with ``lens`` 0 leaves its row as it was; a slot at position 0 starts
    from zero whatever its row holds."""
    S, H, dk, dv = 4, 2, 16, 16
    q, k, v, g, beta, state = _inputs(2, S, 1, H, dk, dv, jnp.bfloat16)
    srows = jnp.asarray([1, 2, 0, 4])
    pos = jnp.asarray([5, 0, 0, 3])
    lens = jnp.asarray([1, 1, 0, 0])
    got = kda_rule(q, k, v, g, beta, state, srows, pos, lens,
                   impl="pallas", interpret=True)
    want = kda_rule(q, k, v, g, beta, state, srows, pos, lens, impl="lax")
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1][4]),
                                  np.asarray(state[4]))
    fresh, _ = _recurrence(q[1], k[1], v[1], g[1], beta[1],
                           np.zeros((H, dk, dv)), 1)
    np.testing.assert_allclose(np.asarray(got[0][1]), fresh, atol=2e-2)


def test_the_decay_averaged_over_channels_is_another_function():
    """The control: the scalar rule (``dl.pallas_gated_delta``) given the
    mean of a head's channel decays misses the per-channel recurrence by
    far more than the tolerance the KDA rule meets."""
    S, w, H, dk, dv = 1, 40, 2, 16, 16
    q, k, v, g, beta, state = _inputs(3, S, w, H, dk, dv, jnp.float32,
                                      A=(1.0, 16.0))
    srows, pos, lens = jnp.asarray([1]), jnp.asarray([7]), jnp.asarray([w])
    want_o, want_s = _recurrence(q[0], k[0], v[0], g[0], beta[0],
                                 np.asarray(state[1]), w)
    o, s = kda_rule(q, k, v, g, beta, state, srows, pos, lens, impl="lax")
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=2e-4)
    o_avg, s_avg = gated_delta_rule(q, k, v, jnp.mean(g, -1), beta, state,
                                    srows, pos, lens, impl="lax")
    assert np.max(np.abs(np.asarray(o_avg[0]) - want_o)) > 50 * 2e-4
    assert np.max(np.abs(np.asarray(s_avg[1]) - want_s)) > 50 * 2e-4
