"""``dl.pallas_gated_delta``: the chunked form of a window, the decode step
token by token and the plain recurrence compute the same rows and the same
state; the Pallas kernels (interpreter) are their lax twins."""

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.dl.pallas_gated_delta import CHUNK, gated_delta_rule


def _inputs(seed, S, w, H, dk, dv, dtype, rows=5):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((S, w, H, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((S, w, H, dk)))
    v = rng.standard_normal((S, w, H, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), 0.0, (S, w, H))) \
        * np.log1p(np.exp(rng.standard_normal((S, w, H)) + 1.0))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((S, w, H))))
    state = rng.standard_normal((rows, H, dk, dv)).astype(np.float32)
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(dtype)
    return (cast(q), cast(k), cast(v), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32), jnp.asarray(state))


def _recurrence(q, k, v, g, beta, s, n):
    """The rule as written, one token at a time, float64."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s = np.asarray(s, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(n):
        s = np.exp(g[t])[:, None, None] * s
        d = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + np.einsum("hk,hv->hkv", k[t], d)
        out[t] = np.einsum("hkv,hk->hv", s, q[t])
    return out, s


@pytest.mark.parametrize("impl,interpret", [("lax", None), ("pallas", True)])
def test_a_window_is_the_recurrence_with_state_carried(impl, interpret):
    """float32 operands, so rounding to the serving type is out of the
    picture: a window of 70 rows (two chunks, the second part padding) on
    a carried state, one on a fresh sequence, one cut short by ``lens``:
    outputs and state within 2e-4 of the float64 recurrence (float32
    products and a 64-row inverse by five squarings)."""
    S, w, H, dk, dv = 3, 70, 2, 16, 16
    q, k, v, g, beta, state = _inputs(0, S, w, H, dk, dv, jnp.float32)
    srows = jnp.asarray([2, 4, 1])
    pos = jnp.asarray([40, 0, 8])
    lens = jnp.asarray([70, 70, 37])
    o, new = gated_delta_rule(q, k, v, g, beta, state, srows, pos, lens,
                              impl=impl, interpret=interpret)
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_step_token_by_token_is_the_chunk(dtype):
    """The decode kernel (interpreter) fed a window's rows one at a time
    ends in the state the chunked form (lax) ends in and gave the same
    rows: float32 operands 2e-4; bfloat16 operands 3e-2 on rows of size 1
    and states of size 1 (each form rounds the state and what it writes to
    bfloat16 at its own points: 2^-9 a product, over 20 tokens)."""
    S, w, H, dk, dv = 2, 20, 2, 16, 16
    q, k, v, g, beta, state = _inputs(1, S, w, H, dk, dv, dtype)
    srows = jnp.asarray([1, 3])
    pos = jnp.asarray([12, 0])
    lens = jnp.full((S,), w)
    want_o, want_s = gated_delta_rule(q, k, v, g, beta, state, srows, pos,
                                      lens, impl="lax")
    s, outs = state, []
    for t in range(w):
        o, s = gated_delta_rule(
            q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], g[:, t:t + 1],
            beta[:, t:t + 1], s, srows, pos + t, jnp.ones((S,), jnp.int32),
            impl="pallas", interpret=True)
        outs.append(o)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(want_o), atol=tol)
    np.testing.assert_allclose(np.asarray(s[srows]),
                               np.asarray(want_s[srows]), atol=tol)


def test_the_step_kernel_is_its_lax_twin_and_skips_absent_slots():
    """Same formulation, same rounding points: the interpreter's step and
    the lax step agree to float32 rounding on bfloat16 operands; a slot
    with ``lens`` 0 leaves its row as it was."""
    S, H, dk, dv = 4, 2, 16, 16
    q, k, v, g, beta, state = _inputs(2, S, 1, H, dk, dv, jnp.bfloat16)
    srows = jnp.asarray([1, 2, 0, 4])
    pos = jnp.asarray([5, 9, 0, 3])
    lens = jnp.asarray([1, 1, 0, 0])
    got = gated_delta_rule(q, k, v, g, beta, state, srows, pos, lens,
                           impl="pallas", interpret=True)
    want = gated_delta_rule(q, k, v, g, beta, state, srows, pos, lens,
                            impl="lax")
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1][4]),
                                  np.asarray(state[4]))
    assert CHUNK == 64
