"""LLM serving engine (``serving/llm.py``): disaggregated prefill and
decode over the paged KV cache, speculation inside the continuous
batch, and the generation-mode load/bench plumbing.

The load-bearing contract is token identity: greedy paged serving —
plain, speculative with a real (disagreeing) draft, and self-draft —
must produce byte-for-byte the tokens ``dl.generate`` produces per
prompt. Everything else (prefix reuse, TTFT split, steady-state
compiles, handoff) is asserted on the obs registry the benches bank
from.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.dl import MaskedLMModel, TextEncoder, generate, \
    make_attention_fn
from mmlspark_tpu.obs.metrics import MetricsRegistry
from mmlspark_tpu.obs.profile import compile_tracker
from mmlspark_tpu.serving.llm import (HandoffQueue, LLMEngine,
                                      pack_handoff, unpack_handoff)

VOCAB, MAXNEW = 32, 4


@pytest.fixture(scope="module")
def lm():
    enc = TextEncoder(vocab=VOCAB, width=16, depth=1, heads=2,
                      mlp_dim=32, dtype=jnp.float32,
                      attention_fn=make_attention_fn("dense",
                                                     causal=True))
    module = MaskedLMModel(enc)
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    return module, variables


@pytest.fixture(scope="module")
def draft_lm(lm):
    module, _ = lm
    # same architecture, different weights: a draft that genuinely
    # disagrees with the target some of the time
    variables = module.init(jax.random.PRNGKey(7),
                            np.zeros((1, 8), np.int32))
    return module, variables


def _prompts(seed=0, sizes=(3, 5, 2, 6, 4)):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, VOCAB, size=n).astype(np.int32)
            for n in sizes]


def _ref(lm, prompts, max_new=MAXNEW):
    module, variables = lm
    return {i: np.asarray(generate(module, variables, p[None, :],
                                   max_new_tokens=max_new,
                                   temperature=0.0)[0])
            for i, p in enumerate(prompts)}


class TestHandoff:
    def test_pack_unpack_roundtrip(self):
        payload = {"seq": {"seq_id": "s0", "chain": [3, 1, 2],
                           "length": 9, "prompt_len": 9,
                           "reused_tokens": 4},
                   "first": 17, "max_new_tokens": 8}
        assert unpack_handoff(pack_handoff(payload)) == payload
        # deterministic bytes (sort_keys): the lease envelope may hash
        assert pack_handoff(payload) == pack_handoff(
            dict(reversed(list(payload.items()))))

    def test_queue_is_fifo_and_wire_shaped(self):
        q = HandoffQueue()
        q.push({"seq": {"seq_id": 0}, "first": 1, "max_new_tokens": 2})
        q.push({"seq": {"seq_id": 1}, "first": 2, "max_new_tokens": 2})
        assert len(q) == 2
        got = q.pull(1)
        assert [p["seq"]["seq_id"] for p in got] == [0]
        assert q.pull(5)[0]["seq"]["seq_id"] == 1
        assert q.pull(1) == []


class TestGreedyIdentity:
    def test_paged_matches_generate(self, lm):
        module, variables = lm
        prompts = _prompts()
        ref = _ref(lm, prompts)
        eng = LLMEngine(module, variables, slots=2, block_len=4,
                        max_seq_len=16, registry=MetricsRegistry())
        for i, p in enumerate(prompts):
            eng.submit(i, p, MAXNEW)
        got = eng.run_until_drained()
        assert set(got) == set(ref)
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])

    def test_speculative_matches_generate(self, lm, draft_lm):
        module, variables = lm
        dmod, dvar = draft_lm
        prompts = _prompts(seed=3)
        ref = _ref(lm, prompts)
        reg = MetricsRegistry()
        eng = LLMEngine(module, variables, draft_module=dmod,
                        draft_variables=dvar, slots=2, block_len=4,
                        max_seq_len=16, spec_k=2, registry=reg)
        for i, p in enumerate(prompts):
            eng.submit(i, p, MAXNEW)
        got = eng.run_until_drained()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        ratio = reg.snapshot().get(
            'gen_spec_accept_ratio{service="llm"}')
        assert ratio is not None and 0.0 <= ratio <= 1.0

    def test_self_draft_accepts_everything(self, lm):
        module, variables = lm
        prompts = _prompts(seed=5, sizes=(4, 3))
        ref = _ref(lm, prompts)
        reg = MetricsRegistry()
        eng = LLMEngine(module, variables, draft_module=module,
                        draft_variables=variables, slots=2, block_len=4,
                        max_seq_len=16, spec_k=2, registry=reg)
        for i, p in enumerate(prompts):
            eng.submit(i, p, MAXNEW)
        got = eng.run_until_drained()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        # draft == target: every proposal must be accepted
        assert reg.snapshot()[
            'gen_spec_accept_ratio{service="llm"}'] == 1.0

    def test_single_token_budget(self, lm):
        # the prefill-produced first token IS the whole budget: the
        # sequence must finish without a decode step ever running
        module, variables = lm
        p = _prompts(seed=9, sizes=(5,))[0]
        ref = _ref(lm, [p], max_new=1)
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=16, registry=MetricsRegistry())
        eng.submit(0, p, 1)
        got = eng.run_until_drained()
        np.testing.assert_array_equal(got[0], ref[0][:len(p) + 1])


class TestChunkedPrefill:
    """A suffix wider than the kernel's widest window is fed in
    ``max_window`` chunks (the width is VMEM-bound on the chip; here it
    is steered down to 8 so that tiny prompts chunk)."""

    @pytest.fixture
    def narrow(self, monkeypatch):
        import mmlspark_tpu.dl.pallas_paged_attention as paged
        monkeypatch.setattr(paged, "max_window", lambda *a: 8)

    @pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "spec"])
    def test_chunked_matches_generate(self, lm, draft_lm, narrow,
                                      spec_k):
        module, variables = lm
        # 1, 2 and 3+ chunks in one batch, plus an exact multiple
        prompts = _prompts(seed=17, sizes=(5, 13, 21, 16))
        ref = _ref(lm, prompts)
        draft = dict(draft_module=draft_lm[0],
                     draft_variables=draft_lm[1]) if spec_k else {}
        eng = LLMEngine(module, variables, slots=2, block_len=4,
                        max_seq_len=32, prefill_batch=4, spec_k=spec_k,
                        registry=MetricsRegistry(), **draft)
        assert eng.prefiller.max_window == 8
        assert eng.prefiller.windows_for(21) == [8, 8, 8]
        assert eng.prefiller.windows_for(16) == [8, 8]
        for i, p in enumerate(prompts):
            eng.submit(i, p, MAXNEW)
        got = eng.run_until_drained()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        # no window wider than the cap was ever built
        assert max(w for _, w, _ in eng.programs.built
                   if w is not None) <= 8

    def test_chunk_after_reused_prefix_and_steady_state(self, lm,
                                                        narrow):
        module, variables = lm
        p = _prompts(seed=19, sizes=(27,))[0]
        ref = _ref(lm, [p])
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=32, service="llmchunk",
                        registry=MetricsRegistry())
        # warm() takes suffix LENGTHS: 27 -> 8, 8, 8 and the bucket of
        # 3; the warm repeat re-feeds 27 - 24 reused = 3 tokens
        fps = eng.warm(prefill_windows=(27, 1), mark_steady=True)
        try:
            eng.submit("cold", p, MAXNEW)
            cold = eng.run_until_drained()["cold"]
            eng.submit("warm", p, MAXNEW)
            warm = eng.run_until_drained()["warm"]
            compile_tracker.assert_steady_state()
        finally:
            compile_tracker.unmark_steady()
        np.testing.assert_array_equal(cold, ref[0][:len(p) + MAXNEW])
        np.testing.assert_array_equal(warm, cold)
        # a chunk before the last may hold no prompt's end: the widest
        # window also has its program with no head
        assert {n for n in fps if "prefill" in n} == {
            "llm_prefill_llmchunk_w1_b2", "llm_prefill_llmchunk_w4_b2",
            "llm_prefill_llmchunk_w8_b2",
            "llm_prefill_llmchunk_w8_b2_nohead"}


# -- the head runs only at the row a token is sampled from ------------------

HEAD_VOCAB = {"per_head": 37, "latent_moe": 251}   # no other size of theirs


@pytest.fixture(scope="module", params=sorted(HEAD_VOCAB))
def decoder(request):
    """``(module, variables, vocabulary)`` of each decoder the engine
    takes, tiny, with a vocabulary no other of its sizes equals (so a
    shape that holds it is a shape of logits)."""
    vocab = HEAD_VOCAB[request.param]
    if request.param == "per_head":
        module = MaskedLMModel(TextEncoder(
            vocab=vocab, width=16, depth=2, heads=2, mlp_dim=24,
            dtype=jnp.float32,
            attention_fn=make_attention_fn("dense", causal=True)))
        variables = module.init(jax.random.PRNGKey(1),
                                np.zeros((1, 8), np.int32))
        return module, variables, vocab
    from test_latent_moe_decoder import ref, small_cfg
    from mmlspark_tpu.dl.latent_moe_decoder import LatentMoEDecoder
    cfg = small_cfg(vocab_size=vocab)
    return (LatentMoEDecoder(cfg, dtype=jnp.float32),
            {"params": ref.make_weights(cfg, 5)}, vocab)


def _head_engine(decoder, reg, **kw):
    """Blocks of 4, and the widest window steered down to 8 (it is
    VMEM-bound on the chip) so that tiny prompts chunk."""
    module, variables, _ = decoder
    eng = LLMEngine(module, variables, block_len=4, max_seq_len=32,
                    num_blocks=24, hbm_fraction=1.0, service="llmhead",
                    registry=reg, **kw)
    eng.prefiller.max_window = 8
    return eng


def _last_row_argmax(decoder, prompt):
    """Argmax (pad masked) of the last prompt row of the ALL-ROWS
    logits: the whole prompt in one window through a fresh pool, the
    head over every row."""
    from mmlspark_tpu.dl.paged_kv import init_pools
    module, variables, _ = decoder
    n = len(prompt)
    blocks = -(-n // 4)
    (hidden,), _, _ = module.apply(
        variables,
        ((jnp.asarray(prompt)[None],
          jnp.arange(1, blocks + 1, dtype=jnp.int32)[None],
          jnp.zeros(1, jnp.int32), jnp.ones((1, n), bool)),),
        init_pools(module.cache_spec(), blocks + 1, 4), method="walk")
    logits = np.array(module.apply(variables, hidden, method="logits"))
    assert logits.shape[:2] == (1, n)
    logits[..., 0] = -np.inf
    return int(logits[0, n - 1].argmax())


def _prefill_calls(reg, service="llmhead"):
    """``(calls that emit, calls with no head)`` of the prefill programs."""
    calls = next(m for m in reg.metrics("gen_prefill_calls_total")
                 if m.name == "gen_prefill_calls_total")
    return (calls.value(service=service, head="row"),
            calls.value(service=service, head="none"))


#: case -> (prompt lengths of one prefill batch, program calls that emit,
#: program calls with no head); windows are 8 wide
HEAD_CASES = {
    "ends_mid_chunk": ((13,), 1, 1),
    "ends_on_a_chunks_last_row": ((16,), 1, 1),
    "three_chunks": ((21,), 1, 2),
    "batch_of_two_ending_in_different_chunks": ((5, 21), 2, 1),
}


@pytest.mark.parametrize("case", sorted(HEAD_CASES))
def test_first_token_is_the_argmax_of_the_last_prompt_row(decoder, case):
    sizes, n_row, n_none = HEAD_CASES[case]
    vocab = decoder[2]
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, vocab, size=n).astype(np.int32)
               for n in sizes]
    reg = MetricsRegistry()
    eng = _head_engine(decoder, reg, slots=2, prefill_batch=2)
    for i, p in enumerate(prompts):
        eng.submit(i, p, 2)
    got = eng.run_until_drained()
    for i, p in enumerate(prompts):
        assert int(got[i][len(p)]) == _last_row_argmax(decoder, p)
    assert _prefill_calls(reg) == (n_row, n_none)


def test_first_token_after_a_fully_reused_prefix(decoder):
    """The second time the whole prompt is in the prefix index and its
    last token alone is fed again (``s0 = prompt_len - 1``): one call of
    the 1-row program, which emits."""
    vocab = decoder[2]
    p = np.random.default_rng(29).integers(1, vocab, 16).astype(np.int32)
    reg = MetricsRegistry()
    eng = _head_engine(decoder, reg, slots=1, prefill_batch=1)
    want = _last_row_argmax(decoder, p)
    eng.submit("cold", p, 2)
    assert int(eng.run_until_drained()["cold"][16]) == want
    assert _prefill_calls(reg) == (1, 1)
    eng.submit("warm", p, 2)
    assert int(eng.run_until_drained()["warm"][16]) == want
    assert _prefill_calls(reg) == (2, 1)
    assert (False, 1, True) in eng.programs.built


def _made_shapes(jaxpr):
    """The shape of every value an equation makes, nested programs
    included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _made_shapes(sub)


def test_no_prefill_program_holds_a_window_of_logits(decoder):
    """The program that emits makes ``[P, V]`` logits and nothing with
    both the window and the vocabulary among its sizes; the program with
    no head makes and returns nothing vocabulary-sized at all."""
    module, variables, vocab = decoder
    eng = _head_engine(decoder, MetricsRegistry(), slots=1,
                       prefill_batch=2)
    w, P = 8, 2
    dec, win = eng.programs.blank(False, w)
    args = (variables["params"], None, eng.pools.target, None, dec, win)
    emits = eng.programs.get(False, w, True).trace(*args).jaxpr
    made = set(_made_shapes(emits.jaxpr))
    assert (P, vocab) in made
    assert not [s for s in made if vocab in s and w in s]
    # one row of logits a prompt, no more
    assert all(np.prod(s) <= P * vocab for s in made if vocab in s)
    quiet = eng.programs.get(False, w, False).trace(*args).jaxpr
    assert not [s for s in _made_shapes(quiet.jaxpr) if vocab in s]
    assert not [v for v in quiet.jaxpr.outvars
                if vocab in getattr(v.aval, "shape", ())]
    # and the decode step keeps its one row a slot
    S = eng.decoder.slots
    step = eng.programs.get(True, None).trace(
        variables["params"], None, eng.pools.target, None,
        *eng.programs.blank(True, None)).jaxpr
    made = set(_made_shapes(step.jaxpr))
    assert (S, vocab) in made
    assert all(np.prod(s) <= S * vocab for s in made if vocab in s)


# -- a prompt's prefill window rides with the decoding rows ------------------

def _serve(eng, waves):
    """Each wave of ``(seq_id, prompt, max_new)`` is submitted, then ONE
    boundary runs; after the last wave the engine drains. Returns
    ``seq_id -> tokens``."""
    out = {}
    for wave in waves:
        for seq_id, prompt, max_new in wave:
            eng.submit(seq_id, prompt, max_new)
        out.update(dict(eng.step()))
    out.update(eng.run_until_drained())
    return out


def _prefill_rows(reg, service):
    """``(rows that rode with the decoding rows, rows fed alone)``."""
    rows = next(m for m in reg.metrics("gen_prefill_rows_total")
                if m.name == "gen_prefill_rows_total")
    return (rows.value(service=service, ride="decode"),
            rows.value(service=service, ride="alone"))


def _one_window_lm(lm):
    """The same decoder saying, through the interface, that its walk
    takes one window a call."""
    module, variables = lm

    class OneWindow(MaskedLMModel):
        several_windows = False

    return OneWindow(module.encoder), variables


#: case -> (engine keywords, waves of (prompt length, new tokens) submitted a
#: boundary apart, prompt rows that ride with the decoding rows); windows
#: are at most 8 wide, blocks hold 4 tokens
RIDE_CASES = {
    # 21 rows ride in over three boundaries while the first decodes on and
    # the second finishes
    "several_chunks_ride_across_boundaries": (
        {}, [[(5, 12), (6, 3)], [(21, 4)]], 21),
    # admitted together a boundary after the first: one rides, the other
    # waits its turn behind it
    "two_wait_for_one_window": (
        {}, [[(5, 12)], [(13, 4), (9, 4)]], 22),
    # two prompts a window
    "two_prompts_a_window": (
        {"prefill_batch": 2}, [[(5, 12)], [(13, 4), (21, 4)]], 34),
    # finished by its prefill alone: no decode step of its own
    "one_new_token": ({}, [[(5, 12)], [(11, 1)], [(3, 2)]], 14),
    # the third cannot be allocated while the second is half in: it waits
    # for the blocks the first releases
    "pool_out_of_blocks_with_a_prompt_half_in": (
        {"num_blocks": 13, "hbm_fraction": 1.0},
        [[(5, 8)], [(21, 4), (9, 4)]], 30),
    # what keeps today's order: nothing to ride with, fewer decoding rows
    # than pay for the slot a riding prompt holds idle (the engine's own 8),
    # a speculative step, a decoder whose walk takes one window
    "no_runnable_slot": ({}, [[(5, 4), (21, 4), (9, 4)]], 0),
    "too_few_rows_decode": ({"ride_from": None}, [[(5, 12)], [(21, 4)]], 0),
    "speculative_step": ({"spec_k": 2}, [[(5, 12)], [(21, 4)]], 0),
    "decoder_of_one_window": ({}, [[(5, 12)], [(21, 4)]], 0),
}


@pytest.mark.parametrize("case", sorted(RIDE_CASES))
def test_riding_prefill_serves_generates_tokens(lm, draft_lm, case):
    kw, waves, rode = RIDE_CASES[case]
    kw = {"ride_from": 1, **kw}
    ride_from = kw.pop("ride_from")
    module, variables = _one_window_lm(lm) \
        if case == "decoder_of_one_window" else lm
    if kw.get("spec_k"):
        kw = dict(kw, draft_module=draft_lm[0], draft_variables=draft_lm[1])
    rng = np.random.default_rng(41)
    sizes = [size for wave in waves for size in wave]
    prompts = [rng.integers(2, VOCAB, size=n).astype(np.int32)
               for n, _ in sizes]
    reg = MetricsRegistry()
    svc = f"ride-{case}"
    eng = LLMEngine(module, variables, block_len=4, max_seq_len=32,
                    service=svc, registry=reg,
                    **{"slots": 3, "prefill_batch": 1, **kw})
    eng.prefiller.max_window = 8         # VMEM-bound on the chip
    if ride_from is not None:
        eng.prefiller.ride_from = ride_from
    ids = iter(range(len(sizes)))
    got = _serve(eng, [[(i, prompts[i], sizes[i][1])
                        for i in (next(ids) for _ in wave)]
                       for wave in waves])
    assert set(got) == set(range(len(sizes)))
    for i, (p, (_, max_new)) in enumerate(zip(prompts, sizes)):
        want = np.asarray(generate(
            module, variables, p[None, :], max_new_tokens=max_new,
            temperature=0.0)[0])
        np.testing.assert_array_equal(got[i], want[:len(p) + max_new])
    assert _prefill_rows(reg, svc) == (
        rode, sum(n for n, _ in sizes) - rode)
    assert eng.kv.stats()["sequences"] == 0


def test_a_prefix_hit_rides_its_suffix_alone(lm):
    """The second request's first 16 tokens are in the prefix index: its
    6-row suffix rides with the first request's decode rows, and the
    tokens are ``dl.generate``'s."""
    module, variables = lm
    rng = np.random.default_rng(43)
    doc = rng.integers(2, VOCAB, size=16).astype(np.int32)
    prompts = [np.concatenate([doc, rng.integers(2, VOCAB, size=n)
                               .astype(np.int32)]) for n in (3, 6)]
    reg = MetricsRegistry()
    eng = LLMEngine(module, variables, slots=2, block_len=4,
                    max_seq_len=40, prefill_batch=1, service="ridehit",
                    registry=reg)
    eng.prefiller.ride_from = 1
    got = _serve(eng, [[(0, prompts[0], 10)], [(1, prompts[1], 5)]])
    ref = _ref(lm, prompts, max_new=10)
    np.testing.assert_array_equal(got[0], ref[0][:len(prompts[0]) + 10])
    np.testing.assert_array_equal(got[1], ref[1][:len(prompts[1]) + 5])
    assert reg.snapshot()[
        'kv_prefix_tokens_reused_total{service="ridehit"}'] == 16.0
    assert _prefill_rows(reg, "ridehit") == (6, 19)


def _weight_products(jaxpr, shapes) -> int:
    """Matrix products of a program whose second operand has the shape of
    one of the decoder's matrices: how often the program reads them."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and \
                tuple(eqn.invars[1].aval.shape) in shapes:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _weight_products(sub, shapes)
    return n


def test_a_boundary_is_one_program_that_reads_each_weight_once(lm):
    """A boundary at which a prompt's window rides is ONE program call —
    no prefill program, no decode program — and that program multiplies
    by each of the decoder's matrices as often as the decode program
    does: the rows of both windows go through them together."""
    module, variables = lm
    reg = MetricsRegistry()
    svc = "rideonce"
    eng = LLMEngine(module, variables, slots=2, block_len=4,
                    max_seq_len=32, prefill_batch=1, service=svc,
                    registry=reg)
    eng.prefiller.max_window = 8         # VMEM-bound on the chip
    eng.prefiller.ride_from = 1
    matrices = {tuple(a.shape) for a in jax.tree.leaves(
        variables["params"]) if a.ndim == 2}
    args = (variables["params"], None, eng.pools.target, None)
    products = {
        key: _weight_products(
            eng.programs.get(*key).trace(
                *args, *eng.programs.blank(*key[:2])).jaxpr.jaxpr, matrices)
        for key in ((True, None, True), (True, 8, True))}
    assert products[True, 8, True] == products[True, None, True] > 0
    rng = np.random.default_rng(47)
    eng.submit("a", rng.integers(2, VOCAB, size=5).astype(np.int32), 12)
    eng.step()                           # alone: nothing decodes yet
    eng.submit("b", rng.integers(2, VOCAB, size=21).astype(np.int32), 2)
    called = {name: compile_tracker.calls(name)
              for name in eng.programs.aot_fingerprints()}
    steps = next(m for m in reg.metrics("gen_decode_steps_total")
                 if m.name == "gen_decode_steps_total")
    before = steps.value(service=svc), sum(_prefill_calls(reg, svc))
    for _ in range(3):                   # b rides in, 8 + 8 + 5 rows
        eng.step()
    now = {name: compile_tracker.calls(name) - n
           for name, n in called.items()}
    assert now == {**dict.fromkeys(called, 0),
                   f"llm_step_{svc}_S2_w8_b1": 3}
    assert steps.value(service=svc) - before[0] == 3
    assert sum(_prefill_calls(reg, svc)) - before[1] == 3
    assert _prefill_rows(reg, svc) == (21, 5)
    eng.run_until_drained()


# -- one boundary ahead: the next program before the last one's fetch ---------

@pytest.fixture
def beside(monkeypatch):
    """The engine as it stands where an accelerator runs beside the
    host: here the programs take the host's own cores, and the engine
    would keep every fetch at its own boundary."""
    from mmlspark_tpu.serving import llm
    monkeypatch.setattr(llm, "_device_beside_host", lambda: True)


@pytest.fixture(scope="module", params=["per_head", "state_a_sequence"])
def crowd(request, lm):
    """``(kind, module, variables, reference)`` of a decoder that ten
    slots decode of: ``MaskedLMModel``, whose streams are
    ``dl.generate``'s, and the block-sparse / lightning decoder, which
    keeps a state a SEQUENCE (restored from a snapshot after a prefix
    hit, copied into one at a cut) and whose streams are those of an
    engine of one slot that serves each request alone, cold, in today's
    order. ``reference(prompt, max_new) -> tokens``."""
    if request.param == "per_head":
        module, variables = lm

        def reference(prompt, max_new):
            return np.asarray(generate(
                module, variables, prompt[None, :], max_new_tokens=max_new,
                temperature=0.0)[0])[:len(prompt) + max_new]

        return request.param, module, variables, reference
    from test_sparse_linear_decoder import BL, ref, small_cfg
    from mmlspark_tpu.dl.sparse_linear_decoder import SparseLinearDecoder
    cfg = small_cfg()
    module = SparseLinearDecoder(cfg, dtype=jnp.float32, max_window=24)
    variables = {"params": ref.make_weights(cfg, 11)}
    served = {}

    def reference(prompt, max_new):
        key = (prompt.tobytes(), max_new)
        if key not in served:
            alone = LLMEngine(module, variables, slots=1, block_len=BL,
                              max_seq_len=128, num_blocks=10, state_slots=1,
                              prefill_batch=1, hbm_fraction=1.0,
                              service="ahead-alone",
                              registry=MetricsRegistry())
            alone.submit("r", prompt, max_new)
            served[key] = alone.run_until_drained()["r"]
        return served[key]

    return request.param, module, variables, reference


def _crowd_engine(crowd, reg, svc, **kw):
    """Ten slots and the engine's OWN rule for what rides and what runs
    ahead (eight rows decoding); blocks of 16, a window of 24 rows at
    most, so that a prompt of 60 rides in three windows."""
    kind, module, variables, _ = crowd
    kw = {"slots": 10, "block_len": 16, "max_seq_len": 128,
          "num_blocks": 64, "prefill_batch": 2, "hbm_fraction": 1.0, **kw}
    if kind == "state_a_sequence":
        kw.setdefault("state_slots", 16)
    eng = LLMEngine(module, variables, service=svc, registry=reg, **kw)
    eng.prefiller.max_window = 24
    assert eng.prefiller.ride_from == 8
    return eng


def _value(reg, name, **labels):
    return next(m for m in reg.metrics(name) if m.name == name).value(
        **labels)


#: case -> (engine keywords, waves of (prompt length or ("doc", extra
#: tokens after the 48-token document), new tokens) a boundary apart —
#: after a first wave of nine requests that decode throughout —, and what
#: the case is there to hold)
AHEAD_CASES = {
    # 60 rows ride in three windows of 24, 24 and 12 while nine rows decode
    "a_prompt_rides_in_several_windows": ({}, [[(60, 5)]], "rode"),
    # the document's three blocks are indexed; the question restores its
    # snapshot, brings a whole block of its own (a cut at 64, where its
    # snapshot is taken) and five rows more
    "a_prefix_hit_restores_and_cuts": (
        {}, [[(("doc", 21), 6)]], "reused"),
    # finished by the window its last row rode in: no decode row of its own
    "one_new_token": ({}, [[(19, 1)], [(7, 3)]], "rode"),
    # 24 blocks: the nine (17 tokens and up: two blocks each from the
    # start) hold 18; the first of 64 rows takes four and grows into a
    # fifth, the second waits for the blocks that finished sequences
    # release, then holds what those held
    "the_pool_runs_out_then_blocks_are_reused": (
        {"num_blocks": 25, "crowd_prompt": 17, "slots": 11},
        [[(64, 4), (64, 4)]],
        "stalled"),
}


def _serve_crowd(crowd, case, reg, svc, **over):
    kind, _, _, reference = crowd
    kw, waves, _ = AHEAD_CASES[case]
    kw = dict(kw)
    crowd_prompt = kw.pop("crowd_prompt", 3)
    rng = np.random.default_rng(53)
    doc = rng.integers(2, VOCAB, size=48).astype(np.int32)

    def prompt(size):
        if isinstance(size, tuple):
            return np.concatenate(
                [doc, rng.integers(2, VOCAB, size=size[1]).astype(np.int32)])
        return rng.integers(2, VOCAB, size=size).astype(np.int32)

    eng = _crowd_engine(crowd, reg, svc, **{**kw, **over})
    eng.submit("doc", doc, 1)
    served = dict(eng.run_until_drained())
    sent = {"doc": (doc, 1)}
    # nine that decode from the second boundary to the end of the case
    first = [(f"bg{i}", prompt(crowd_prompt + i % 4), 10 + i % 5)
             for i in range(9)]
    later = [[(f"w{j}-{i}", prompt(size), new)
              for i, (size, new) in enumerate(wave)]
             for j, wave in enumerate(waves)]
    stalled = 0
    for wave in [first] + later:
        for seq_id, p, new in wave:
            eng.submit(seq_id, p, new)
            sent[seq_id] = (p, new)
        served.update(dict(eng.step()))
        stalled += bool(eng._to_prefill)
    for _ in range(200):
        if not eng.sched.busy:
            break
        served.update(dict(eng.step()))
        stalled += bool(eng._to_prefill)
    assert not eng.sched.busy and eng.decoder.flying is None
    assert set(served) == set(sent)
    for seq_id, (p, new) in sent.items():
        np.testing.assert_array_equal(served[seq_id], reference(p, new),
                                      err_msg=str(seq_id))
    assert eng.kv.stats()["sequences"] == 0
    return eng, stalled


@pytest.mark.parametrize("case", sorted(AHEAD_CASES))
def test_one_boundary_ahead_serves_the_references_tokens(crowd, beside,
                                                         case):
    """Ten slots, nine of them decoding throughout, the engine's own rule
    (eight rows) for what rides and what runs ahead: every stream is the
    reference's, token for token, over a prompt that rides in several
    windows, a prefix hit (with its state's restore and a snapshot at the
    cut), a request of one new token, and a pool that runs out and hands
    a finished sequence's blocks to the next admission."""
    kind = crowd[0]
    reg = MetricsRegistry()
    svc = f"ahead-{kind}-{case}"
    eng, stalled = _serve_crowd(crowd, case, reg, svc)
    steps = _value(reg, "gen_decode_steps_total", service=svc)
    ahead = _value(reg, "gen_steps_ahead_total", service=svc)
    # every boundary at which eight or more decode, but the first, ran
    # ahead of the fetch before it
    assert 6 <= ahead < steps
    rode = _value(reg, "gen_prefill_rows_total", service=svc, ride="decode")
    reused = _value(reg, "kv_prefix_tokens_reused_total", service=svc)
    holds = AHEAD_CASES[case][2]
    if holds == "rode":
        assert rode == sum(size for wave in AHEAD_CASES[case][1]
                           for size, _ in wave)
    elif holds == "reused":
        assert reused == 48 and rode == 21
        if kind == "state_a_sequence":
            assert _value(reg, "kv_state_restores_total", service=svc) == 1
            # the document's, and the question's at its cut
            assert _value(reg, "kv_state_snapshots", service=svc) >= 2
    else:
        assert stalled > 0
    if kind == "state_a_sequence":
        assert _value(reg, "kv_state_slots_used", service=svc) == 0


def test_the_next_program_is_dispatched_before_the_last_ones_fetch(
        crowd, beside, monkeypatch):
    """The order of a boundary, seen from the two calls that touch the
    device: program ``k + 1`` goes out (``_Programs.call``) BEFORE the
    tokens of ``k`` are fetched (``jax.device_get`` of what ``k`` made),
    tokens and first tokens are counted when they are home, and
    ``run_until_drained`` leaves nothing in flight."""
    from mmlspark_tpu.serving import llm
    kind = crowd[0]
    events = []
    made_by = {}
    real_call, real_get = llm._Programs.call, jax.device_get

    def call(self, dec=(), win=(), head=True):
        out = real_call(self, dec, win, head)
        if dec:
            made_by[id(out.made["tok"])] = len(made_by)
            events.append(("dispatch", made_by[id(out.made["tok"])]))
            call.keep.append(out.made["tok"])     # ids stay distinct
        return out

    call.keep = []

    def device_get(tree):
        if isinstance(tree, dict) and id(tree.get("tok")) in made_by:
            events.append(("fetch", made_by[id(tree["tok"])]))
        return real_get(tree)

    monkeypatch.setattr(llm._Programs, "call", call)
    monkeypatch.setattr(jax, "device_get", device_get)
    reg = MetricsRegistry()
    svc = f"order-{kind}"
    eng, _ = _serve_crowd(crowd, "a_prompt_rides_in_several_windows", reg,
                          svc)
    assert eng.decoder.flying is None and not eng.decoder.src.max() >= 0
    order = {e: i for i, e in enumerate(events)}
    n = len(made_by)
    assert all(("fetch", k) in order for k in range(n))     # all came home
    ahead = [k for k in range(1, n)
             if order["dispatch", k] < order["fetch", k - 1]]
    assert len(ahead) == _value(reg, "gen_steps_ahead_total", service=svc)
    assert len(ahead) >= 6
    # a fetch is never of a program after one still out, and at most one
    # program is out beyond the one being fetched
    for k in range(n):
        assert order["dispatch", k] < order["fetch", k]
        if k + 2 < n:
            assert order["fetch", k] < order["dispatch", k + 2]
    spans = [s for s in llm._tracer.recent() if s.name == "llm.fetch"]
    assert spans
    roots = [s for s in llm._tracer.recent() if s.name == "llm.step"
             and s.attrs.get("ahead")]
    assert roots


def test_tokens_count_when_they_are_home_not_at_dispatch(crowd, beside):
    """Between two boundaries that run ahead the engine's counters hold
    what has been FETCHED: the program in flight has moved ``ptr`` (the
    driver's record of rows reads it) and no token of it is counted."""
    kind, _, _, reference = crowd
    reg = MetricsRegistry()
    svc = f"home-{kind}"
    eng = _crowd_engine(crowd, reg, svc)
    rng = np.random.default_rng(59)
    prompts = [rng.integers(2, VOCAB, size=4).astype(np.int32)
               for _ in range(9)]
    for i, p in enumerate(prompts):
        eng.submit(i, p, 12)
    eng.step()                  # nothing decoded yet: prefill alone, then
    assert eng.decoder.flying is None       # a step that waits for itself
    assert _value(reg, "gen_tokens_total", service=svc) == 9
    eng.step()                  # nine decode: this program stays in flight
    assert eng.decoder.flying is not None
    assert _value(reg, "gen_tokens_total", service=svc) == 9
    assert _value(reg, "gen_steps_ahead_total", service=svc) == 0
    assert (eng.decoder.ptr[:9] == 4 + 3).all()        # moved at dispatch
    assert (eng.decoder.src[:9] == np.arange(9)).all()  # tokens not home
    eng.step()                  # the next goes out, then the first's fetch
    assert _value(reg, "gen_tokens_total", service=svc) == 18
    assert _value(reg, "gen_steps_ahead_total", service=svc) == 1
    assert (eng.decoder.ptr[:9] == 4 + 4).all()
    got = eng.run_until_drained()
    assert eng.decoder.flying is None
    assert _value(reg, "gen_tokens_total", service=svc) == 9 * 11
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(got[i], reference(p, 12))


def _count(reg, name, **labels):
    return next(m for m in reg.metrics(name) if m.name == name).count(
        **labels)


def test_a_boundary_says_what_it_ran_and_a_fetch_what_it_brings(crowd,
                                                                beside):
    """The spans of a boundary name its program as XLA does: ``llm.step``
    the one it dispatched (with ``gen_decode_steps_total`` as the
    boundary left it), ``llm.fetch`` the one it brought home, which one boundary ahead is
    the one dispatched a boundary BEFORE; ``gen_decode_attn_seconds`` is
    observed at the fetch, once a program."""
    from mmlspark_tpu.serving import llm
    kind, module, variables, reference = crowd
    reg = MetricsRegistry()
    svc = f"names-{kind}"
    from mmlspark_tpu.obs.tracing import now_ns
    served_by = []              # when the serving ended

    def compare(prompt, max_new):
        # the reference may serve with engines of its own: their spans
        # come after
        served_by.append(now_ns())
        return reference(prompt, max_new)

    start = now_ns()
    _serve_crowd((kind, module, variables, compare),
                 "a_prompt_rides_in_several_windows", reg, svc)
    spans = [s for s in llm._tracer.recent(since=start)
             if s.end_ns <= served_by[0]]
    roots = [s for s in spans if s.name == "llm.step"]
    decodes = {s.span_id: s.parent_id for s in spans
               if s.name == "llm.decode"}
    fetched = {}                        # root -> the programs it brought home
    for s in spans:
        if s.name == "llm.fetch":
            root = decodes.get(s.parent_id, s.parent_id)
            fetched.setdefault(root, []).append(s)
    safe = svc.replace("-", "_")
    steps = 0
    sent = []                           # (boundary, program) in dispatch order
    for k, root in enumerate(roots):
        if "program" in root.attrs:
            steps += 1
            sent.append((k, root.attrs["program"]))
            assert root.attrs["program"].startswith(
                (f"llm_decode_paged_{safe}_S10", f"llm_step_{safe}_S10_w"))
            # a window rode in it where the boundary says its rows
            rode = root.attrs.get("ride_rows", 0) > 0
            assert rode == root.attrs["program"].startswith("llm_step_")
        assert root.attrs["steps"] == steps
    assert steps == _value(reg, "gen_decode_steps_total", service=svc)
    assert {p.split("_")[1] for _, p in sent} == {"decode", "step"}
    # every program dispatched is fetched once, in the order of dispatch:
    # at its own boundary, or ahead at the boundary after
    home = [(k, f.attrs["program"]) for k, root in enumerate(roots)
            for f in fetched.get(root.span_id, [])]
    assert [p for _, p in home] == [p for _, p in sent]
    assert all(at in (k, k + 1) for (at, _), (k, _) in zip(home, sent))
    # a boundary that ran ahead fetched the program of the boundary before
    ahead = [k for k, root in enumerate(roots) if root.attrs.get("ahead")]
    assert len(ahead) == _value(reg, "gen_steps_ahead_total",
                                service=svc) >= 6
    for k in ahead:
        assert [f.attrs["program"] for f in fetched[roots[k].span_id]] \
            == [roots[k - 1].attrs["program"]]
    # once a program, at its fetch: the steps, and the prefills alone
    assert _count(reg, "gen_decode_attn_seconds", service=svc,
                  phase="decode") == steps
    alone = sum(_value(reg, "gen_prefill_calls_total", service=svc, head=h)
                for h in ("row", "none")) - sum(
        p.startswith("llm_step_") for _, p in sent)
    assert _count(reg, "gen_decode_attn_seconds", service=svc,
                  phase="prefill") == alone > 0


def test_a_programs_label_is_its_lowered_modules_name(crowd):
    """``_Programs.name`` reaches XLA: the decode step, a step with a
    window riding in it, a prefill alone and the state-row copy are
    ``jit_<label>`` in the lowered module (a service's ``-`` as ``_``)."""
    kind = crowd[0]
    eng = _crowd_engine(crowd, MetricsRegistry(), f"named-{kind}")
    progs = eng.programs
    safe = f"named_{kind}"
    for key, want in (((True, None, True), f"llm_decode_paged_{safe}_S10_k0"),
                      ((True, 32, True), f"llm_step_{safe}_S10_w32_b2"),
                      ((False, 8, False), f"llm_prefill_{safe}_w8_b2_nohead")):
        prog = progs.get(*key)
        assert prog.__name__ == want
        assert prog.__tracked_label__ == progs.name(*key)
        text = prog.lower(*progs._args(*progs.blank(*key[:2]))).as_text()
        assert f"module @jit_{want} " in text
    if kind == "state_a_sequence":
        progs.copy_rows([])
        rows = jnp.zeros(progs.batch, jnp.int32)
        text = progs._copy.lower(eng.pools.target, rows, rows).as_text()
        assert f"module @jit_llm_state_copy_{safe}_b2 " in text


def test_running_ahead_compiles_no_program_of_its_own(lm, beside):
    """The program that reads its tokens from the picks of the one before
    it IS the step's program: a warmed engine serves a crowd one boundary
    ahead, the picks now a host-made array and now a program's output,
    with no compile, and ``warm`` built no program more than it did."""
    module, variables = lm
    reg = MetricsRegistry()
    eng = LLMEngine(module, variables, slots=10, block_len=4,
                    max_seq_len=32, prefill_batch=2, service="aheadwarm",
                    registry=reg)
    eng.prefiller.max_window = 8
    fps = eng.warm(prefill_windows=(3, 21), mark_steady=True)
    try:
        prompts = _prompts(seed=71, sizes=(3, 5, 2, 6, 4, 3, 5, 2, 6))
        for i, p in enumerate(prompts):
            eng.submit(i, p, 10)
        eng.step()
        eng.step()
        late = _prompts(seed=73, sizes=(21,))[0]
        eng.submit("late", late, 4)
        got = eng.run_until_drained()
        compile_tracker.assert_steady_state()
    finally:
        compile_tracker.unmark_steady()
    assert _value(reg, "gen_steps_ahead_total", service="aheadwarm") >= 6
    assert set(fps) == {
        "llm_decode_paged_aheadwarm_S10_k0", "llm_prefill_aheadwarm_w4_b2",
        "llm_prefill_aheadwarm_w8_b2", "llm_prefill_aheadwarm_w8_b2_nohead",
        "llm_step_aheadwarm_S10_w8_b2"}
    ref = _ref(lm, prompts + [late], max_new=10)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(got[i], ref[i][:len(p) + 10])
    np.testing.assert_array_equal(got["late"], ref[9][:len(late) + 4])


@pytest.mark.parametrize("regime", ["speculative", "under_ride_from",
                                    "decoder_of_one_window",
                                    "no_device_beside_the_host"])
def test_what_keeps_the_synchronous_order(lm, draft_lm, monkeypatch,
                                          regime):
    """A speculative step (what it commits is known from its fetch),
    fewer rows decoding than a window rides with, a decoder whose walk
    takes one window, and a backend whose programs take the host's own
    cores (this one, left as it is): every program is fetched at the
    boundary that dispatched it, and the counter stays 0."""
    if regime != "no_device_beside_the_host":
        from mmlspark_tpu.serving import llm
        monkeypatch.setattr(llm, "_device_beside_host", lambda: True)
    module, variables = _one_window_lm(lm) \
        if regime == "decoder_of_one_window" else lm
    kw = {"slots": 10}
    if regime == "speculative":
        kw.update(spec_k=2, draft_module=draft_lm[0],
                  draft_variables=draft_lm[1])
    elif regime == "under_ride_from":
        kw.update(slots=7)
    reg = MetricsRegistry()
    svc = f"sync-{regime}"
    eng = LLMEngine(module, variables, block_len=4, max_seq_len=32,
                    prefill_batch=2, service=svc, registry=reg, **kw)
    prompts = _prompts(seed=61, sizes=(3, 5, 2, 6, 4, 3, 5, 2, 6, 4))
    for i, p in enumerate(prompts):
        eng.submit(i, p, 8)
    got = {}
    boundaries = 0
    for _ in range(100):
        boundaries += eng.sched.busy
        got.update(dict(eng.step()))
        assert eng.decoder.flying is None
    assert not eng.sched.busy
    # the scheduler counts ONE step a boundary at which it holds a slot
    assert _value(reg, "sched_continuous_steps_total",
                  service=svc) == boundaries
    ref = _ref(lm, prompts, max_new=8)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(got[i], ref[i][:len(p) + 8])
    assert _value(reg, "gen_decode_steps_total", service=svc) > 0
    assert _value(reg, "gen_steps_ahead_total", service=svc) == 0


def test_a_landed_prompt_no_slot_takes_goes_through_the_queue_by_value(
        lm, beside):
    """A prompt that ended in the window of a program in flight goes to
    its slot by its first token's ROW, beside the queue. If no slot takes
    it at once it waits on the host, the fetch brings the value home, and
    it goes through the queue like any payload: the wire never carries a
    row of this process's device."""
    module, variables = lm
    reg = MetricsRegistry()
    eng = LLMEngine(module, variables, slots=10, block_len=4,
                    max_seq_len=32, prefill_batch=1, service="late",
                    registry=reg)
    prompts = _prompts(seed=67, sizes=(3, 5, 2, 6, 4, 3, 5, 2, 6, 7))
    for i, p in enumerate(prompts[:9]):
        eng.submit(i, p, 12)
    eng.step()
    eng.step()                           # nine decode, one program out
    eng.submit(9, prompts[9], 6)
    eng.decoder.active[9] = True         # no slot is free at this boundary
    eng.step()                           # its 7 rows ride and end here
    assert eng._landing == {9: 10} and not len(eng.handoff)
    eng.step()                           # the fetch brings its token home
    assert not eng._landing
    (payload,) = eng.handoff._q
    assert isinstance(payload["first"], int)
    assert set(payload) == {"seq", "first", "max_new_tokens"}
    eng.decoder.active[9] = False
    got = eng.run_until_drained()
    ref = _ref(lm, prompts, max_new=12)
    np.testing.assert_array_equal(got[9], ref[9][:len(prompts[9]) + 6])
    for i in range(9):
        np.testing.assert_array_equal(got[i], ref[i][:len(prompts[i]) + 12])


@pytest.mark.parametrize("then", ["rows_finish_under_ride_from",
                                  "warm_mid_serve"])
def test_a_landed_prompts_token_comes_home_when_the_order_turns(
        crowd, beside, then):
    """A prompt lands in a window that rides while the engine runs ahead:
    its slot has its first token by ROW alone. The next boundary waits
    for its own program — three of the nine rows reached their end in the
    very program the prompt rode in, so fewer than ``ride_from`` decode;
    or ``warm`` is called there — and first brings the program in flight
    home: the landed slot's token with it, though the slot decoded
    nothing in that program. Every stream is the reference's."""
    kind, _, _, reference = crowd
    reg = MetricsRegistry()
    svc = f"turn-{kind}-{then}"
    eng = _crowd_engine(crowd, reg, svc)
    rng = np.random.default_rng(84)
    # a first token, then one token a program: 5 new tokens end in the
    # fourth decode program, the one the late prompt rides in
    short = 5 if then == "rows_finish_under_ride_from" else 14
    sent = {i: (rng.integers(2, VOCAB, size=3 + i % 4).astype(np.int32),
                short if i < 3 else 14) for i in range(9)}
    for i, (p, new) in sent.items():
        eng.submit(i, p, new)
    served = {}
    for _ in range(3):
        served.update(dict(eng.step()))
    sent["late"] = (rng.integers(2, VOCAB, size=9).astype(np.int32), 6)
    eng.submit("late", *sent["late"])
    served.update(dict(eng.step()))     # nine decode, its nine rows ride
    slot = eng._meta["late"].slot
    assert eng.decoder.flying is not None
    assert eng.decoder.src[slot] == 10 and eng._meta["late"].handed
    if then == "warm_mid_serve":
        eng.warm(prefill_windows=(9,), mark_steady=False)
        assert eng.decoder.last[slot] == reference(*sent["late"])[9]
    else:
        assert eng.decoder.runnable.sum() == 7      # under ride_from
        served.update(dict(eng.step()))             # its second token
        assert eng.decoder.last[slot] == reference(*sent["late"])[10]
    assert eng.decoder.flying is None and (eng.decoder.src < 0).all()
    assert eng._meta["late"].first_token == reference(*sent["late"])[9]
    served.update(eng.run_until_drained())
    assert set(served) == set(sent)
    for seq_id, (p, new) in sent.items():
        np.testing.assert_array_equal(served[seq_id], reference(p, new),
                                      err_msg=str(seq_id))


class TestPoolSizing:
    def test_block_priced_in_the_kernels_tiled_layout(self):
        from mmlspark_tpu.dl.paged_kv import pool_block_bytes

        def price(heads, hd, dtype, depth=8, block_len=16):
            return pool_block_bytes(MaskedLMModel(TextEncoder(
                vocab=8, width=heads * hd, depth=depth, heads=heads,
                mlp_dim=8, dtype=dtype)).cache_spec(), block_len)
        # 2*depth arrays at rest + 4 in flight around the kernel, each
        # [block_len, heads * head_dim] with the heads side by side
        assert price(8, 128, jnp.bfloat16) == 20 * 16 * 8 * 128 * 2
        # stated lane-dense, 8 heads of 64 fill whole tiles: no padding
        assert price(8, 64, jnp.bfloat16) == 20 * 16 * 8 * 64 * 2
        # 2 heads of 16 in f32 are 32 of 128 lanes: 4x logical
        assert price(2, 16, jnp.float32, depth=1, block_len=4) == \
            6 * 4 * (4 * 2 * 16 * 4)
        # the row dim rounds to the dtype's sublane tile once past it
        assert price(8, 128, jnp.bfloat16, block_len=20) == \
            price(8, 128, jnp.bfloat16, block_len=32)

    def test_target_and_draft_share_one_fraction(self, lm, monkeypatch):
        import mmlspark_tpu.obs.memory as memory
        from mmlspark_tpu.dl.paged_kv import pool_block_bytes
        module, variables = lm
        free = 1 << 22
        monkeypatch.setattr(
            memory, "device_memory_stats",
            lambda: [{"bytes_limit": free + 1000, "bytes_in_use": 1000}])
        kw = dict(slots=1, block_len=4, max_seq_len=16,
                  registry=MetricsRegistry())
        one = pool_block_bytes(module.cache_spec(), 4)
        plain = LLMEngine(module, variables, **kw)
        spec = LLMEngine(module, variables, draft_module=module,
                         draft_variables=variables, spec_k=1, **kw)
        assert plain.kv.num_blocks == free // 2 // one
        # both models' pools together stay inside the same half
        assert spec.kv.num_blocks == free // 2 // (2 * one)


class TestPrefixReuseAndTTFT:
    def test_repeated_prefix_hits_and_ttft_split(self, lm):
        module, variables = lm
        reg = MetricsRegistry()
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=24, service="llmttft", registry=reg)
        p = _prompts(seed=11, sizes=(16,))[0]
        ref = _ref(lm, [p])
        eng.submit("cold", p, MAXNEW)
        got1 = eng.run_until_drained()
        eng.submit("warm", p, MAXNEW)
        got2 = eng.run_until_drained()
        # identical output either way — reuse must be invisible to the
        # tokens (acceptance: ≥1 prefix hit + identical greedy output)
        np.testing.assert_array_equal(got1["cold"],
                                      ref[0][:len(p) + MAXNEW])
        np.testing.assert_array_equal(got2["warm"],
                                      ref[0][:len(p) + MAXNEW])
        snap = reg.snapshot()
        assert snap['kv_prefix_hits_total{service="llmttft"}'] >= 1.0
        assert snap[
            'kv_prefix_tokens_reused_total{service="llmttft"}'] >= 4.0
        # TTFT lands in the right reuse label
        h = reg.metrics("gen_ttft_seconds")[0]
        assert h.count(service="llmttft", reuse="cold") == 1
        assert h.count(service="llmttft", reuse="warm") == 1

    def test_expired_deadline_is_shed_not_served(self, lm):
        module, variables = lm
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=16, registry=MetricsRegistry())
        p = _prompts(sizes=(3,))[0]
        eng.submit("dead", p, 2, deadline=-1.0)     # already expired
        eng.submit("live", p, 2)
        got = eng.run_until_drained()
        assert "dead" not in got and "live" in got
        assert eng.expired == ["dead"]

    def test_pool_too_small_raises_instead_of_spinning(self, lm):
        module, variables = lm
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=16, num_blocks=2,
                        registry=MetricsRegistry())
        from mmlspark_tpu.dl.paged_kv import OutOfBlocks
        eng.submit(0, _prompts(sizes=(9,))[0], MAXNEW)  # needs 3 blocks
        with pytest.raises(OutOfBlocks):
            eng.run_until_drained()


class TestSteadyState:
    def test_warmed_worker_serves_with_zero_compiles(self, lm):
        module, variables = lm
        eng = LLMEngine(module, variables, slots=2, block_len=4,
                        max_seq_len=16, service="llmsteady",
                        registry=MetricsRegistry())
        prompts = _prompts(seed=13, sizes=(3, 6, 5))
        windows = sorted({1, 4, 8})
        fps = eng.warm(prefill_windows=tuple(windows), mark_steady=True)
        try:
            for i, p in enumerate(prompts):
                eng.submit(i, p, MAXNEW)
            got = eng.run_until_drained()
            compile_tracker.assert_steady_state()
        finally:
            compile_tracker.unmark_steady()
        assert len(got) == 3
        # one decode program + one prefill program per window bucket +
        # one riding window (the ladder starts at 32 rows), each with an
        # AOT fingerprint pair
        assert set(fps) == {"llm_decode_paged_llmsteady_S2_k0",
                            "llm_prefill_llmsteady_w1_b2",
                            "llm_prefill_llmsteady_w4_b2",
                            "llm_prefill_llmsteady_w8_b2",
                            "llm_step_llmsteady_S2_w32_b2"}
        for static_fp, full_fp in fps.values():
            assert static_fp and full_fp

    def test_warmed_worker_serves_chunked_prompts_with_zero_compiles(
            self, lm):
        """A prompt of three chunks: ``warm`` built both kinds of the
        widest window's program, nothing compiles afterwards, and the
        calls split as the host knew before each call."""
        module, variables = lm
        reg = MetricsRegistry()
        eng = LLMEngine(module, variables, slots=1, block_len=4,
                        max_seq_len=32, prefill_batch=1,
                        service="llmsteady3", registry=reg)
        eng.prefiller.max_window = 8     # VMEM-bound on the chip
        # 21 -> none, none, row; 5 -> row; 16 -> none, row
        prompts = _prompts(seed=31, sizes=(21, 5, 16))
        ref = _ref(lm, prompts)
        fps = eng.warm(prefill_windows=(21, 5, 16), mark_steady=True)
        try:
            assert {(False, 8, True), (False, 8, False)} \
                <= set(eng.programs.built)
            for i, p in enumerate(prompts):
                eng.submit(i, p, MAXNEW)
            got = eng.run_until_drained()
            compile_tracker.assert_steady_state()
        finally:
            compile_tracker.unmark_steady()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(got[i],
                                          ref[i][:len(p) + MAXNEW])
        assert set(fps) == {"llm_decode_paged_llmsteady3_S1_k0",
                            "llm_prefill_llmsteady3_w8_b1",
                            "llm_prefill_llmsteady3_w8_b1_nohead"}
        assert fps["llm_prefill_llmsteady3_w8_b1"] != \
            fps["llm_prefill_llmsteady3_w8_b1_nohead"]
        assert _prefill_calls(reg, "llmsteady3") == (3, 3)


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "spec"])
def test_program_names_and_fingerprint_keys(lm, draft_lm, monkeypatch,
                                            spec_k):
    """What the persistent compile cache and ``serving/deploy.py``'s
    static fingerprints key on: the programs' names and the fields of
    the keys behind ``aot_fingerprints()``. A suffix of several chunks
    gets the widest window's program with no head."""
    from mmlspark_tpu.core import aot
    key_of = {}                          # fingerprint pair -> its key
    real = aot.fingerprints

    def spy(key, donated, dropped):
        fp = real(key, donated, dropped)
        key_of[fp] = key
        return fp

    monkeypatch.setattr(aot, "fingerprints", spy)
    module, variables = lm
    svc = f"llmnames{spec_k}"
    draft = dict(draft_module=draft_lm[0], draft_variables=draft_lm[1],
                 spec_k=spec_k) if spec_k else {}
    eng = LLMEngine(module, variables, slots=2, block_len=4,
                    max_seq_len=32, prefill_batch=1, service=svc,
                    registry=MetricsRegistry(), **draft)
    eng.prefiller.max_window = 8         # VMEM-bound on the chip
    fps = eng.warm(prefill_windows=(3, 21), mark_steady=False)
    assert eng.prefiller.windows_for(21) == [8, 8, 8]
    decode = f"llm_decode_paged_{svc}_S2_k{spec_k}"
    # a window rides with the plain decode step alone: one program a
    # riding width (here every chunk rides 8 wide), with the head
    ride = set() if spec_k else {f"llm_step_{svc}_S2_w8_b1"}
    assert set(fps) == {decode, f"llm_prefill_{svc}_w4_b1",
                        f"llm_prefill_{svc}_w8_b1",
                        f"llm_prefill_{svc}_w8_b1_nohead"} | ride
    by_name = {name: key_of[fp] for name, fp in fps.items()}
    assert all(key["attn"] == "paged" for key in by_name.values())
    for name in ride:
        key = by_name[name]
        assert (key["phase"], key["window"], key["batch"], key["slots"],
                key["spec_k"]) == ("step", 8, 1, 2, 0)
    assert by_name[decode]["phase"] == "decode"
    assert by_name[decode]["spec_k"] == spec_k
    assert by_name[decode]["slots"] == 2
    for w, head in ((4, True), (8, True), (8, False)):
        key = by_name[f"llm_prefill_{svc}_w{w}_b1"
                      + ("" if head else "_nohead")]
        assert (key["phase"], key["window"], key["head"],
                key["batch"]) == ("prefill", w, head, 1)


def test_dense_gather_counter_stays_registered_for_the_benchmark(lm):
    """``benchmark/drivers`` look ``kv_dense_gather_bytes_total`` up at
    set-up the way this test does: the engine registers it, once, and
    nothing increments it."""
    module, variables = lm
    reg = MetricsRegistry()
    name = "kv_dense_gather_bytes_total"
    assert not reg.metrics(name)
    eng = LLMEngine(module, variables, slots=2, block_len=4,
                    max_seq_len=16, service="llmodo", registry=reg)
    assert len(reg.metrics(name)) == 1
    counter = next(m for m in reg.metrics(name) if m.name == name)
    for i, p in enumerate(_prompts(seed=3, sizes=(3, 6))):
        eng.submit(i, p, MAXNEW)
    assert len(eng.run_until_drained()) == 2
    for phase in ("prefill", "decode"):
        assert counter.value(service="llmodo", phase=phase) == 0
    assert not [k for k in reg.snapshot() if k.startswith(name)]


class TestScenarioAndLoadgen:
    def test_llm_serving_scenario_smoke(self):
        from mmlspark_tpu.testing.benchmarks import llm_serving_scenario
        out = llm_serving_scenario(service="llmscen", slots=2,
                                   n_prompts=3, prompt_len=8,
                                   max_new_tokens=3,
                                   registry=MetricsRegistry())
        assert out["sequences"] == 9                # 3 prompts × 3 rounds
        assert out["prefix_hits"] >= 1
        assert out["prefix_hit_rate"] > 0
        assert out["tokens_per_s"] > 0
        assert out["steady_state_ok"]
        assert out["ttft_cold_p50_ms"] > 0
        assert out["ttft_warm_p50_ms"] > 0
        # warm round prefills a 1-token suffix instead of the whole
        # prompt — the TTFT improvement the cache exists to buy
        assert out["ttft_warm_p50_ms"] <= out["ttft_cold_p50_ms"]

    def test_llm_decode_scenario_smoke(self):
        from mmlspark_tpu.testing.benchmarks import llm_decode_scenario
        out = llm_decode_scenario(service="llmdecscen",
                                  context_tokens=256, block_len=16,
                                  max_new_tokens=8,
                                  registry=MetricsRegistry())
        assert out["context_blocks"] == 16
        assert out["tokens_per_s"] > 0
        assert out["decode_tokens"] > 0
        assert out["steady_state_ok"]

    def test_summarize_ttft_columns(self):
        from mmlspark_tpu.serving.loadgen import summarize
        lat = np.full((2, 30), 10.0)
        st = np.full((2, 30), 200, np.int32)
        tt = np.full((2, 30), 3.0)
        lat[0, 25] = tt[0, 25] = -1.0
        st[0, 25] = -1
        s = summarize(lat, st, 1.0, warmup=5,
                      tenants=["gold", "be"], ttft=tt)
        assert s["ttft_p50_ms"] == pytest.approx(3.0)
        assert s["ttft_p99_ms"] == pytest.approx(3.0)
        assert s["ttft_p50_ms"] <= s["p50_ms"]
        for tname in ("gold", "be"):
            assert "ttft_p99_ms" in s["tenants"][tname]
        # without a ttft matrix the columns stay absent (lg_run5 path)
        s2 = summarize(lat, st, 1.0, warmup=5)
        assert "ttft_p50_ms" not in s2
