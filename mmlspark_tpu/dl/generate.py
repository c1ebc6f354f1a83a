"""Autoregressive generation for causal-LM models.

Rounds out the text stack (BPE → causal pretraining → generation); the
reference has no language-model surface at all (SURVEY §5 marks text as
the framework's extension axis).

TPU shape discipline: the ids buffer is a FIXED [B, max_len] array and
the whole decode is one ``lax.scan`` under one ``jit``. The default
path keeps per-block KV caches — prefill and decode unify into one
scan where each step embeds one token and attends over the cache
(O(L²·W) total). ``use_cache=False`` re-encodes the buffer every step
through the encoder's own attention_fn (O(steps·L²·W)) — the reference
the cached path is equivalence-tested against.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..core.contracts import HasInputCol, HasOutputCol
from ..core.param import (ComplexParam, Param, StageParam,
                          TypeConverters as TC)
from ..core.pipeline import Transformer


def _sample(logits, key, temperature: float, pad_id: int):
    """Shared sampling epilogue — ONE copy so the cached and re-encode
    paths cannot drift. Never emits pad (it would terminate the row's
    mask early)."""
    logits = logits.at[:, pad_id].set(-jnp.inf)
    if temperature > 0:
        nxt = jax.random.categorical(key, logits / temperature, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32)


def _make_run(module, max_new_tokens: int, temperature: float,
              pad_id: int):
    """One jitted decode program per (module, decode config) — weights
    and buffers are traced arguments, so repeated generate() calls with
    the same shapes hit the compile cache instead of retracing."""

    @jax.jit
    def run(params, buf, ptr, key):
        B = buf.shape[0]

        def step(carry, i):
            buf, ptr = carry
            logits = module.apply({"params": params}, buf)["logits"]
            # logits at the LAST WRITTEN position predict the next token
            last = jnp.take_along_axis(
                logits, (ptr - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]                           # [B, V]
            # per-step key by fold_in (not a split chain): deterministic
            # given (seed, step index) alone
            nxt = _sample(last, jax.random.fold_in(key, i), temperature,
                          pad_id)
            buf = buf.at[jnp.arange(B), ptr].set(nxt)
            return (buf, ptr + 1), None

        (buf, ptr), _ = jax.lax.scan(
            step, (buf, ptr), jnp.arange(max_new_tokens))
        return buf

    return run


def _make_cached_run(module, max_new_tokens: int, temperature: float,
                     pad_id: int, scan_len: int, prefill_len: int):
    """KV-cached decode: batched prefill + ONE scan over the writable
    positions. The first ``prefill_len`` positions (statically
    ``min(prompt_len) - 1`` — guaranteed real tokens in every row) seed
    the per-block KV caches in one causal forward whose projections are
    large MXU matmuls; the scan then starts at the first position whose
    write can matter, each step embedding one token and attending over
    the caches (O(L·W) per step instead of a full O(L²·W) re-encode)."""

    @jax.jit
    def run(params, buf, ptr, key):
        B, L = buf.shape
        enc = module.encoder
        hd = enc.width // enc.heads
        caches = tuple(
            (jnp.zeros((B, enc.heads, L, hd), enc.dtype),
             jnp.zeros((B, enc.heads, L, hd), enc.dtype))
            for _ in range(enc.depth))
        if prefill_len > 0:
            caches = module.apply(
                {"params": params}, buf[:, :prefill_len], caches,
                method="prefill")

        def step(carry, pos):
            buf, caches = carry
            tok = jax.lax.dynamic_slice_in_dim(buf, pos, 1,
                                               axis=1)[:, 0]
            logits, caches = module.apply(
                {"params": params}, tok, caches, pos,
                method="decode_step")                   # [B, V]
            # per-POSITION fold_in: for ragged batches the same written
            # token index lands at different positions per row, so the
            # temperature>0 stream is path-specific (greedy is the
            # cached-vs-reencode equivalence contract)
            nxt = _sample(logits, jax.random.fold_in(key, pos),
                          temperature, pad_id)
            # write at pos+1 only inside this row's generation window;
            # prompt positions keep their tokens, the rest stays pad
            write = (pos + 1 >= ptr) & (pos + 1 < ptr + max_new_tokens)
            cur = jax.lax.dynamic_slice_in_dim(buf, pos + 1, 1,
                                               axis=1)[:, 0]
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(write, nxt, cur)[:, None], (0, pos + 1))
            return (buf, caches), None

        # scan only positions that can still write: start past the
        # prefilled prefix, stop at the last useful write position (the
        # buffer tail past every row's window would burn full decode
        # steps for nothing)
        (buf, _), _ = jax.lax.scan(
            step, (buf, caches),
            jnp.arange(prefill_len, min(scan_len, L - 1)))
        return buf

    return run


# bounded LRU: each entry pins its flax module AND its jitted decode
# program for as long as it stays hot — an unbounded dict would leak
# compiled programs in long-lived serving processes that cycle models
_RUN_CACHE: OrderedDict = OrderedDict()
_RUN_CACHE_MAX = 16
# modules whose causality probe already passed — the property is fixed
# per module architecture, so re-probing every generate() call would
# cost two eager encoder forwards per request on the serving path
_CAUSAL_OK: OrderedDict = OrderedDict()
# one lock for both caches: concurrent serving threads cycling > MAX
# models would otherwise race get/move_to_end against popitem eviction
_CACHE_LOCK = threading.Lock()


def generate(module, variables, prompt_ids, *, max_new_tokens: int,
             max_len: int | None = None, temperature: float = 0.0,
             seed: int = 0, pad_id: int = 0, use_cache: bool = True):
    """Generate continuations for a batch of prompts.

    ``module`` must produce token logits (``MaskedLMModel`` — the same
    trunk+head causal pretraining trains) and must run causal
    attention — enforced by the same perturbation probe
    ``pretrain_causal_lm`` uses (a bidirectional encoder would
    condition on its own padding, silently).

    ``prompt_ids``: [B, Tp] int32, RIGHT-padded with ``pad_id`` (a
    left-padded or empty row raises — the write pointer is the non-pad
    count). Returns [B, max_len] int32 — prompts, then generated
    tokens, then pad. ``temperature`` 0 = greedy; > 0 = softmax
    sampling.

    ``use_cache`` (default): KV-cached decode — O(L²·W) total via one
    scan with per-block caches. ``use_cache=False`` re-encodes the
    whole buffer every step (O(steps·L²·W)) through the encoder's own
    attention_fn — the reference path the cached one is tested
    against."""
    from .pretrain import assert_causal

    prompt_ids = np.asarray(prompt_ids, np.int32)
    B, Tp = prompt_ids.shape
    max_len = max_len or (Tp + max_new_tokens)
    if max_len < Tp + max_new_tokens:
        raise ValueError(
            f"max_len={max_len} cannot hold the prompt ({Tp}) plus "
            f"{max_new_tokens} new tokens")
    # per-row write pointer = non-pad count — only correct for strictly
    # right-padded prompts, so validate instead of silently scrambling
    ptr = (prompt_ids != pad_id).sum(axis=1).astype(np.int32)
    if (ptr == 0).any():
        raise ValueError("empty (all-pad) prompt row")
    trailing_ok = np.all(
        (np.arange(Tp)[None, :] < ptr[:, None])
        == (prompt_ids != pad_id))
    if not trailing_ok:
        raise ValueError(
            f"prompts must be RIGHT-padded with pad_id={pad_id} "
            "(found a pad before a real token)")
    with _CACHE_LOCK:
        causal_ok = module in _CAUSAL_OK
    if not causal_ok:
        vocab = getattr(getattr(module, "encoder", None), "vocab",
                        int(prompt_ids.max()) + 2)
        probe = prompt_ids[:1, :max(int(ptr[0]), 2)]
        if probe.shape[1] < 2:
            # a single-token prompt would make the probe a silent no-op
            # — duplicate the token so the check always actually runs
            # before the module is marked causally OK
            probe = np.repeat(probe, 2, axis=1)
        assert_causal(module, {"params": variables["params"]}, probe,
                      vocab)
        with _CACHE_LOCK:
            _CAUSAL_OK[module] = True
            while len(_CAUSAL_OK) > _RUN_CACHE_MAX:
                _CAUSAL_OK.popitem(last=False)

    buf = np.full((B, max_len), pad_id, np.int32)
    buf[:, :Tp] = prompt_ids
    # keyed on the module OBJECT (hashable frozen dataclass): an id()
    # key could collide after garbage collection and silently serve a
    # different model's compiled program
    scan_len = Tp + max_new_tokens - 1  # last useful write position
    # batched-prefill length: positions [0, min(ptr) - 1) hold real
    # tokens in EVERY row, so their caches can be seeded in one causal
    # forward; the scan takes over at the first position whose write
    # can matter. Static (ptr is host-side numpy), part of the key —
    # bucketed DOWN to a power of two so ragged serving batches whose
    # shortest prompt wobbles by a token share a compiled program
    # (any prefix ≤ min(ptr)-1 is a valid prefill; the scan streams
    # the remainder)
    prefill_len = max(int(ptr.min()) - 1, 0)
    if prefill_len >= 64:
        prefill_len -= prefill_len % 64   # ≤ 63 steps streamed instead
    elif prefill_len > 0:
        prefill_len = 1 << (prefill_len.bit_length() - 1)
    key = (module, max_new_tokens, float(temperature), pad_id,
           bool(use_cache),
           (scan_len, prefill_len) if use_cache else None)
    with _CACHE_LOCK:
        run = _RUN_CACHE.get(key)
        if run is not None:
            _RUN_CACHE.move_to_end(key)
    if run is None:
        if use_cache:
            run = _make_cached_run(module, max_new_tokens, temperature,
                                   pad_id, scan_len, prefill_len)
        else:
            run = _make_run(module, max_new_tokens, temperature, pad_id)
        with _CACHE_LOCK:
            _RUN_CACHE[key] = run
            while len(_RUN_CACHE) > _RUN_CACHE_MAX:
                _RUN_CACHE.popitem(last=False)
    return np.asarray(run(variables["params"], jnp.asarray(buf),
                          jnp.asarray(ptr), jax.random.PRNGKey(seed)))


class ContinuousGenerator:
    """Continuous batching for causal-LM decoding: a FIXED pool of
    sequence slots over a fixed ``[slots, max_len]`` token buffer, with
    new sequences admitted into free slots at **step boundaries**
    instead of waiting for the whole batch to drain.

    Why: classic dynamic batching (``generate`` behind a batcher) makes
    an arriving prompt wait for every in-flight generation to finish —
    up to ``max_new_tokens`` full steps of queueing. Here a sequence
    waits at most ONE decode step for a free slot. Slot bookkeeping and
    admission order live in ``sched.SlotScheduler`` (the same policy
    layer online serving uses — pure Python, device-free); this class
    is the device half: ONE jitted step program whose shapes never
    change (``[slots, max_len]``), so admission costs a buffer write,
    never a recompile.

    Decode math matches ``generate(use_cache=False)``: each step runs a
    full causal forward and samples from the logits at each row's
    ``ptr - 1`` (``_sample``, the shared epilogue). With
    ``temperature=0`` (greedy) per-sequence outputs are IDENTICAL to
    the non-continuous path — rows of a causal transformer are batch-
    independent — which is the equivalence contract the tests pin.
    With ``temperature > 0`` each token is still a sample from the
    model's distribution, but the sampled STREAM differs from
    ``generate``'s: keys fold in the global step index, and a sequence
    admitted mid-flight sees different step indices than one starting a
    fresh batch (same caveat as ``TextGenerator.draftLm``).

    Each step re-encodes the whole buffer (O(L²·W) per step, the
    ``use_cache=False`` reference path); slot-wise KV caches with
    per-slot prefill are the follow-up optimization and change nothing
    about the admission protocol.
    """

    def __init__(self, module, variables, *, slots: int = 4,
                 max_len: int = 64, temperature: float = 0.0,
                 pad_id: int = 0, seed: int = 0,
                 service: str = "generate", registry=None):
        from ..sched import SlotScheduler

        self.module = module
        self.variables = variables
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.pad_id = int(pad_id)
        self.sched = SlotScheduler(self.slots, service=service,
                                   registry=registry)
        self._buf = jnp.full((self.slots, self.max_len), self.pad_id,
                             jnp.int32)
        # free slots idle at ptr=1 (keeps the ptr-1 logit gather in
        # bounds); their sampled tokens are never written (write mask)
        self._ptr = jnp.ones((self.slots,), jnp.int32)
        self._active = np.zeros(self.slots, bool)
        self._key = jax.random.PRNGKey(seed)
        self._step_idx = 0
        self._probed = False
        self._run = self._make_step()

    def _make_step(self):
        module, temperature, pad_id = \
            self.module, self.temperature, self.pad_id
        S, L = self.slots, self.max_len

        @jax.jit
        def step(params, buf, ptr, active, key, i):
            logits = module.apply({"params": params}, buf)["logits"]
            last = jnp.take_along_axis(
                logits, (ptr - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]                            # [S, V]
            nxt = _sample(last, jax.random.fold_in(key, i), temperature,
                          pad_id)
            write = active & (ptr < L)
            at = jnp.minimum(ptr, L - 1)
            cur = buf[jnp.arange(S), at]
            buf = buf.at[jnp.arange(S), at].set(
                jnp.where(write, nxt, cur))
            return buf, ptr + write.astype(jnp.int32)

        return step

    # -- intake ------------------------------------------------------------
    def submit(self, seq_id, prompt_ids, max_new_tokens: int) -> None:
        """Queue one sequence. ``prompt_ids``: 1-D int32, no padding.
        Admitted at the next step boundary with a free slot."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if (prompt == self.pad_id).any():
            raise ValueError(f"prompt contains pad_id={self.pad_id}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + {max_new_tokens} new tokens "
                f"exceeds max_len={self.max_len}")
        if not self._probed:
            # same causality gate as generate(): a bidirectional
            # encoder would silently condition on its own padding
            from .pretrain import assert_causal
            probe = prompt[None, :] if prompt.size >= 2 else \
                np.repeat(prompt[None, :], 2, axis=1)
            vocab = getattr(getattr(self.module, "encoder", None),
                            "vocab", int(probe.max()) + 2)
            assert_causal(self.module,
                          {"params": self.variables["params"]}, probe,
                          vocab)
            self._probed = True
        self.sched.offer(seq_id, prompt, int(max_new_tokens))

    # -- the boundary protocol ---------------------------------------------
    def step(self) -> list:
        """One step boundary: admit pending sequences into free slots,
        run one jitted decode step, account completions. Returns
        ``(seq_id, output_row)`` pairs finished by this step."""
        for a in self.sched.admit():
            row = np.full(self.max_len, self.pad_id, np.int32)
            row[:len(a.prompt)] = a.prompt
            self._buf = self._buf.at[a.slot].set(jnp.asarray(row))
            self._ptr = self._ptr.at[a.slot].set(len(a.prompt))
            self._active[a.slot] = True
        if not self._active.any():
            return []
        # a copy: ``_active`` changes below while the program may not have
        # read it yet (off the chip an uploaded array can share the host's
        # memory)
        self._buf, self._ptr = self._run(
            self.variables["params"], self._buf, self._ptr,
            jnp.asarray(self._active.copy()), self._key, self._step_idx)
        self._step_idx += 1
        done = []
        for seq_id, slot in self.sched.step():
            self._active[slot] = False
            done.append((seq_id, np.asarray(self._buf[slot])))
        return done

    def run_until_drained(self) -> dict:
        """Step until every offered sequence completes; returns
        ``{seq_id: [max_len] int32 row}`` (prompt, then generated
        tokens, then pad)."""
        out = {}
        while self.sched.busy:
            for seq_id, row in self.step():
                out[seq_id] = row
        return out


class TextGenerator(Transformer, HasInputCol, HasOutputCol):
    """Pipeline stage: text prompts → generated continuations.

    Composes the whole decoder stack at the framework's core
    abstraction: a fitted ``BpeTokenizerModel`` encodes prompts to id
    rows, :func:`generate` decodes with the causal LM (KV-cached), and
    the tokenizer's ``decode`` renders continuations back to text. No
    reference counterpart (SURVEY §5: text/long-context is the
    framework's extension axis)."""

    # StageParam: fitted stages round-trip through their OWN save/load
    # (raw pickling would bake BpeTokenizerModel's internal caches and
    # attribute layout into the artifact)
    tokenizer = StageParam("tokenizer", "fitted BpeTokenizerModel")
    lm = ComplexParam("lm", "(module, variables): a causal MaskedLMModel "
                      "and its trained variables")
    maxNewTokens = Param("maxNewTokens", "tokens to generate per row",
                         TC.toInt, default=16, has_default=True)
    temperature = Param("temperature", "0 = greedy; > 0 = sampling",
                        TC.toFloat, default=0.0, has_default=True)
    seed = Param("seed", "sampling seed", TC.toInt, default=0,
                 has_default=True)
    draftLm = ComplexParam(
        "draftLm", "(module, variables) of a smaller same-vocab causal "
        "LM: when set, decoding runs SPECULATIVELY (dl.speculative — "
        "the draft proposes, the lm verifies k positions per pass). "
        "temperature=0: output identical to the non-draft stage. "
        "temperature>0: each token is still an EXACT sample from the "
        "lm's distribution (rejection-sampling acceptance, see "
        "dl.speculative), but the sampled STREAM differs from the "
        "non-draft stage run — length-grouping changes batch "
        "composition and per-row key schedules, so equality is "
        "distribution-exactness, not stream equality. Rows are "
        "grouped by prompt length (speculation needs dense "
        "equal-length rows), one compiled program per distinct "
        "length.",
        default=None, has_default=True)
    speculativeK = Param(
        "speculativeK", "draft tokens proposed per verify pass",
        TC.toInt, default=4, has_default=True)

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._setDefault(inputCol="text", outputCol="generated")

    def _transform(self, df):
        tok = self.get("tokenizer")
        module, variables = self.get("lm")
        if len(df) == 0:  # nothing to decode (and generate() reduces
            return df.with_column(self.getOutputCol(),
                                  np.empty(0, object))
        ids = tok.transform(
            df.with_column(tok.getInputCol(),
                           df[self.getInputCol()]))[tok.getOutputCol()]
        ids = np.asarray(ids, np.int32)
        # generate() requires non-empty rows; give blank prompts UNK
        ptr = (ids != 0).sum(axis=1)
        ids[ptr == 0, 0] = 1
        ptr = np.maximum(ptr, 1)
        n_new = self.get("maxNewTokens")
        draft = self.get("draftLm")
        texts = np.empty(len(ids), object)
        if draft is not None:
            from .speculative import generate_speculative
            draft_module, draft_variables = draft
            # speculation needs dense equal-length rows: group ragged
            # prompts by length, one batched call per group
            for plen in np.unique(ptr):
                rows = np.flatnonzero(ptr == plen)
                out_g, _ = generate_speculative(
                    module, variables, draft_module, draft_variables,
                    ids[rows, :plen], max_new_tokens=n_new,
                    k=self.get("speculativeK"),
                    temperature=self.get("temperature"),
                    seed=self.get("seed"))
                for r, row in zip(rows, out_g):
                    texts[r] = tok.decode(row[plen:plen + n_new])
            return df.with_column(self.getOutputCol(), texts)
        out = generate(module, variables, ids, max_new_tokens=n_new,
                       temperature=self.get("temperature"),
                       seed=self.get("seed"))
        # each row's continuation starts at ITS prompt length (ragged
        # prompts generate before Tp), never contains pad
        texts[:] = [tok.decode(row[p:p + n_new])
                    for row, p in zip(out, ptr)]
        return df.with_column(self.getOutputCol(), texts)
