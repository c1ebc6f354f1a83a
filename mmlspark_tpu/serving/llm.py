"""LLM serving engine: disaggregated prefill/decode over the paged KV
cache, with speculation inside the continuous batch.

The pre-existing generation path (``dl.ContinuousGenerator``) is a
monolithic dense-cache decoder: every slot owns a ``[max_len]`` cache
row, prompts prefill inside the decode program, and a long prompt
admission stalls the whole batch for its prefill. This module is the
serving-shaped rebuild the ROADMAP names (and the TPU serving
comparison in arXiv:2605.25645 measures): the two phases have opposite
execution profiles — prefill is a large, MXU-saturating causal forward;
decode is a tiny launch-latency-bound step — so they get SEPARATE
executors with separate padding buckets and separate AOT-fingerprinted
programs, stitched together by a handoff of (sequence, block chain)
over the paged KV pool (``dl.paged_kv``):

- :class:`PrefillExecutor` fills KV blocks in padding-bucketed batches
  (one compiled program per window bucket), starting AFTER any
  prefix-reused blocks — a warm prompt skips exactly the prefill the
  cache already holds, which is the TTFT win the bench measures.
- :class:`DecodeExecutor` runs the fixed-shape continuous-batching step
  over block tables. Attention reads the pools IN PLACE through the
  block table (``dl.pallas_paged_attention`` — the Pallas kernel on
  TPU, its bit-exact lax reference on CPU): each step embeds the
  slots' tokens, scatters the new kv through the table, and attends
  each slot's own chain: no dense copy of a chain exists. Greedy
  output stays token-identical to ``dl.generate`` (pinned by test).
  With a draft model,
  ``dl.speculative``'s draft/verify window runs PER SLOT: each slot
  accepts its own longest agreeing prefix (no batch sync-on-min —
  block chains advance independently), so accepted bursts move a slot
  by up to k+1 tokens per step; the verify window is the kernel's
  windowed variant (k+1 query rows per slot).
- Handoff rides :class:`HandoffQueue`: the prefill side exports the
  sequence from the block table (:meth:`PagedKVManager.export_seq` —
  ownership moves with the payload), the decode side adopts it when it
  has a free slot (load-aware pull). The payload is a flat JSON dict —
  :func:`pack_handoff` / :func:`unpack_handoff` — exactly the shape the
  distributed tier's ``__lease__`` envelope (``serving.distributed``)
  already carries for replayed work, so a cross-host split reuses that
  plumbing unchanged (plus a block-content transfer, which in-process
  disaggregation doesn't need: both executors address the same pools).

Every device program is built through ``compile_tracker.jit`` with a
stable name and carries an AOT fingerprint (``core.aot.fingerprints``
over the program's static shape key), so a warmed worker serves both
phases with zero runtime compiles (``mark_steady`` + the CompileTracker
steady-state assertion is the acceptance test). On TPU-class backends
the pools are DONATED to every program (``donate_argnums``): each step
writes its kv into the buffers it read from, so steady-state decode
allocates nothing per step (donation is skipped off-TPU, where XLA
ignores it with a warning).

The engine knows nothing of a model's layers. It asks a DECODER MODULE
for what each layer caches (``cache_spec()``: the arrays, as trailing
shape, dtype and KIND — one entry a token, one every ``n`` tokens, or one
a sequence whatever its length; ``paged_kv.init_pools`` /
``pool_block_bytes`` / ``state_row_bytes`` / ``scatter_positions`` take
that spec), for a
walk over a window of tokens through the paged pools
(``module.apply({"params": ...}, toks, pools, rows, pos, valid,
method="walk") -> (hidden, pools, counts)``: the window's ``[S, w,
width]`` hidden rows after the last block and NO head; prefill windows
and the decode step alike) and for the head over the rows it names
(``module.apply({"params": ...}, hidden_rows, method="logits") ->
[..., V]``); with them ``max_window()``, ``program_key()`` and
``walk_stats``, the names of the counts. Logits exist only for rows a
token is sampled from, and each caller says which from the shapes it
already holds: a prefill program asks for ONE row a prompt (the last
prompt row of the chunk in which the prompt ends) and for none in a
chunk where no prompt ends; the decode step for its one row a slot; the
speculative verify for all ``k + 1`` rows of its window. The model's
type picks the path — ``dl.MaskedLMModel`` (per-head k and v pools),
``dl.LatentMoEDecoder`` (one latent array a layer, dropless experts) or
``dl.SparseLinearDecoder`` (grouped-query k and v pools with compressed
keys beside them, blocks chosen inside paged attention, and a recurrent
state a sequence in the lightning layers) — and no flag does.

A decoder that caches arrays a SEQUENCE gets rows for them from the same
block manager (``PagedKVManager(state_slots=...)``; the pools of all
three kinds are ONE pytree, donated to every program alike), its walk is
handed the slots' rows after the arguments every walk takes, and prefix
reuse goes by STATE SNAPSHOT: admission after a prefix hit copies the
snapshot's row into the sequence's row on the device (an
``llm.state_restore`` span under ``llm.prefill``), a chunked prefill
carries the row from chunk to chunk, and a prompt that brings new whole
chunks is cut at its last one, where its state is copied into a snapshot
row that ``publish`` indexes with the blocks. Speculation is refused
beside such a decoder: a state cannot be rewound.

Obs: every boundary is an ``llm.step`` span on the tracer's ring with
``llm.prefill`` and ``llm.decode`` children; a decoder's walk counts
land on the registry inside the step's one fetch (``<name>_total``
counters, ``*_max`` gauges: ``moe_pairs_held_total``,
``moe_pairs_absent_total``, ``moe_experts_touched_total``,
``moe_expert_load_max``; ``sparse_blocks_chosen_total``,
``sparse_blocks_in_chain_total``, ``sparse_dense_rows_total``);
``gen_ttft_seconds{reuse=cold|warm}``,
``gen_tokens_total``, ``gen_prefill_calls_total{head=row|none}``
(prefill program calls by what they emit),
``gen_spec_accept_ratio``, ``gen_decode_steps_total``,
``gen_decode_attn_seconds{phase}`` here, the ``kv_*`` families
(``kv_state_*`` among them) in ``dl.paged_kv`` — all federated
fleet-wide and recorded by the telemetry history plane. Completions land FeatureLog rows with
``decode_steps``/``prefill_tokens``/``context_blocks`` so the cost
model prices the two phases separately and decode by resident context
(``perf.costmodel``, schema v5).
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field

import numpy as np

from ..core import aot
from ..dl.paged_kv import (TRASH_ROW, OutOfBlocks, PagedKVManager,
                           blocks_for_hbm_budget, copy_state_rows,
                           init_pools, pool_block_bytes,
                           state_row_bytes)
from ..obs import registry as _default_registry
from ..obs.attribution import cost_attribution
from ..obs.profile import compile_tracker, feature_log
from ..obs.tracing import tracer as _tracer
from ..sched.continuous import SlotScheduler

__all__ = ["LLMEngine", "PrefillExecutor", "DecodeExecutor",
           "HandoffQueue", "pack_handoff", "unpack_handoff"]


def _bucket_window(n: int) -> int:
    """Pad a prefill window to the compile-cache-friendly grid —
    the same ladder ``dl.generate`` buckets prefix lengths on (≥64:
    multiple of 64, below: power of two)."""
    n = max(int(n), 1)
    if n >= 64:
        return ((n + 63) // 64) * 64
    p = 1
    while p < n:
        p <<= 1
    return p


def _attribute_warm(prog, service: str, *args) -> None:
    """Analytic roofline attribution for a warmed program
    (obs.attribution, ISSUE 20): re-lower the tracked jit AOT and read
    ``cost_analysis`` off the Lowered (a trace, not a compile — the
    compile only happens on JAX builds whose Lowered cannot answer).
    Runs at warm time, before ``mark_steady``, so the extra trace never
    counts as a runtime compile. Attribution is telemetry, never a
    serving gate: a lowering that fails is skipped, a cost analysis
    that yields nothing and a device without a PeakSpec row are
    counted (``profile_cost_analysis_missing_total``,
    ``profile_peak_spec_missing_total``) and warm-up goes on."""
    lower = getattr(prog, "lower", None)
    if lower is None:
        return
    name = getattr(prog, "__tracked_label__", f"llm_{service}")
    try:
        lowered = lower(*args)
    except Exception:
        return
    if cost_attribution.record_compiled(
            name, lowered, service=service) is not None:
        return
    try:
        compiled = lowered.compile()
    except Exception:
        return
    cost_attribution.record_compiled(name, compiled, service=service)


def _donate_pools_kwargs() -> dict:
    """``donate_argnums`` for the pool arguments (positions 2/3 of
    every executor program) on backends where donation is real — each
    step then writes its kv into the buffers it read from, so warmed
    decode allocates nothing per step. Off-TPU XLA ignores donation
    with a warning per program, so skip it there."""
    from ..utils.platform import target_platform
    if target_platform() == "tpu":
        return {"donate_argnums": (2, 3)}
    return {}


def _greedy(logits, pad_id: int):
    """The greedy pick every program samples with: ``argmax`` over the
    vocabulary with the pad column masked out, as int32."""
    import jax.numpy as jnp
    logits = logits.at[..., pad_id].set(-jnp.inf)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _WalkStats:
    """The counts a decoder's walk returns beside its hidden rows
    (``module.walk_stats`` names them), on the engine's registry: a name
    that ends in ``_max`` is a gauge of that name (the last call's
    value), every other a counter ``<name>_total``. The executors fetch
    the counts inside the fetch of the tokens they return."""

    def __init__(self, module, reg, service: str):
        self.service = service
        self._sinks = []
        for name in getattr(module, "walk_stats", ()):
            if name.endswith("_max"):
                gauge = reg.gauge(
                    name, f"decoder walk count {name}, last call, "
                    "by service")
                self._sinks.append(gauge.set)
            else:
                counter = reg.counter(
                    f"{name}_total", f"decoder walk count {name}, summed "
                    "over layers and calls, by service")
                self._sinks.append(counter.inc)

    def __bool__(self) -> bool:
        return bool(self._sinks)

    def record(self, counts) -> None:
        for sink, value in zip(self._sinks, np.asarray(counts)):
            sink(float(value), service=self.service)


# ----------------------------------------------------------------- handoff

def pack_handoff(payload: dict) -> bytes:
    """Serialize a prefill→decode handoff for the wire — the body the
    distributed tier's ``__lease__`` envelope carries when the two
    executors live on different hosts."""
    return json.dumps(payload, sort_keys=True).encode()


def unpack_handoff(data: bytes) -> dict:
    return json.loads(data.decode())


class HandoffQueue:
    """The prefill→decode boundary: prefill pushes exported sequences,
    decode pulls AT MOST its free-slot count per boundary (load-aware —
    a saturated decoder leaves work queued instead of overcommitting).
    Payloads round-trip :func:`pack_handoff` so the in-process queue
    and the cross-host lease path carry identical bytes."""

    def __init__(self):
        self._q: list[dict] = []

    def push(self, payload: dict) -> None:
        # serialize/deserialize even in-process: the payload must stay
        # wire-shaped or the cross-host path rots silently
        self._q.append(unpack_handoff(pack_handoff(payload)))

    def pull(self, max_items: int) -> list[dict]:
        n = max(int(max_items), 0)
        out, self._q = self._q[:n], self._q[n:]
        return out

    def __len__(self) -> int:
        return len(self._q)


class _PoolState:
    """Shared mutable holder for the device pools: both executors read
    and replace the SAME pools (in-process disaggregation — the block
    table addresses one physical pool)."""

    def __init__(self, target, draft=None):
        self.target = target
        self.draft = draft


# --------------------------------------------------------------- executors

class PrefillExecutor:
    """Fills KV blocks for admitted prompts in padding-bucketed batches.

    Two compiled programs per window bucket ``w``, keyed by (window,
    emits a token). Both run the paged window walk (the decoder's
    ``walk``, which returns hidden rows and no logits) over the prompt
    SUFFIX (everything past the prefix-reused blocks) at per-row start
    positions — SCATTER-ONLY: each block's kv writes through the table
    as it is computed and attention reads the pools in place. The one
    that emits picks each prompt's last row of the window out of the
    hidden rows, asks the decoder's ``logits`` for those ``[P, width]``
    rows alone and returns each row's first generated token (TTFT is
    measured here): no ``[P, w, V]`` value exists. The other runs no head at
    all and returns the pools and the walk's counts; :meth:`prefill`
    calls it for a chunk in which no prompt of the batch ends, which it
    knows on the host before the call
    (``gen_prefill_calls_total{head="row"|"none"}`` counts both kinds).
    With a draft model the same window also fills the DRAFT pools, so
    prefix-reused blocks hold both models' kv consistently."""

    def __init__(self, module, variables, kv: PagedKVManager,
                 pools: _PoolState, *, draft_module=None,
                 draft_variables=None, max_blocks: int, batch: int = 4,
                 pad_id: int = 0, service: str = "llm", registry=None):
        self.module = module
        self.variables = variables
        self.draft_module = draft_module
        self.draft_variables = draft_variables
        self.kv = kv
        self.pools = pools
        self.max_blocks = int(max_blocks)
        self.batch = max(int(batch), 1)
        self.pad_id = int(pad_id)
        self.service = service
        # the widest window every model's walk takes: a longer suffix
        # is fed in chunks of this width
        self.max_window = min(
            m.max_window() for m in (module, draft_module)
            if m is not None)
        reg = registry if registry is not None else _default_registry
        self._h_attn = reg.histogram(
            "gen_decode_attn_seconds",
            "attention-program wall time, by service and phase",
            buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1,
                     .25, .5, 1., 2.5))
        self._c_calls = reg.counter(
            "gen_prefill_calls_total",
            "prefill program calls, by service and what the program "
            "emits: head=row one token a prompt, head=none no head")
        self._walk_stats = _WalkStats(module, reg, service)
        self._programs: dict[tuple[int, bool], object] = {}
        self._fps: dict[str, tuple[str, str]] = {}
        # a decoder with per-sequence cache arrays: its walk takes the
        # rows' state rows, and snapshots are copied row to row
        self.stateful = bool(kv.state_slots)
        self._copy = None

    def _copy_program(self):
        """Rows of the per-sequence arrays copied onto other rows, ``batch``
        pairs a call (a snapshot restored, a snapshot taken), the pools
        donated like every program's."""
        if self._copy is None:
            spec = self.module.cache_spec()
            donate = _donate_pools_kwargs()
            self._copy = compile_tracker.jit(
                lambda pools, src, dst: copy_state_rows(spec, pools, src,
                                                        dst),
                name=f"llm_state_copy_{self.service}_b{self.batch}",
                **({"donate_argnums": (0,)} if donate else {}))
        return self._copy

    def _copy_rows(self, pairs: list) -> None:
        import jax.numpy as jnp
        src = np.full(self.batch, TRASH_ROW, np.int32)
        dst = np.full(self.batch, TRASH_ROW, np.int32)
        for i, (a, b) in enumerate(pairs):
            src[i], dst[i] = a, b
        self.pools.target = self._copy_program()(
            self.pools.target, jnp.asarray(src), jnp.asarray(dst))

    # -- compiled programs per window bucket -------------------------------
    def _program(self, w: int, head: bool):
        """The program of window ``w`` that emits each prompt's first
        token (``head``), or the one that runs no head."""
        prog = self._programs.get((w, head))
        if prog is not None:
            return prog
        import jax.numpy as jnp
        module, draft = self.module, self.draft_module
        pad_id, P = self.pad_id, self.batch

        def run(params, dparams, pools_t, pools_d, rows, toks, pos, lens,
                *srows):
            valid = (jnp.arange(w)[None] < lens[:, None]) & \
                (lens[:, None] > 0)
            hidden, pools_t, counts = module.apply(
                {"params": params}, toks, pools_t, rows, pos, valid,
                *srows, method="walk")                  # [P, w, W]
            if draft is not None:
                _, pools_d, _ = draft.apply(
                    {"params": dparams}, toks, pools_d, rows, pos, valid,
                    method="walk")
            if not head:
                return pools_t, pools_d, None, counts
            # the head AFTER the pick: one row a prompt
            last = jnp.clip(lens - 1, 0, w - 1)
            row = jnp.take_along_axis(
                hidden, last[:, None, None], axis=1)[:, 0]      # [P, W]
            logits = module.apply({"params": params}, row,
                                  method="logits")      # [P, V]
            return pools_t, pools_d, _greedy(logits, pad_id), counts

        name = f"llm_prefill_{self.service}_w{w}_b{P}" \
            + ("" if head else "_nohead")
        prog = compile_tracker.jit(run, name=name,
                                   **_donate_pools_kwargs())
        self._programs[(w, head)] = prog
        key = {"phase": "prefill", "service": self.service,
               "window": w, "batch": P, "head": head,
               "attn": "paged",
               "max_blocks": self.max_blocks,
               "block_len": self.kv.block_len,
               "encoder": self.module.program_key(),
               "draft": None if draft is None else draft.program_key(),
               "versions": aot.runtime_versions()}
        self._fps[name] = aot.fingerprints(key, [], [])
        return prog

    def aot_fingerprints(self) -> dict:
        """program name -> (static_fp, full_fp) for every program built
        so far — the identity a warmed worker advertises."""
        return dict(self._fps)

    # -- host driver --------------------------------------------------------
    def windows_for(self, n: int) -> list:
        """The window of each program call a suffix of ``n`` tokens is
        fed through: whole ``max_window`` chunks, then the remainder on
        its bucket. One entry for every suffix the kernel holds whole."""
        full, rem = divmod(max(int(n), 1), self.max_window)
        return [self.max_window] * full + (
            [_bucket_window(rem)] if rem else [])

    def prefill(self, jobs: list) -> dict:
        """``jobs``: list of ``(seq_id, prompt_tokens)`` whose chains
        are already allocated in ``kv``. Runs bucketed batches — a
        suffix wider than ``max_window`` in consecutive chunks, each
        attending what the chunks before it wrote, and a chunk in which
        no prompt of the batch ends through the program with no head —
        commits lengths (``kv.advance`` + ``kv.publish``), returns
        ``seq_id -> (first_token, suffix_len)``.

        With per-sequence cache arrays a row that reuses a prefix first
        has the prefix's snapshot copied into its state row (an
        ``llm.state_restore`` span; on the device), and a prompt that
        brings new whole chunks is fed in two stretches, cut at its last
        whole chunk (``snapshot_at``), where its state is copied into a
        snapshot row before the rest is fed."""
        import jax
        import jax.numpy as jnp
        out: dict = {}
        P = self.batch
        for start in range(0, len(jobs), P):
            metas, restore = [], []
            for seq_id, prompt in jobs[start:start + P]:
                h = self.kv.handle(seq_id)
                # a fully reused prompt still re-feeds its last token:
                # the window must emit logits for the first generated
                # position (the rewrite stores bit-identical kv)
                s0 = min(h.reused_tokens, h.prompt_len - 1)
                metas.append((seq_id, np.asarray(prompt), s0,
                              h.prompt_len - s0))
                if h.restore_row is not None:
                    restore.append((h.restore_row, h.state_row))
            if restore:
                with _tracer.span("llm.state_restore", rows=len(restore)):
                    self._copy_rows(restore)
                for seq_id, *_ in metas:
                    self.kv.restored(seq_id)
            ids: list = [m[0] for m in metas]
            padded = ids + [None] * (P - len(ids))
            rows = jnp.asarray(self.kv.block_rows(padded, self.max_blocks))
            state_rows = self.kv.state_rows(padded) if self.stateful \
                else None
            firsts: dict = {}
            counts: list = []           # each call's walk counts

            def feed(spans):
                """One stretch of every row: ``spans[i]`` is row ``i``'s
                ``(first position, tokens)``."""
                done = 0                # tokens of the stretch fed so far
                for w in self.windows_for(max(n for _, n in spans)):
                    # a row whose stretch ended in an earlier chunk rides
                    # along like a padding row: position 0, length 0
                    toks = np.zeros((P, w), np.int32)
                    pos = np.zeros(P, np.int32)
                    lens = np.zeros(P, np.int32)
                    srows = np.full(P, TRASH_ROW, np.int32)
                    ends = []
                    for i, (p0, n) in enumerate(spans):
                        k = min(n - done, w)
                        if k <= 0:
                            continue
                        prompt = metas[i][1]
                        toks[i, :k] = prompt[p0 + done:p0 + done + k]
                        pos[i] = p0 + done
                        lens[i] = k
                        if self.stateful:
                            srows[i] = state_rows[i]
                        # known on the host before the call: whose last
                        # token is in this chunk
                        if p0 + done + k == len(prompt):
                            ends.append(i)
                    prog = self._program(w, bool(ends))
                    self._c_calls.inc(1, service=self.service,
                                      head="row" if ends else "none")
                    t0 = time.perf_counter()
                    pools_t, pools_d, first, count = prog(
                        self.variables["params"],
                        None if self.draft_module is None
                        else self.draft_variables["params"],
                        self.pools.target, self.pools.draft,
                        rows, jnp.asarray(toks),
                        jnp.asarray(pos), jnp.asarray(lens),
                        *((jnp.asarray(srows),) if self.stateful else ()))
                    if self._walk_stats:
                        counts.append(count)
                    self._h_attn.observe(time.perf_counter() - t0,
                                         service=self.service,
                                         phase="prefill")
                    self.pools.target = pools_t
                    if self.draft_module is not None:
                        self.pools.draft = pools_d
                    for i in ends:
                        firsts[i] = first
                    done += w

            # where a row's feed is cut: the boundary its snapshot is
            # taken at, when the prompt goes on past it
            cuts = []
            for seq_id, prompt, s0, _ in metas:
                at = self.kv.handle(seq_id).snapshot_at
                cuts.append(at if at is not None and at < len(prompt)
                            else len(prompt))
            feed([(s0, cut - s0) for (_, _, s0, _), cut in zip(metas, cuts)])
            if self.stateful:
                taken = []
                for seq_id, *_ in metas:
                    h = self.kv.handle(seq_id)
                    if h.snapshot_at is not None:
                        row = self.kv.take_snapshot(seq_id)
                        if row is not None:
                            taken.append((h.state_row, row))
                if taken:
                    self._copy_rows(taken)
            if any(cut < len(m[1]) for m, cut in zip(metas, cuts)):
                feed([(cut, len(m[1]) - cut) for m, cut in zip(metas, cuts)])
            # ONE fetch: the first tokens and the calls' counts together
            firsts, counts = jax.device_get((firsts, counts))
            for count in counts:
                self._walk_stats.record(count)
            for i, (seq_id, _, _, n) in enumerate(metas):
                h = self.kv.handle(seq_id)
                self.kv.advance(seq_id, h.prompt_len - h.length)
                self.kv.publish(seq_id)
                out[seq_id] = (int(firsts[i][i]), int(n))
        return out

    def warm(self, windows=(1,)) -> None:
        """Compile (and run, against the trash block only) the programs
        a suffix of each given length is fed through — the warmup sweep
        before ``compile_tracker.mark_steady()``. Every window gets the
        program that emits (a prompt of the batch can end in any chunk);
        a chunk before the last is ``max_window`` wide and may hold no
        prompt's end, so a suffix of several chunks adds that window's
        program with no head."""
        import jax.numpy as jnp
        P = self.batch
        kinds = set()
        for n in windows:
            chunks = self.windows_for(n)
            kinds.update((w, True) for w in chunks)
            kinds.update((w, False) for w in chunks[:-1])
        if self.stateful:               # trash row onto trash row
            self._copy_rows([])
        for w, head in sorted(kinds):
            rows = jnp.zeros((P, self.max_blocks), jnp.int32)
            prog = self._program(w, head)
            args = (
                self.variables["params"],
                None if self.draft_module is None
                else self.draft_variables["params"],
                self.pools.target, self.pools.draft, rows,
                jnp.zeros((P, w), jnp.int32), jnp.zeros(P, jnp.int32),
                jnp.zeros(P, jnp.int32),
                *((jnp.zeros(P, jnp.int32),) if self.stateful else ()))
            # attribution must lower BEFORE the call: donation
            # invalidates the pool buffers the args reference
            _attribute_warm(prog, self.service, *args)
            pools_t, pools_d, *_ = prog(*args)
            self.pools.target = pools_t
            if self.draft_module is not None:
                self.pools.draft = pools_d


class DecodeExecutor:
    """The fixed-shape continuous-batching decode step over block
    tables. All shapes are pinned at construction — ``[slots]`` state
    vectors, ``[slots, max_blocks]`` block tables — so ONE program per
    mode serves every step (the zero-runtime-compile contract).

    Plain mode: ONE paged window walk of width 1 — embed the slots'
    last tokens, scatter kv through the table, paged attention over
    each chain in place — then the decoder's ``logits`` over its one
    row a slot and a greedy ``argmax`` with pad masked — the
    numerics of ``dl.generate``'s cached path with zero dense
    gathers. Spec mode (draft present): ``dl.speculative``'s
    draft/verify runs as k width-1 draft walks plus one width-(k+1)
    target walk (the kernel's windowed variant) whose ``k + 1`` rows
    all get logits; each slot accepts its
    own longest agreeing prefix — no batch sync-on-min, block chains
    advance independently."""

    def __init__(self, module, variables, kv: PagedKVManager,
                 pools: _PoolState, *, draft_module=None,
                 draft_variables=None, slots: int, max_blocks: int,
                 spec_k: int = 0, pad_id: int = 0,
                 service: str = "llm", registry=None):
        if spec_k and draft_module is None:
            raise ValueError("spec_k > 0 needs a draft model")
        self.module = module
        self.variables = variables
        self.draft_module = draft_module
        self.draft_variables = draft_variables
        self.kv = kv
        self.pools = pools
        self.slots = int(slots)
        self.max_blocks = int(max_blocks)
        self.spec_k = int(spec_k)
        self.pad_id = int(pad_id)
        self.service = service
        reg = registry if registry is not None else _default_registry
        self._h_attn = reg.histogram(
            "gen_decode_attn_seconds",
            "attention-program wall time, by service and phase",
            buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1,
                     .25, .5, 1., 2.5))
        self._walk_stats = _WalkStats(module, reg, service)
        # host-side slot state (the engine owns seq metadata)
        self.seq_ids: list = [None] * self.slots
        self.ptr = np.ones(self.slots, np.int32)   # committed tokens
        self.end = np.ones(self.slots, np.int32)   # commit cap
        self.last = np.zeros(self.slots, np.int32)  # token @ ptr-1
        self.active = np.zeros(self.slots, bool)
        self._program = None
        self._fps: dict[str, tuple[str, str]] = {}

    @property
    def free_slots(self) -> int:
        return int(self.slots - self.active.sum())

    # -- slot lifecycle -----------------------------------------------------
    def activate(self, slot_hint, state: dict) -> int:
        """Adopt a handoff payload into a free slot. ``slot_hint`` (the
        scheduler's assignment) is used when free; any free slot
        otherwise."""
        slot = slot_hint if (slot_hint is not None
                             and not self.active[slot_hint]) else \
            int(np.flatnonzero(~self.active)[0])
        handle = self.kv.adopt(state["seq"])
        self.seq_ids[slot] = handle.seq_id
        # cache holds [0, prompt_len); the first generated token (from
        # prefill) is committed at position prompt_len, pending embed
        self.ptr[slot] = handle.length + 1
        self.end[slot] = handle.length + int(state["max_new_tokens"])
        self.last[slot] = int(state["first"])
        self.active[slot] = True
        return slot

    def deactivate(self, slot: int) -> None:
        self.seq_ids[slot] = None
        self.active[slot] = False
        self.ptr[slot] = 1
        self.end[slot] = 1
        self.last[slot] = self.pad_id

    # -- the compiled step --------------------------------------------------
    def _build(self):
        if self._program is not None:
            return self._program
        import jax.numpy as jnp
        module, draft = self.module, self.draft_module
        pad_id, k, S = self.pad_id, self.spec_k, self.slots

        if k == 0:
            def run(params, dparams, pools_t, pools_d, rows, last, ptr,
                    end, active, *srows):
                hidden, pools_t, counts = module.apply(
                    {"params": params}, last[:, None], pools_t, rows,
                    ptr - 1, active[:, None], *srows,
                    method="walk")                      # [S, 1, W]
                logits = module.apply({"params": params}, hidden,
                                      method="logits")  # every row: w = 1
                committed = _greedy(logits[:, 0], pad_id)[:, None]  # [S, 1]
                n_new = jnp.where(active, 1, 0)
                return pools_t, pools_d, committed, n_new, n_new, counts
        else:
            if self.kv.state_slots:
                raise ValueError(
                    "speculative decoding rewinds a slot by the tokens it "
                    "rejects, and a per-sequence state cannot be rewound")

            def run(params, dparams, pools_t, pools_d, rows, last, ptr,
                    end, active):
                pos = ptr - 1
                av = active[:, None]
                tok = last[:, None]                     # [S, 1]
                drafts = []
                for j in range(k):
                    hd, pools_d, _ = draft.apply(
                        {"params": dparams}, tok, pools_d, rows,
                        pos + j, av, method="walk")
                    ld = draft.apply({"params": dparams}, hd,
                                     method="logits")
                    tok = _greedy(ld[:, 0], pad_id)[:, None]
                    drafts.append(tok[:, 0])
                # extra cache-fill step: d_k's kv, or the next round's
                # draft attends a zero hole after a full accept (same
                # fix as dl.speculative)
                _, pools_d, _ = draft.apply(
                    {"params": dparams}, tok, pools_d, rows, pos + k,
                    av, method="walk")
                d = jnp.stack(drafts, 1)                # [S, k]
                window = jnp.concatenate([last[:, None], d], 1)
                ht, pools_t, counts = module.apply(
                    {"params": params}, window, pools_t, rows, pos,
                    av & jnp.ones((S, k + 1), bool),
                    method="walk")                      # [S, k+1, W]
                # the verify samples from every row of its window
                lt = module.apply({"params": params}, ht,
                                  method="logits")      # [S, k+1, V]
                t = _greedy(lt, pad_id)
                agree = jnp.cumprod(
                    (d == t[:, :k]).astype(jnp.int32), axis=1)
                n_acc = agree.sum(axis=1)               # PER-SLOT
                bonus = jnp.take_along_axis(
                    t, n_acc[:, None], axis=1)[:, 0]
                ar = jnp.arange(k + 1)[None]            # [1, k+1]
                d_ext = jnp.concatenate(
                    [d, jnp.zeros((S, 1), jnp.int32)], 1)
                committed = jnp.where(
                    ar < n_acc[:, None], d_ext,
                    jnp.where(ar == n_acc[:, None], bonus[:, None],
                              pad_id))                  # [S, k+1]
                # never commit past the slot's budget (end - ptr
                # tokens remain; runnable slots have at least 1)
                n_new = jnp.clip(n_acc + 1, 1,
                                 jnp.maximum(end - ptr, 1))
                n_new = jnp.where(active, n_new, 0)
                return pools_t, pools_d, committed, n_new, \
                    jnp.where(active, n_acc, 0), counts

        name = f"llm_decode_paged_{self.service}_S{S}_k{k}"
        self._program = compile_tracker.jit(run, name=name,
                                            **_donate_pools_kwargs())
        key = {"phase": "decode", "service": self.service, "slots": S,
               "spec_k": k, "attn": "paged",
               "max_blocks": self.max_blocks,
               "block_len": self.kv.block_len,
               "encoder": self.module.program_key(),
               "draft": None if draft is None else draft.program_key(),
               "versions": aot.runtime_versions()}
        self._fps[name] = aot.fingerprints(key, [], [])
        return self._program

    def aot_fingerprints(self) -> dict:
        return dict(self._fps)

    @property
    def runnable(self) -> np.ndarray:
        """Slots that should actually decode this step: active AND
        budget remaining (a 1-token sequence is complete the moment its
        prefill-produced first token lands)."""
        return self.active & (self.ptr < self.end)

    def step(self) -> dict:
        """One decode step over every runnable slot. Returns
        ``slot -> (tokens_committed list, n_accepted)``; the caller
        commits tokens, advances the block table, and retires finished
        sequences."""
        import jax
        import jax.numpy as jnp
        runnable = self.runnable
        if not runnable.any():
            return {}
        # capacity for this step's writes: positions up to ptr-1+k
        for s in range(self.slots):
            if runnable[s]:
                self.kv.ensure_capacity(self.seq_ids[s],
                                        int(self.ptr[s]) + self.spec_k)
        running = [sid if runnable[i] else None
                   for i, sid in enumerate(self.seq_ids)]
        rows = self.kv.block_rows(running, self.max_blocks)
        prog = self._build()
        t0 = time.perf_counter()
        pools_t, pools_d, committed, n_new, n_acc, counts = prog(
            self.variables["params"],
            None if self.draft_module is None
            else self.draft_variables["params"],
            self.pools.target, self.pools.draft, jnp.asarray(rows),
            jnp.asarray(self.last), jnp.asarray(self.ptr),
            jnp.asarray(self.end), jnp.asarray(runnable),
            *((jnp.asarray(self.kv.state_rows(running)),)
              if self.kv.state_slots else ()))
        self._h_attn.observe(time.perf_counter() - t0,
                             service=self.service, phase="decode")
        self.pools.target = pools_t
        if self.draft_module is not None:
            self.pools.draft = pools_d
        # ONE fetch a step: the tokens and the walk's counts together
        committed, n_new, n_acc, counts = jax.device_get(
            (committed, n_new, n_acc, counts))
        if self._walk_stats:
            self._walk_stats.record(counts)
        out = {}
        for s in range(self.slots):
            if not runnable[s]:
                continue
            n = int(n_new[s])
            toks = [int(t) for t in committed[s, :n]]
            self.kv.advance(self.seq_ids[s], n)
            self.ptr[s] += n
            self.last[s] = toks[-1]
            out[s] = (toks, int(n_acc[s]))
        return out

    def warm(self) -> None:
        """Run the step program once against the trash block (all slots
        inactive — every write lands in block 0) — the warmup before
        ``mark_steady``."""
        import jax.numpy as jnp
        prog = self._build()
        S = self.slots
        args = (
            self.variables["params"],
            None if self.draft_module is None
            else self.draft_variables["params"],
            self.pools.target, self.pools.draft,
            jnp.zeros((S, self.max_blocks), jnp.int32),
            jnp.zeros(S, jnp.int32), jnp.ones(S, jnp.int32),
            jnp.full(S, 2, jnp.int32), jnp.zeros(S, bool),
            *((jnp.zeros(S, jnp.int32),) if self.kv.state_slots else ()))
        # attribution must lower BEFORE the call: donation invalidates
        # the pool buffers the args reference
        _attribute_warm(prog, self.service, *args)
        pools_t, pools_d, *_ = prog(*args)
        self.pools.target = pools_t
        if self.draft_module is not None:
            self.pools.draft = pools_d


# ------------------------------------------------------------------ engine

@dataclass
class _SeqMeta:
    prompt: np.ndarray
    max_new_tokens: int
    t_submit: float
    slot: int | None = None
    t_first: float | None = None
    first_token: int | None = None
    reused_tokens: int = 0
    prefill_tokens: int = 0
    decode_steps: int = 0
    generated: list = field(default_factory=list)


class LLMEngine:
    """The assembled serving engine: paged KV pool + prefill executor +
    decode executor + continuous-batching scheduler.

    Greedy-only (``dl.generate`` temperature-0 semantics — the output
    contract is token identity with ``generate``); sampled speculative
    serving needs the rejection-sampling correction wired per slot and
    is out of scope here (``dl.speculative`` has the batched version).

    ``submit`` then ``step`` at boundaries (or ``run_until_drained``):
    each boundary admits pending sequences through the scheduler
    (shedding expired deadlines), prefills their suffixes in bucketed
    batches, hands off to decode through the load-aware queue, and runs
    one decode step. ``warm()`` precompiles both phases and declares
    CompileTracker steady state."""

    def __init__(self, module, variables, *, draft_module=None,
                 draft_variables=None, slots: int = 2,
                 block_len: int = 8, max_seq_len: int = 128,
                 num_blocks: int | None = None, spec_k: int = 0,
                 pad_id: int = 0, prefill_batch: int = 2,
                 hbm_fraction: float = 0.5, service: str = "llm",
                 registry=None, clock=time.monotonic,
                 state_slots: int | None = None):
        reg = registry if registry is not None else _default_registry
        self.module = module
        self.variables = variables
        self.pad_id = int(pad_id)
        self.service = service
        self.clock = clock
        self.max_seq_len = int(max_seq_len)
        self.block_len = int(block_len)
        self.max_blocks = -(-self.max_seq_len // self.block_len)
        # what a token takes in each layer's cache is the decoder's to
        # state; target and draft pools share the chains, so one block
        # costs both models' bytes and the two pools share ONE
        # hbm_fraction
        spec = module.cache_spec()
        block_bytes = pool_block_bytes(spec, self.block_len)
        if draft_module is not None:
            block_bytes += pool_block_bytes(draft_module.cache_spec(),
                                            self.block_len)
        if num_blocks is None:
            # HBM-derived sizing with a host/CPU fallback generous
            # enough for the slot count
            num_blocks = blocks_for_hbm_budget(
                block_bytes, fraction=hbm_fraction,
                default=1 + 2 * slots * self.max_blocks)
        # a decoder that keeps arrays a SEQUENCE (a recurrent state)
        # gets rows for them from the same manager: one a slot, and as
        # many again for snapshots unless the caller says how many
        row_bytes = state_row_bytes(spec)
        if not row_bytes:
            state_slots = 0
        elif state_slots is None:
            state_slots = 2 * int(slots)
        if draft_module is not None and (
                row_bytes or state_row_bytes(draft_module.cache_spec())):
            raise ValueError("a draft model beside per-sequence cache "
                             "arrays is not supported")
        row_blocks = -(-row_bytes // block_bytes)
        self.kv = PagedKVManager(
            num_blocks, self.block_len,
            block_budget=blocks_for_hbm_budget(
                block_bytes, fraction=hbm_fraction,
                default=num_blocks - 1 + state_slots * row_blocks),
            service=service, registry=reg, state_slots=state_slots,
            state_row_bytes=row_bytes, state_row_blocks=row_blocks)
        self.pools = _PoolState(
            init_pools(spec, num_blocks, self.block_len, state_slots),
            None if draft_module is None else init_pools(
                draft_module.cache_spec(), num_blocks, self.block_len))
        self.sched = SlotScheduler(slots, service=service,
                                   registry=reg, clock=clock)
        self.prefiller = PrefillExecutor(
            module, variables, self.kv, self.pools,
            draft_module=draft_module, draft_variables=draft_variables,
            max_blocks=self.max_blocks, batch=prefill_batch,
            pad_id=pad_id, service=service, registry=reg)
        self.decoder = DecodeExecutor(
            module, variables, self.kv, self.pools,
            draft_module=draft_module, draft_variables=draft_variables,
            slots=slots, max_blocks=self.max_blocks, spec_k=spec_k,
            pad_id=pad_id, service=service, registry=reg)
        self.handoff = HandoffQueue()
        self._meta: dict = {}
        self._to_prefill: list = []
        self._first_credit: dict = {}
        self._done: dict = {}
        self.expired: list = []
        self._spec_acc = [0, 0]     # accepted, offered
        self._h_ttft = reg.histogram(
            "gen_ttft_seconds",
            "submit→first-token latency, by service and prefix reuse",
            buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5,
                     1., 2.5, 5., 10.))
        self._c_tokens = reg.counter(
            "gen_tokens_total", "generated tokens committed, by service")
        # never incremented: benchmark/drivers look the name up at
        # set-up; it goes with ROADMAP Q1
        reg.counter(
            "kv_dense_gather_bytes_total",
            "always 0: no dense copy of a chain exists; kept registered "
            "for the benchmark's reader until ROADMAP Q1 drops it")
        self._c_steps = reg.counter(
            "gen_decode_steps_total", "decode steps executed, by service")
        self._g_accept = reg.gauge(
            "gen_spec_accept_ratio",
            "rolling fraction of offered draft tokens accepted, "
            "by service")
        self._c_spec_rejected = reg.counter(
            "gen_spec_rejected_total",
            "offered draft tokens rejected at verification, by service "
            "— target-model work the speculative gamble threw away "
            "(the goodput ledger prices it at the measured "
            "seconds-per-token)")

    # -- intake ------------------------------------------------------------
    def submit(self, seq_id, prompt, max_new_tokens: int,
               deadline: float | None = None) -> None:
        # kept as one array from here to the block table's hashes and the
        # prefill windows: a 65,536-token prompt is not a list of ints
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + int(max_new_tokens) > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len="
                f"{self.max_seq_len}")
        self._meta[seq_id] = _SeqMeta(prompt=prompt,
                                      max_new_tokens=int(max_new_tokens),
                                      t_submit=self.clock())
        self.sched.offer(seq_id, prompt, max_new_tokens,
                         deadline=deadline)

    # -- one step boundary --------------------------------------------------
    def step(self) -> list:
        """Admit → prefill → handoff → decode. Returns ``(seq_id,
        tokens)`` pairs (full sequence: prompt then generated) finished
        at this boundary."""
        with _tracer.span("llm.step") as root:
            return self._step(root)

    def _step(self, root) -> list:
        for a in self.sched.admit():
            self._to_prefill.append(a)
        for seq_id in self.sched.drain_expired():
            self._meta.pop(seq_id, None)
            self.expired.append(seq_id)
        if self._to_prefill:
            with _tracer.span("llm.prefill", parent=root):
                self._run_prefill()
        for payload in self.handoff.pull(self.decoder.free_slots):
            meta = self._meta[payload["seq"]["seq_id"]]
            slot = self.decoder.activate(meta.slot, payload)
            meta.slot = slot
            meta.first_token = int(payload["first"])
            # the prefill-produced first token spends 1 of the slot's
            # budget; credit it at this boundary's scheduler step
            self._first_credit[slot] = 1
        finished = []
        with _tracer.span("llm.decode", parent=root):
            results = self.decoder.step()
        if results:
            self._c_steps.inc(1, service=self.service)
        tokens_by_slot = dict(self._first_credit)
        self._first_credit = {}
        for slot, (toks, n_acc) in results.items():
            seq_id = self.decoder.seq_ids[slot]
            meta = self._meta[seq_id]
            meta.generated.extend(toks)
            meta.decode_steps += 1
            tokens_by_slot[slot] = tokens_by_slot.get(slot, 0) \
                + len(toks)
            self._c_tokens.inc(len(toks), service=self.service)
            if self.decoder.spec_k:
                self._spec_acc[0] += n_acc
                self._spec_acc[1] += self.decoder.spec_k
                rejected = self.decoder.spec_k - n_acc
                if rejected > 0:
                    self._c_spec_rejected.inc(rejected,
                                              service=self.service)
        if self._spec_acc[1]:
            self._g_accept.set(self._spec_acc[0] / self._spec_acc[1],
                               service=self.service)
        active = self.sched.active_slots
        if active:
            # sequences still in prefill/handoff hold scheduler slots
            # but committed nothing this step
            for slot in active:
                tokens_by_slot.setdefault(slot, 0)
            for seq_id, slot in self.sched.step(tokens_by_slot):
                if self.decoder.active[slot] and \
                        self.decoder.seq_ids[slot] == seq_id:
                    self.decoder.deactivate(slot)
                finished.append((seq_id, self._finish(seq_id)))
        return finished

    def _run_prefill(self) -> None:
        ready = []
        still_stalled = []
        for a in self._to_prefill:
            try:
                h = self.kv.allocate(a.seq_id, a.prompt)
            except OutOfBlocks:
                # pool saturated: the slot idles (0-token step entries)
                # until decode completions release blocks
                still_stalled.append(a)
                continue
            meta = self._meta[a.seq_id]
            meta.slot = a.slot
            meta.reused_tokens = h.reused_tokens
            ready.append(a)
        self._to_prefill = still_stalled
        if not ready:
            return
        firsts = self.prefiller.prefill(
            [(a.seq_id, a.prompt) for a in ready])
        now = self.clock()
        for a in ready:
            first, suffix_len = firsts[a.seq_id]
            meta = self._meta[a.seq_id]
            meta.t_first = now
            meta.prefill_tokens = suffix_len
            self._h_ttft.observe(
                now - meta.t_submit, service=self.service,
                reuse="warm" if meta.reused_tokens else "cold")
            self.handoff.push({
                "seq": self.kv.export_seq(a.seq_id),
                "first": first,
                "max_new_tokens": a.max_new_tokens,
            })

    def _finish(self, seq_id) -> np.ndarray:
        meta = self._meta.pop(seq_id)
        self.kv.release(seq_id)
        total_len = min(len(meta.prompt) + 1 + len(meta.generated),
                        len(meta.prompt) + meta.max_new_tokens)
        a_flops, a_bytes = cost_attribution.service_cost(self.service)
        feature_log.record(
            service=self.service, route="decode",
            batch=self.decoder.slots,
            bucket=_bucket_window(len(meta.prompt)),
            queue_depth=self.sched.pending_count,
            decode_steps=meta.decode_steps,
            prefill_tokens=meta.prefill_tokens,
            context_blocks=-(-total_len // self.block_len),
            execute_ms=(self.clock() - meta.t_submit) * 1e3,
            analytic_flops=a_flops, analytic_bytes=a_bytes)
        # prompt + [prefill's first token] + decode commits, trimmed to
        # the budget (a final speculative burst can overshoot by 0 —
        # the decode step clamps — but trim defensively anyway)
        full = np.concatenate([meta.prompt, [meta.first_token],
                               meta.generated]).astype(np.int32)
        return full[:len(meta.prompt) + meta.max_new_tokens]

    # -- warmup / acceptance -----------------------------------------------
    def warm(self, prefill_windows=(1,), mark_steady: bool = True
             ) -> dict:
        """Precompile both phases (prefill for every window a suffix of
        each given length is fed through — its bucket, or
        ``max_window`` chunks plus the remainder's; the decode step)
        and optionally declare CompileTracker steady state. Returns the
        union of both executors' AOT fingerprints."""
        self.prefiller.warm(prefill_windows)
        self.decoder.warm()
        if mark_steady:
            compile_tracker.mark_steady()
        return {**self.prefiller.aot_fingerprints(),
                **self.decoder.aot_fingerprints()}

    def run_until_drained(self) -> dict:
        """Step until every submitted sequence completes or expires;
        returns ``seq_id -> [prompt + generated] int32 array``."""
        stalled = 0
        while self.sched.busy or self._to_prefill or len(self.handoff):
            before = len(self._done)
            for seq_id, toks in self.step():
                self._done[seq_id] = toks
            # deadlock guard: prefill permanently out of blocks with no
            # in-flight decode to release any is unrecoverable
            if len(self._done) == before and self._to_prefill and \
                    not self.decoder.active.any() and \
                    not len(self.handoff):
                stalled += 1
                if stalled > 3:
                    raise OutOfBlocks(
                        f"{len(self._to_prefill)} sequence(s) cannot "
                        "allocate KV blocks and no in-flight decode "
                        "can release any — the pool is too small for "
                        "this workload")
            else:
                stalled = 0
        out, self._done = self._done, {}
        return out
