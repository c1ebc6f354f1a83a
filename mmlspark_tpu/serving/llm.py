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
executors with separate padding buckets, stitched together by a handoff
of (sequence, block chain) over the paged KV pool (``dl.paged_kv``);
what they share is the model's weights, so where both have work at a
boundary their rows go through ONE AOT-fingerprinted program
(:class:`_Programs` builds every program: a walk over the decoding rows,
a prefill window, or both):

- :class:`PrefillExecutor` fills KV blocks in padding-bucketed windows,
  starting AFTER any prefix-reused blocks — a warm prompt skips exactly
  the prefill the cache already holds, which is the TTFT win the bench
  measures. While enough slots decode, a prompt's next window RIDES in
  the decode step's program, one window a boundary, so the weights are
  read once a boundary and no decoding slot waits for a prefill program;
  with nothing to ride with (set-up, a cold start) its windows run in
  programs of their own, back to back.
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
(``module.apply({"params": ...}, windows, pools, method="walk") ->
(hidden, pools, counts)``, ``windows`` a tuple of ``(toks [S, w], rows,
pos, valid[, state rows])`` — the decode step's ``[S, 1]``, a prefill
window's ``[P, w]``, or both — and ``hidden`` each window's ``[S, w,
width]`` rows after the last block, NO head; ``several_windows`` says
whether the walk takes more than one) and for the head over the rows it names
(``module.apply({"params": ...}, hidden_rows, method="logits") ->
[..., V]``); with them ``max_window()``, ``program_key()`` and
``walk_stats``, the names of the counts. Logits exist only for rows a
token is sampled from, and each caller says which from the shapes it
already holds: a prefill program asks for ONE row a prompt (the last
prompt row of the chunk in which the prompt ends) and for none in a
chunk where no prompt ends; the decode step for its one row a slot, and
in the same call for one row a prompt of a window that rides with it; the
speculative verify for all ``k + 1`` rows of its window. The model's
type picks the path — ``dl.MaskedLMModel`` (per-head k and v pools),
``dl.LatentMoEDecoder`` (one latent array a layer, dropless experts) or
``dl.SparseLinearDecoder`` (grouped-query k and v pools with compressed
keys beside them, blocks chosen inside paged attention, and a recurrent
state a sequence in the lightning layers) — and no flag does.

A decoder that caches arrays a SEQUENCE gets rows for them from the same
block manager (``PagedKVManager(state_slots=...)``; the pools of all
three kinds are ONE pytree, donated to every program alike), each window
of its walk ends in the slots' rows, and prefix
reuse goes by STATE SNAPSHOT: admission after a prefix hit copies the
snapshot's row into the sequence's row on the device (an
``llm.state_restore`` span under ``llm.prefill``, before the boundary's
program), a chunked prefill carries the row from chunk to chunk (from
boundary to boundary where the chunks ride), and a prompt that brings new
whole chunks is cut at its last one, where its state is copied into a
snapshot row that ``publish`` indexes with the blocks. Speculation is refused
beside such a decoder: a state cannot be rewound.

One boundary ahead: a plain step commits one token a runnable row
whatever the token is, so while enough rows decode for a window to ride
with them (and a device runs beside the host) the engine dispatches the
NEXT boundary's program before it fetches the last one's tokens. The
sampled tokens stay on the device between the two — every step program
takes the picks of the one before it and an index a row, ``last =
picks[src]``, or the host's ``last`` where the token is home — and what
the host does a boundary (commit, handoff, finish, the caller's refill,
admission, allocation, the window, the block tables) runs under a program
instead of between two (:class:`DecodeExecutor`, :meth:`LLMEngine.step`).
A token, a first token and a finished sequence are counted when they are
home. A speculative step, fewer decoding rows than a window rides with,
a decoder of one window a walk and the CPU backend keep every fetch at
its own boundary: no setting chooses.

Obs: every boundary is an ``llm.step`` span on the tracer's ring (its
``ahead`` attribute: the program went out before the fetch of the one
before it; ``gen_steps_ahead_total`` counts those beside
``gen_decode_steps_total``; ``program``: the name XLA and a device
trace have for the program it dispatched, less ``jit_``, which is where
to look on the trace's ``XLA Modules`` line for what this boundary ran;
``steps``: ``gen_decode_steps_total`` as the boundary left it) with
``llm.prefill`` (the prefill programs of a boundary with nothing to ride
with; the host's part of a riding window, whose real rows the root says
as ``ride_rows``) and ``llm.decode`` (the block tables, the step's one
program and the boundary's fetch, which is an ``llm.fetch`` span of its
own: what the host waits there is what the device still had to do, the
host's slack; its ``program`` is the one whose results it brings
home) children; a decoder's walk counts
land on the registry inside the step's one fetch (``<name>_total``
counters, ``*_max`` gauges: ``moe_pairs_held_total``,
``moe_pairs_absent_total``, ``moe_experts_touched_total``,
``moe_expert_load_max``; ``sparse_blocks_chosen_total``,
``sparse_blocks_in_chain_total``, ``sparse_dense_rows_total``);
``gen_ttft_seconds{reuse=cold|warm}``,
``gen_tokens_total``, ``gen_prefill_calls_total{head=row|none}``
(program calls that held a prefill window, by whether a prompt's first
token came of it), ``gen_prefill_rows_total{ride=decode|alone}`` (prompt
rows by how their window ran),
``gen_spec_accept_ratio``, ``gen_decode_steps_total``,
``gen_decode_attn_seconds{phase}`` (a program's seconds from its
dispatch, or the fetch before it, to its fetch: observed at the fetch)
here, the ``kv_*`` families
(``kv_state_*`` among them) in ``dl.paged_kv`` — all federated
fleet-wide and recorded by the telemetry history plane. Completions land FeatureLog rows with
``decode_steps``/``prefill_tokens``/``context_blocks`` so the cost
model prices the two phases separately and decode by resident context
(``perf.costmodel``, schema v5).
"""

from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass, field
from itertools import islice

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


def _device_beside_host() -> bool:
    """Whether a program runs on a processor of its own while the host
    goes on: an accelerator. On the CPU backend it takes the host's own
    cores, so there is nothing to hide the host's work under, and the
    engine keeps every fetch at the boundary that dispatched its program
    (:meth:`LLMEngine.step`)."""
    from ..utils.platform import target_platform
    return target_platform() != "cpu"


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


# ---------------------------------------------------------------- programs

@dataclass(eq=False)
class _Flight:
    """A program between its dispatch and its fetch."""
    program: str            # the name XLA has for it, less ``jit_``
    phase: str              # "decode" (a step, whatever rides in it) | "prefill"
    t0: float               # ``time.perf_counter`` at its dispatch
    made: dict | None = None        # what it made, on the device
    slots: np.ndarray | None = None     # the slots whose rows it decoded
    landed: dict | None = None  # seq_id -> (pick row, rows fed): prompts it ended


class _Programs:
    """The one builder of the engine's device programs. Every program is
    ONE walk of the decoder over a tuple of windows — the decoding rows
    (``[S, 1]``), a prefill window (``[P, w]``), or both — then the head,
    once, over the rows a token is sampled from (each decoding row, and
    each prompt's last row of the window) and one greedy pick.

    A program is keyed by ``(decode, window, head)``: ``(True, None,
    True)`` the decode step; ``(False, w, head)`` a prefill window alone
    (``head`` False: the program of a chunk in which no prompt ends, which
    runs no head at all); ``(True, w, True)`` the decode step with a
    prefill window RIDING in it, so that the weights are read once a
    boundary (the head comes free: decode reads it anyway). Every program
    takes ``(params, draft params, pools, draft pools, dec, win)``:
    ``dec`` the decode rows' ``(rows, last, ptr, end, active, prev,
    src[, state rows])`` or ``()`` — a row's input token is ``last`` where
    it is on the host (``src`` < 0) and ``prev[src]`` where it is a pick
    of the program before this one, which may still be running — ``win``
    the window's ``(rows, toks, pos, lens[, state rows])`` or ``()``; it
    returns the pools and a dict of what it made: ``tok``, every pick
    (``[S + P]`` of a step: the decoding rows', one token a runnable row,
    then the window's prompts'; ``[P]`` of a prefill alone), and the
    walk's ``counts``. With a draft model a prefill window also fills the
    DRAFT pools, and the decode step (``spec_k`` > 0) is
    ``dl.speculative``'s draft/verify per slot, which no window rides in
    and which takes no ``prev`` / ``src`` (what it commits,
    ``committed``/``n_new``/``n_acc``, is known from its fetch alone)."""

    def __init__(self, module, variables, kv: PagedKVManager,
                 pools: _PoolState, *, draft_module=None,
                 draft_variables=None, slots: int, batch: int,
                 max_blocks: int, spec_k: int = 0, pad_id: int = 0,
                 service: str = "llm", registry=None):
        if spec_k and draft_module is None:
            raise ValueError("spec_k > 0 needs a draft model")
        if spec_k and kv.state_slots:
            raise ValueError(
                "speculative decoding rewinds a slot by the tokens it "
                "rejects, and a per-sequence state cannot be rewound")
        self.module = module
        self.variables = variables
        self.draft_module = draft_module
        self.draft_variables = draft_variables
        self.kv = kv
        self.pools = pools
        self.slots = int(slots)
        self.batch = max(int(batch), 1)
        self.max_blocks = int(max_blocks)
        self.spec_k = int(spec_k)
        self.pad_id = int(pad_id)
        self.service = service
        reg = registry if registry is not None else _default_registry
        self._h_attn = reg.histogram(
            "gen_decode_attn_seconds",
            "a program's seconds from its dispatch, or from the fetch of "
            "the one before it where that is later, to its fetch: its "
            "device time where a device runs beside the host; by service "
            "and phase",
            buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1,
                     .25, .5, 1., 2.5))
        self._home = 0.0        # when the last fetch came home
        self.walk_stats = _WalkStats(module, reg, service)
        self.built: dict[tuple, object] = {}
        self._fps: dict[str, tuple[str, str]] = {}
        self._copy = None

    def name(self, decode: bool, w: int | None, head: bool = True) -> str:
        S, P, svc = self.slots, self.batch, self.service
        if w is None:
            return f"llm_decode_paged_{svc}_S{S}_k{self.spec_k}"
        if decode:
            return f"llm_step_{svc}_S{S}_w{w}_b{P}"
        return f"llm_prefill_{svc}_w{w}_b{P}" + ("" if head else "_nohead")

    def get(self, decode: bool, w: int | None, head: bool = True):
        key = (bool(decode), w, bool(head))
        prog = self.built.get(key)
        if prog is not None:
            return prog
        run = self._speculative() if decode and self.spec_k \
            else self._walk_program(*key)
        name = self.name(*key)
        prog = compile_tracker.jit(run, name=name, **_donate_pools_kwargs())
        self.built[key] = prog
        draft = self.draft_module
        fp = {"service": self.service, "attn": "paged",
              "max_blocks": self.max_blocks,
              "block_len": self.kv.block_len,
              "encoder": self.module.program_key(),
              "draft": None if draft is None else draft.program_key(),
              "versions": aot.runtime_versions()}
        if decode:
            fp.update(slots=self.slots, spec_k=self.spec_k)
        if w is not None:
            fp.update(window=w, batch=self.batch, head=bool(head))
        fp["phase"] = "decode" if w is None else \
            "step" if decode else "prefill"
        self._fps[name] = aot.fingerprints(fp, [], [])
        return prog

    def _walk_program(self, decode: bool, w: int | None, head: bool):
        import jax.numpy as jnp
        module, draft = self.module, self.draft_module
        pad_id, S, P = self.pad_id, self.slots, self.batch

        def run(params, dparams, pools_t, pools_d, dec, win):
            windows = []
            if decode:
                rows, last, ptr, _, active, prev, src, *srows = dec
                # a row's input token, where it lies: at ``src`` among the
                # picks of the program before this one, which may still
                # be running, or on the host (``src`` < 0: ``last``)
                last = jnp.where(src < 0, last, prev[jnp.maximum(src, 0)])
                windows.append((last[:, None], rows, ptr - 1,
                                active[:, None], *srows))
            if w is not None:
                rows, toks, pos, lens, *srows = win
                windows.append((toks, rows, pos,
                                jnp.arange(w)[None] < lens[:, None], *srows))
            hidden, pools_t, counts = module.apply(
                {"params": params}, tuple(windows), pools_t, method="walk")
            if draft is not None and w is not None:
                _, pools_d, _ = draft.apply(
                    {"params": dparams}, (windows[-1],), pools_d,
                    method="walk")
            out = {"counts": counts}
            picked = [hidden[0][:, 0]] if decode else []    # [S, W]
            if w is not None and head:
                # the head AFTER the pick: one row a prompt
                at = jnp.clip(lens - 1, 0, w - 1)
                picked.append(jnp.take_along_axis(
                    hidden[-1], at[:, None, None], axis=1)[:, 0])  # [P, W]
            if not picked:
                return pools_t, pools_d, out
            # the head ONCE, over every row a token is sampled from
            tok = _greedy(module.apply(
                {"params": params}, jnp.concatenate(picked),
                method="logits"), pad_id)
            # every pick, one shape whatever rode: the decoding rows'
            # ``[:S]`` (one token a runnable row, known before any fetch),
            # then the window's prompts'
            out["tok"] = jnp.pad(tok, (0, S + P - len(tok))) if decode \
                else tok
            return pools_t, pools_d, out

        return run

    def _speculative(self):
        import jax.numpy as jnp
        module, draft = self.module, self.draft_module
        pad_id, k, S = self.pad_id, self.spec_k, self.slots

        def run(params, dparams, pools_t, pools_d, dec, win):
            rows, last, ptr, end, active = dec
            pos = ptr - 1
            av = active[:, None]
            tok = last[:, None]                     # [S, 1]
            drafts = []
            for j in range(k):
                (hd,), pools_d, _ = draft.apply(
                    {"params": dparams}, ((tok, rows, pos + j, av),),
                    pools_d, method="walk")
                ld = draft.apply({"params": dparams}, hd, method="logits")
                tok = _greedy(ld[:, 0], pad_id)[:, None]
                drafts.append(tok[:, 0])
            # extra cache-fill step: d_k's kv, or the next round's
            # draft attends a zero hole after a full accept (same
            # fix as dl.speculative)
            _, pools_d, _ = draft.apply(
                {"params": dparams}, ((tok, rows, pos + k, av),), pools_d,
                method="walk")
            d = jnp.stack(drafts, 1)                # [S, k]
            window = jnp.concatenate([last[:, None], d], 1)
            (ht,), pools_t, counts = module.apply(
                {"params": params},
                ((window, rows, pos, av & jnp.ones((S, k + 1), bool)),),
                pools_t, method="walk")             # [S, k+1, W]
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
            return pools_t, pools_d, {
                "committed": committed, "n_new": n_new,
                "n_acc": jnp.where(active, n_acc, 0), "counts": counts}

        return run

    def aot_fingerprints(self) -> dict:
        """program name -> (static_fp, full_fp) for every program built
        so far — the identity a warmed worker advertises."""
        return dict(self._fps)

    def _args(self, dec, win) -> tuple:
        return (self.variables["params"],
                None if self.draft_module is None
                else self.draft_variables["params"],
                self.pools.target, self.pools.draft, dec, win)

    def call(self, dec: tuple = (), win: tuple = (), head: bool = True
             ) -> _Flight:
        """Dispatch the program of these windows over the shared pools;
        returns it in flight: what it made is still on the device."""
        w = win[1].shape[1] if win else None
        prog = self.get(bool(dec), w, head)
        flight = _Flight(prog.__name__, "decode" if dec else "prefill",
                         time.perf_counter())
        pools_t, pools_d, flight.made = prog(*self._args(dec, win))
        self.pools.target = pools_t
        if self.draft_module is not None:
            self.pools.draft = pools_d
        return flight

    def home(self, flights: list) -> None:
        """What these programs made has just been fetched, in one fetch:
        ``gen_decode_attn_seconds`` gets, once a program, an equal share
        of the seconds from the first one's dispatch — or from the fetch
        before this one, where the device was still busy with that — to
        now."""
        now = time.perf_counter()
        share = (now - max(flights[0].t0, self._home)) / len(flights)
        for flight in flights:
            self._h_attn.observe(share, service=self.service,
                                 phase=flight.phase)
        self._home = now

    def tokens_at_home(self) -> tuple:
        """The walk program's ``(prev, src)`` where every row's token is on
        the host: no pick to read, ``src`` -1 a row. The speculative step
        takes neither."""
        import jax.numpy as jnp
        if self.spec_k:
            return ()
        return (jnp.zeros(self.slots + self.batch, jnp.int32),
                jnp.full(self.slots, -1, jnp.int32))

    def blank(self, decode: bool, w: int | None) -> tuple:
        """``(dec, win)`` of a call that touches the trash block alone:
        every decode row inactive, every window row of length 0."""
        import jax.numpy as jnp
        S, P, MB = self.slots, self.batch, self.max_blocks
        state = bool(self.kv.state_slots)
        dec = (jnp.zeros((S, MB), jnp.int32), jnp.zeros(S, jnp.int32),
               jnp.ones(S, jnp.int32), jnp.full(S, 2, jnp.int32),
               jnp.zeros(S, bool), *self.tokens_at_home(),
               *((jnp.zeros(S, jnp.int32),) if state else ())) \
            if decode else ()
        win = (jnp.zeros((P, MB), jnp.int32), jnp.zeros((P, w), jnp.int32),
               jnp.zeros(P, jnp.int32), jnp.zeros(P, jnp.int32),
               *((jnp.zeros(P, jnp.int32),) if state else ())) \
            if w is not None else ()
        return dec, win

    def warm(self, decode: bool, w: int | None, head: bool = True) -> None:
        """Compile the program and run it once against the trash block —
        the warmup before ``mark_steady``."""
        dec, win = self.blank(decode, w)
        # a step with a window riding in it is the decode step's work and
        # that window's prefill program's, which are both attributed: the
        # service's cost is the sum over its programs
        if w is None or not decode:
            # attribution must lower BEFORE the call: donation
            # invalidates the pool buffers the args reference
            _attribute_warm(self.get(decode, w, head), self.service,
                            *self._args(dec, win))
        self.call(dec, win, head)

    # -- the per-sequence arrays' rows ----------------------------------------
    def copy_rows(self, pairs: list) -> None:
        """Rows of the per-sequence arrays copied onto other rows, at most
        ``batch`` ``(from, to)`` pairs a call (a snapshot restored, a
        snapshot taken), the pools donated like every program's."""
        import jax.numpy as jnp
        if self._copy is None:
            spec = self.module.cache_spec()
            self._copy = compile_tracker.jit(
                lambda pools, src, dst: copy_state_rows(spec, pools, src,
                                                        dst),
                name=f"llm_state_copy_{self.service}_b{self.batch}",
                **({"donate_argnums": (0,)} if _donate_pools_kwargs()
                   else {}))
        src = np.full(self.batch, TRASH_ROW, np.int32)
        dst = np.full(self.batch, TRASH_ROW, np.int32)
        for i, (a, b) in enumerate(pairs):
            src[i], dst[i] = a, b
        self.pools.target = self._copy(
            self.pools.target, jnp.asarray(src), jnp.asarray(dst))


# --------------------------------------------------------------- executors

@dataclass(eq=False)
class _Feed:
    """A prompt on its way into the cache: ``at`` is the next position to
    feed, kept on the host between the windows (and, for a prompt that
    rides in with the decoding rows, between the boundaries). With
    per-sequence cache arrays the feed is cut at ``cut``, the boundary the
    prompt's snapshot is taken at (``snap``: still to take)."""
    seq_id: object
    prompt: np.ndarray
    at: int
    fed_from: int
    cut: int
    snap: bool
    restore: tuple | None = None

    @property
    def stop(self) -> int:
        """Where the stretch being fed ends."""
        return self.cut if self.at < self.cut else len(self.prompt)


class PrefillExecutor:
    """Fills KV blocks for admitted prompts, a window of at most ``batch``
    prompts and ``max_window`` rows a program call, over the prompt SUFFIX
    (everything past the prefix-reused blocks) at per-row start positions
    — SCATTER-ONLY: each block's kv writes through the table as it is
    computed and attention reads the pools in place. The program picks
    each prompt's last row of the window out of the hidden rows, asks the
    decoder's ``logits`` for those rows alone and returns each row's first
    generated token (TTFT is measured here): no ``[P, w, V]`` value
    exists.

    :meth:`prefill` takes a window one of two ways, by what it observes.
    While slots DECODE (``rider``, the engine's :class:`DecodeExecutor`,
    has ``ride_from`` runnable slots or more) one window a boundary RIDES
    with the decoding rows in ONE program (``_Programs``' ``(True, w,
    True)``): the weights are read once, and the prompts' progress is
    kept here between boundaries; the decode executor's one fetch brings
    the first tokens back (:meth:`landed`). With too few rows to ride
    with (set-up, a cold start), beside a speculative decode step, or
    with a decoder that takes one window a walk (``rider`` None), the
    prompts run to their ends ALONE, window after
    window, as batches of ``batch`` in step with each other: a chunk in
    which no prompt of the batch ends goes through the program with no
    head, which is known on the host before the call
    (``gen_prefill_calls_total{head="row"|"none"}`` counts the window
    calls of both ways; ``gen_prefill_rows_total{ride="decode"|"alone"}``
    the prompt rows by how they ran). With a draft model the same window
    also fills the DRAFT pools, so prefix-reused blocks hold both models'
    kv consistently."""

    def __init__(self, programs: _Programs, *, registry=None):
        self.programs = programs
        self.kv = programs.kv
        self.pools = programs.pools
        self.max_blocks = programs.max_blocks
        self.batch = programs.batch
        self.service = programs.service
        # the widest window every model's walk takes: a longer suffix
        # is fed in chunks of this width
        self.max_window = min(
            m.max_window() for m in (programs.module,
                                     programs.draft_module)
            if m is not None)
        reg = registry if registry is not None else _default_registry
        self._c_calls = reg.counter(
            "gen_prefill_calls_total",
            "program calls that held a prefill window, by service and "
            "whether a prompt's first token was taken from the call: "
            "head=row | head=none")
        self._c_rows = reg.counter(
            "gen_prefill_rows_total",
            "prompt rows fed through prefill windows, by service and how "
            "the window ran: ride=decode with the decoding rows in one "
            "program, ride=alone in a program of its own")
        # a decoder with per-sequence cache arrays: its walk takes the
        # rows' state rows, and snapshots are copied row to row
        self.stateful = bool(self.kv.state_slots)
        #: the decode executor whose rows a window rides with; None where
        #: every window runs alone
        self.rider: DecodeExecutor | None = None
        #: a window rides where at least this many rows decode: the
        #: riding prompt holds its slot idle a boundary a window and one
        #: more, which the weights read once pay for from 6 to 8 decoding
        #: rows up at the benchmark's sizes (PERF.md section 6, PR 34)
        self.ride_from = 8
        self._queue: deque = deque()    # allocated, not wholly fed: FIFO
        self.rode = 0                   # rows of the last riding window

    @property
    def waiting(self) -> int:
        """Prompts whose chains are allocated and not wholly fed."""
        return len(self._queue)

    @property
    def rides(self) -> bool:
        """Whether enough rows decode at this boundary for a window to
        ride with them — and for the host's work to hide under their
        program (:meth:`LLMEngine.step` runs one boundary ahead by the
        same rule)."""
        rider = self.rider
        return rider is not None and rider.runnable.sum() >= self.ride_from

    # -- host driver --------------------------------------------------------
    def _chunks(self, n: int) -> list:
        """The rows of each program call a suffix of ``n`` tokens is fed
        through: whole ``max_window`` chunks, then the remainder."""
        full, rem = divmod(max(int(n), 1), self.max_window)
        return [self.max_window] * full + [rem] * bool(rem)

    def windows_for(self, n: int) -> list:
        """The window of each program call a suffix of ``n`` tokens is
        fed through alone: each chunk on its bucket. One entry for every
        suffix the kernel holds whole."""
        return [_bucket_window(k) if k < self.max_window else k
                for k in self._chunks(n)]

    def riding_window(self, n: int) -> int:
        """The window a chunk of ``n`` rows rides in: the next multiple of
        32. A window's padding rows go through the attention kernel like
        real ones (a latent-attention window of 128 padded rows costs the
        step 30 ms where 64 cost 9), so the ladder is finer than
        :func:`_bucket_window`'s and one fixed width is not it; a program
        of a width costs ``engine.warm`` a fraction of a second once the
        compile cache holds it (PERF.md section 6, PR 34)."""
        return min(-(-max(int(n), 1) // 32) * 32, self.max_window)

    def _feed(self, seq_id, prompt) -> _Feed:
        h = self.kv.handle(seq_id)
        # a fully reused prompt still re-feeds its last token: the window
        # must emit logits for the first generated position (the rewrite
        # stores bit-identical kv)
        s0 = min(h.reused_tokens, h.prompt_len - 1)
        return _Feed(
            seq_id, np.asarray(prompt), s0, s0,
            cut=h.prompt_len if h.snapshot_at is None else h.snapshot_at,
            snap=h.snapshot_at is not None,
            restore=None if h.restore_row is None
            else (h.restore_row, h.state_row))

    def _restore(self, feeds: list) -> None:
        """Copy the snapshot of its reused prefix into the state row of
        every feed that waits for one (on the device, before the window
        that feeds it)."""
        pairs = [f.restore for f in feeds if f.restore is not None]
        if not pairs:
            return
        with _tracer.span("llm.state_restore", rows=len(pairs)):
            self.programs.copy_rows(pairs)
        for f in feeds:
            if f.restore is not None:
                self.kv.restored(f.seq_id)
                f.restore = None

    def _snapshot(self, feeds: list) -> None:
        """Copy the state of every feed that has just reached its cut into
        a snapshot row (``publish`` indexes it with the blocks)."""
        taken = []
        for f in feeds:
            if f.snap and f.at == f.cut:
                f.snap = False
                row = self.kv.take_snapshot(f.seq_id)
                if row is not None:
                    taken.append((self.kv.handle(f.seq_id).state_row, row))
        if taken:
            self.programs.copy_rows(taken)

    def _window(self, feeds: list, width) -> tuple:
        """The next window of ``feeds`` (``batch`` entries, None a row
        that sits this window out): each feed's next chunk of its stretch,
        on the bucket ``width`` gives the longest. Moves the feeds on.
        Returns ``(win, ends, rows)``: the program's window argument,
        the rows whose prompt ends in it, the real rows it holds."""
        import jax.numpy as jnp
        P = self.batch
        spans = [0 if f is None else min(f.stop - f.at, self.max_window)
                 for f in feeds]
        w = width(max(spans))
        ids = [None if f is None else f.seq_id for f in feeds]
        # a row that sits out rides along like a padding row: position 0,
        # length 0, the trash row
        toks = np.zeros((P, w), np.int32)
        pos = np.zeros(P, np.int32)
        lens = np.zeros(P, np.int32)
        srows = np.full(P, TRASH_ROW, np.int32)
        state_rows = self.kv.state_rows(ids) if self.stateful else None
        ends = []
        for i, (f, k) in enumerate(zip(feeds, spans)):
            if k <= 0:
                continue
            toks[i, :k] = f.prompt[f.at:f.at + k]
            pos[i], lens[i] = f.at, k
            if self.stateful:
                srows[i] = state_rows[i]
            f.at += k
            # known on the host before the call: whose last token is in
            # this window
            if f.at == len(f.prompt):
                ends.append(i)
        win = (jnp.asarray(self.kv.block_rows(ids, self.max_blocks)),
               jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(lens),
               *((jnp.asarray(srows),) if self.stateful else ()))
        return win, ends, int(sum(spans))

    def _count(self, ends: list, rows: int, ride: str) -> None:
        self._c_calls.inc(1, service=self.service,
                          head="row" if ends else "none")
        self._c_rows.inc(rows, service=self.service, ride=ride)

    def _commit(self, feed: _Feed) -> int:
        """A prompt is wholly in (the program that holds its last row is
        dispatched; nothing here waits for a token): commit its length,
        index its new blocks (and its snapshot) for reuse. Returns the
        rows it was fed."""
        h = self.kv.handle(feed.seq_id)
        self.kv.advance(feed.seq_id, h.prompt_len - h.length)
        self.kv.publish(feed.seq_id)
        return len(feed.prompt) - feed.fed_from

    def prefill(self, jobs: list) -> dict:
        """``jobs``: list of ``(seq_id, prompt_tokens)`` whose chains
        have just been allocated in ``kv``; they join the prompts that
        wait. Returns ``seq_id -> (first_token, suffix_len)`` of the
        prompts that ran to their end ALONE in this call. While slots
        decode nothing runs alone: the next window of the oldest ``batch``
        prompts is left with the decode executor, whose step takes it
        through its one program; the prompts that end in it are that
        step's ``landed`` once the program is dispatched, and their first
        tokens its ``firsts`` once it is fetched. The prompts behind
        them wait their turn, a window a boundary.

        With per-sequence cache arrays a feed that reuses a prefix first
        has the prefix's snapshot copied into its state row (an
        ``llm.state_restore`` span; on the device), and a prompt that
        brings new whole chunks is fed in two stretches, cut at its last
        whole chunk (``snapshot_at``), where its state is copied into a
        snapshot row before the rest is fed."""
        self._queue.extend(self._feed(*job) for job in jobs)
        self.rode = 0
        if self.rides:
            # the prompts beyond the window wait their turn, a window a
            # boundary: a waiting prompt idles ONE slot, a prefill program
            # of its own would hold every decoding slot (chip sweep,
            # PERF.md section 6, PR 34)
            if self._queue:
                self._ride(self.rider)
            return {}
        out: dict = {}
        while self._queue:
            batch = [self._queue.popleft()
                     for _ in range(min(self.batch, len(self._queue)))]
            out.update(self._alone(batch))
        return out

    def _ride(self, rider: "DecodeExecutor") -> None:
        """The next window of the oldest ``batch`` prompts, handed to the
        decode executor to go through its step's program."""
        feeds = list(islice(self._queue, self.batch))
        self._restore(feeds)
        win, ends, rows = self._window(
            feeds + [None] * (self.batch - len(feeds)), self.riding_window)
        self._count(ends, rows, "decode")
        self.rode = rows
        ended = [(i, feeds[i]) for i in ends]
        for _, feed in ended:
            self._queue.remove(feed)

        def land() -> dict:
            """After the step's dispatch: a feed's cut falls between this
            boundary's program and the next one's, and the prompts that
            ended in the window are in. Their first tokens are picks of
            the program, wherever it is by now: ``seq_id -> (row among
            the picks, rows fed)``."""
            self._snapshot(feeds)
            return {feed.seq_id: (self.programs.slots + i,
                                  self._commit(feed))
                    for i, feed in ended}

        rider.riding = (win, land)

    def _alone(self, feeds: list) -> dict:
        """A batch of prompts to their ends, in step with each other: a
        suffix wider than ``max_window`` in consecutive chunks, each
        attending what the chunks before it wrote; ONE fetch brings the
        first tokens and the calls' counts."""
        import jax
        self._restore(feeds)
        padded = feeds + [None] * (self.batch - len(feeds))
        firsts: dict = {}
        flights: list = []

        def feed_while(live) -> None:
            while True:
                part = [f if f is not None and live(f) else None
                        for f in padded]
                if not any(f is not None for f in part):
                    return
                win, ends, rows = self._window(part, _bucket_window)
                self._count(ends, rows, "alone")
                flights.append(self.programs.call((), win, head=bool(ends)))
                for i in ends:
                    firsts[i] = flights[-1].made["tok"]

        feed_while(lambda f: f.at < f.cut)
        self._snapshot(feeds)
        feed_while(lambda f: f.at < len(f.prompt))
        firsts, counts = jax.device_get((
            firsts, [f.made["counts"] for f in flights]
            if self.programs.walk_stats else []))
        self.programs.home(flights)
        for count in counts:
            self.programs.walk_stats.record(count)
        return {f.seq_id: (int(firsts[i][i]), self._commit(f))
                for i, f in enumerate(feeds)}

    def warm(self, windows=(1,)) -> None:
        """Compile (and run, against the trash block only) the programs
        a suffix of each given length is fed through — the warmup sweep
        before ``compile_tracker.mark_steady()``. Alone, every window
        gets the program that emits (a prompt of the batch can end in any
        chunk); a chunk before the last is ``max_window`` wide and may
        hold no prompt's end, so a suffix of several chunks adds that
        window's program with no head. With a rider, each chunk's riding
        window besides."""
        kinds = set()
        for n in windows:
            chunks = self.windows_for(n)
            kinds.update((False, w, True) for w in chunks)
            kinds.update((False, w, False) for w in chunks[:-1])
            if self.rider is not None:
                kinds.update((True, self.riding_window(k), True)
                             for k in self._chunks(n))
        if self.stateful:               # trash row onto trash row
            self.programs.copy_rows([])
        for key in sorted(kinds):
            self.programs.warm(*key)


class DecodeExecutor:
    """The fixed-shape continuous-batching decode step over block
    tables. All shapes are pinned at construction — ``[slots]`` state
    vectors, ``[slots, max_blocks]`` block tables — so ONE program per
    mode serves every step (the zero-runtime-compile contract), and one
    more a riding window's width.

    Plain mode: ONE paged window walk of width 1 — embed the slots'
    last tokens, scatter kv through the table, paged attention over
    each chain in place — then the decoder's ``logits`` over its one
    row a slot and a greedy ``argmax`` with pad masked — the
    numerics of ``dl.generate``'s cached path with zero dense
    gathers. A prefill window the :class:`PrefillExecutor` leaves
    (``riding``) goes through the same walk and the same head call, and
    the program's one fetch brings its prompts' first tokens back with
    the slots'. Spec mode (draft present): ``dl.speculative``'s
    draft/verify runs as k width-1 draft walks plus one width-(k+1)
    target walk (the kernel's windowed variant) whose ``k + 1`` rows
    all get logits; each slot accepts its
    own longest agreeing prefix — no batch sync-on-min, block chains
    advance independently.

    ONE BOUNDARY AHEAD. A plain step commits one token a runnable row
    whatever the token is, so all a step's bookkeeping but the tokens'
    values is done at DISPATCH (``ptr``, the chains' lengths, who is
    runnable next, a riding window's snapshot, ``publish`` and the slot
    of the prompt that ended in it), and the next program reads its input
    tokens where the last one left them: ``src[s]`` is the row among the
    picks of the program in flight (``flying``) that slot ``s`` takes —
    its own ``s``, or ``S + i`` of the prompt that rode in window row
    ``i`` — and -1 once the token is home in ``last``. With ``ahead`` set
    (the engine says so a boundary: enough rows decode to hide the host
    under), :meth:`step` dispatches its program and fetches the one
    BEFORE it, so the device is never without work while the host commits,
    hands off, finishes, admits and builds the next tables; otherwise it
    fetches its own, as ever. Tokens, first tokens and finished sequences
    are counted when they are home, never at dispatch."""

    def __init__(self, programs: _Programs):
        self.programs = programs
        self.kv = programs.kv
        self.pools = programs.pools
        self.slots = programs.slots
        self.max_blocks = programs.max_blocks
        self.spec_k = programs.spec_k
        self.pad_id = programs.pad_id
        # host-side slot state (the engine owns seq metadata)
        self.seq_ids: list = [None] * self.slots
        self.ptr = np.ones(self.slots, np.int32)   # committed tokens
        self.end = np.ones(self.slots, np.int32)   # commit cap
        self.last = np.zeros(self.slots, np.int32)  # token @ ptr-1, at home
        self.active = np.zeros(self.slots, bool)
        #: where each slot's token @ ptr-1 lies among the picks of the
        #: program in flight; -1: at home, in ``last``
        self.src = np.full(self.slots, -1, np.int32)
        #: the program dispatched and not fetched
        self.flying: _Flight | None = None
        #: the program the last :meth:`step` dispatched; None: it had no
        #: runnable row
        self.sent: _Flight | None = None
        #: whether :meth:`step` leaves its program in flight and fetches
        #: the one before it (the engine sets it, a boundary at a time)
        self.ahead = False
        #: ``(window, land)`` the prefill executor left for the next step:
        #: the window goes through the step's program with the decoding
        #: rows, ``land`` is called once that program is dispatched
        self.riding: tuple | None = None
        #: of the last :meth:`step` / :meth:`fetch`: the prompts the
        #: dispatched window ended (``seq_id -> (pick row, rows fed)``)
        #: and the first tokens that came home (``seq_id -> token``)
        self.landed: dict = {}
        self.firsts: dict = {}
        self._at_home = programs.tokens_at_home()

    @property
    def free_slots(self) -> int:
        return int(self.slots - self.active.sum())

    # -- slot lifecycle -----------------------------------------------------
    def activate(self, slot_hint, state: dict,
                 first_row: int | None = None) -> int:
        """Adopt a handoff payload into a free slot. ``slot_hint`` (the
        scheduler's assignment) is used when free; any free slot
        otherwise. ``first_row``: the payload's first token is not home
        yet (``first`` None; such a payload never went over the wire) and
        lies at this row among the picks of the program in flight."""
        slot = slot_hint if (slot_hint is not None
                             and not self.active[slot_hint]) else \
            int(np.flatnonzero(~self.active)[0])
        handle = self.kv.adopt(state["seq"])
        self.seq_ids[slot] = handle.seq_id
        # cache holds [0, prompt_len); the first generated token (from
        # prefill) is committed at position prompt_len, pending embed
        self.ptr[slot] = handle.length + 1
        self.end[slot] = handle.length + int(state["max_new_tokens"])
        if first_row is None:
            self.last[slot] = int(state["first"])
        else:
            self.src[slot] = int(first_row)
        self.active[slot] = True
        return slot

    def deactivate(self, slot: int) -> None:
        self.seq_ids[slot] = None
        self.active[slot] = False
        self.ptr[slot] = 1
        self.end[slot] = 1
        self.last[slot] = self.pad_id
        self.src[slot] = -1

    @property
    def runnable(self) -> np.ndarray:
        """Slots that should actually decode this step: active AND
        budget remaining (a 1-token sequence is complete the moment its
        prefill-produced first token lands)."""
        return self.active & (self.ptr < self.end)

    # -- the step -----------------------------------------------------------
    def step(self) -> dict:
        """One decode step over every runnable slot, ONE program: the
        decode rows and, riding with them, the prefill window the
        :class:`PrefillExecutor` left (``riding``). Dispatches it, then
        fetches — with ``ahead`` the program dispatched BEFORE it, which
        this one followed onto the device, else its own — and returns
        what came home, ``slot -> (tokens_committed list, n_accepted)``;
        the caller commits the tokens and retires finished sequences."""
        import jax.numpy as jnp
        runnable = self.runnable
        (win, land), self.riding = self.riding or ((), None), None
        before = self.flying
        self.landed = {}
        self.sent = None
        if runnable.any():
            slots = np.flatnonzero(runnable)
            # capacity for this step's writes: positions up to ptr-1+k
            for s in slots:
                self.kv.ensure_capacity(self.seq_ids[s],
                                        int(self.ptr[s]) + self.spec_k)
            running = [sid if runnable[i] else None
                       for i, sid in enumerate(self.seq_ids)]
            # copies: the slot state moves on below while the program may
            # not have read its arguments yet (off the chip an uploaded
            # array can share the host's memory)
            picks = () if self.spec_k else (
                before.made["tok"] if before is not None
                else self._at_home[0], jnp.asarray(self.src.copy()))
            dec = (jnp.asarray(self.kv.block_rows(running, self.max_blocks)),
                   jnp.asarray(self.last.copy()),
                   jnp.asarray(self.ptr.copy()),
                   jnp.asarray(self.end.copy()), jnp.asarray(runnable),
                   *picks,
                   *((jnp.asarray(self.kv.state_rows(running)),)
                     if self.kv.state_slots else ()))
            self.sent = flight = self.programs.call(dec, win)
            if not self.spec_k:
                # one token a row, whatever it is: it lies at the row's
                # own pick until it is home
                self._advance(slots, 1)
                self.src[slots] = slots
            self.landed = land() if land is not None else {}
            flight.slots, flight.landed = slots, self.landed
            self.flying = flight
        if self.ahead:
            return self._fetch(before)
        assert before is None, "fetch() what flies before a step that waits"
        return self.fetch()

    def _advance(self, slots, n) -> None:
        for s, k in zip(slots, np.broadcast_to(n, len(slots))):
            self.kv.advance(self.seq_ids[s], int(k))
            self.ptr[s] += int(k)

    def fetch(self) -> dict:
        """Bring the program in flight home: its tokens as :meth:`step`
        returns them, nothing where none flies."""
        return self._fetch(self.flying)

    def _fetch(self, flight: _Flight | None) -> dict:
        """ONE fetch a program: the tokens, a riding window's first
        tokens and the walk's counts together (``llm.fetch``: what the
        host waits is what the device still had to do)."""
        import jax
        self.firsts = {}
        if flight is None:
            return {}
        with _tracer.span("llm.fetch", program=flight.program):
            made = jax.device_get(flight.made)
        self.programs.home([flight])
        if flight is self.flying:
            # nothing flies from here on: EVERY token comes home, that of
            # a slot handed a prompt this program ended too (it did not
            # decode in it, so it is in no ``out`` below, and the next
            # program reads it from ``last``)
            self.flying = None
            away = self.src >= 0
            if away.any():
                self.last[away] = made["tok"][self.src[away]]
                self.src[:] = -1
        if self.programs.walk_stats:
            self.programs.walk_stats.record(made["counts"])
        if self.spec_k:
            self._advance(flight.slots, made["n_new"][flight.slots])
            out = {int(s): ([int(t) for t in
                             made["committed"][s, :made["n_new"][s]]],
                            int(made["n_acc"][s])) for s in flight.slots}
        else:
            out = {int(s): ([int(made["tok"][s])], 1) for s in flight.slots}
            self.firsts = {seq_id: int(made["tok"][row])
                           for seq_id, (row, _) in flight.landed.items()}
        for s, (toks, _) in out.items():
            self.last[s] = toks[-1]
        return out

    def warm(self) -> None:
        """Run the step program once against the trash block (all slots
        inactive — every write lands in block 0) — the warmup before
        ``mark_steady``."""
        self.programs.warm(True, None)


# ------------------------------------------------------------------ engine

@dataclass
class _SeqMeta:
    prompt: np.ndarray
    max_new_tokens: int
    t_submit: float
    slot: int | None = None
    t_first: float | None = None
    first_token: int | None = None     # None until it is home
    handed: bool = False                # to its decode slot
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
        self.programs = _Programs(
            module, variables, self.kv, self.pools,
            draft_module=draft_module, draft_variables=draft_variables,
            slots=slots, batch=prefill_batch, max_blocks=self.max_blocks,
            spec_k=spec_k, pad_id=pad_id, service=service, registry=reg)
        self.prefiller = PrefillExecutor(self.programs, registry=reg)
        self.decoder = DecodeExecutor(self.programs)
        # a prompt's window rides with the decoding rows where the
        # decoder's walk takes several windows and another slot can be
        # decoding meanwhile; the speculative step's draft walks share
        # nothing with a prefill window
        if not spec_k and int(slots) > 1 and \
                getattr(module, "several_windows", False):
            self.prefiller.rider = self.decoder
        self.handoff = HandoffQueue()
        # one boundary ahead only where a device runs beside the host
        self._beside = _device_beside_host()
        self._meta: dict = {}
        self._to_prefill: list = []
        #: prompts wholly in whose first token still flies and that no
        #: slot has taken: ``seq_id -> pick row``, oldest first
        self._landing: dict = {}
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
        self._c_ahead = reg.counter(
            "gen_steps_ahead_total",
            "decode steps whose program was dispatched before the tokens "
            "of the step before were fetched, by service")
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
        """One boundary: admit, allocate the admitted prompts' chains,
        dispatch ONE program — the decoding rows and, riding with them,
        the next prefill window of the prompts that wait — fetch, commit
        what the fetch brought, and hand the prompts whose last row was
        in the window to their slots (they decode from the next boundary).

        While enough rows decode for a window to ride with them
        (``prefiller.rides``) the engine runs ONE BOUNDARY AHEAD: the
        fetch is of the program dispatched at the boundary BEFORE, which
        this boundary's program followed onto the device, so that
        everything the host does — this commit, the handoffs, the
        finishes, the caller's refill, the next boundary's admission,
        allocation, window and block tables — happens under a running
        program (:class:`DecodeExecutor`). Where no slot decodes or too
        few, beside a speculative step, or with a decoder that takes one
        window a walk, the order is prefill (every window of every ready
        prompt, alone) → handoff → decode → its own fetch, after whatever
        still flew has come home.

        Returns ``(seq_id, tokens)`` pairs (full sequence: prompt then
        generated) of the sequences whose last token has come HOME at
        this boundary — one boundary after the program that made it,
        while the engine runs ahead. A token counts as served
        (``gen_tokens_total``, ``gen_ttft_seconds``) when it is home."""
        with _tracer.span("llm.step") as root:
            return self._step(root)

    def _step(self, root) -> list:
        ahead = self._beside and self.prefiller.rides
        # a boundary that waits for its own program first brings home
        # the one that still flies
        finished = self._settle(self.decoder.fetch()) \
            if not ahead and self.decoder.flying is not None else []
        for a in self.sched.admit():
            self._to_prefill.append(a)
        for seq_id in self.sched.drain_expired():
            self._meta.pop(seq_id, None)
            self.expired.append(seq_id)
        jobs = self._allocate()
        if jobs or self.prefiller.waiting:
            # the host's part of a riding window; every window, every
            # program and the fetch of a prefill alone
            with _tracer.span("llm.prefill", parent=root):
                alone = self.prefiller.prefill(jobs)
                for seq_id, (first, _) in alone.items():
                    self._first_token(seq_id, first)
                self._hand_off({seq_id: (None, rows)
                                for seq_id, (_, rows) in alone.items()})
            root.set_attr("ride_rows", self.prefiller.rode)
        # a program goes out wherever a slot is runnable: ahead of the
        # fetch of the one that still flies
        stepping = bool(self.decoder.runnable.any())
        flew = stepping and self.decoder.flying is not None
        root.set_attr("ahead", flew)
        self.decoder.ahead = ahead
        with _tracer.span("llm.decode", parent=root):
            results = self.decoder.step()
        sent = self.decoder.sent
        if sent is not None:
            self._c_steps.inc(1, service=self.service)
            self._c_ahead.inc(int(flew), service=self.service)
            # what this boundary put on the device, by the name the device
            # trace has for it
            root.set_attr("program", sent.program)
        # ``gen_decode_steps_total`` as this boundary leaves it: where a
        # caller reads the counter a boundary, the ring's roots lie against
        # its record
        root.set_attr("steps", int(self._c_steps.value(service=self.service)))
        return finished + self._settle(results, self.decoder.landed)

    def _settle(self, results: dict, landed: dict | None = None) -> list:
        """What a fetch brought home — first tokens, then ``slot ->
        (tokens, n_accepted)`` — committed and counted; the prompts the
        program just dispatched ended (``landed``) handed to their slots;
        the sequences that are complete retired. Returns those."""
        for seq_id, first in self.decoder.firsts.items():
            self._first_token(seq_id, first)
        if landed is not None:
            self._hand_off(landed)
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
        finished = []
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

    def _allocate(self) -> list:
        """Chains for the admitted prompts the pool has blocks for:
        ``(seq_id, prompt)`` of each; the others wait for a boundary at
        which decode completions have released some."""
        ready, still_stalled = [], []
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
            ready.append((a.seq_id, a.prompt))
        self._to_prefill = still_stalled
        return ready

    def _first_token(self, seq_id, first: int) -> None:
        """A prompt's first token is home: TTFT, and 1 of its slot's
        budget."""
        meta = self._meta[seq_id]
        meta.t_first = self.clock()
        meta.first_token = int(first)
        self._h_ttft.observe(
            meta.t_first - meta.t_submit, service=self.service,
            reuse="warm" if meta.reused_tokens else "cold")
        if meta.handed:
            self._first_credit[meta.slot] = 1
        elif self._landing.pop(seq_id, None) is not None:
            # no slot took it while its token flew: by value from here on
            self.handoff.push(self._payload(seq_id))

    def _payload(self, seq_id) -> dict:
        meta = self._meta[seq_id]
        return {"seq": self.kv.export_seq(seq_id),
                "first": meta.first_token,
                "max_new_tokens": meta.max_new_tokens}

    def _hand_off(self, ready: dict) -> None:
        """Prompts that are wholly in, ``seq_id -> (pick row, rows
        fed)``, to their slots. One whose first token is home goes through
        the handoff queue, by value. One whose token still flies goes to
        a free slot by the token's ROW among the picks of the program in
        flight and never through the queue: a row means something on
        this process's device alone, and the wire carries tokens. With no
        slot free it waits here, and goes through the queue once its
        token is home (:meth:`_first_token`)."""
        for seq_id, (row, rows) in ready.items():
            meta = self._meta[seq_id]
            meta.prefill_tokens = rows
            # home already: a prefill alone, or a window that rode in a
            # program this boundary waited for
            if meta.first_token is not None:
                self.handoff.push(self._payload(seq_id))
            else:
                self._landing[seq_id] = row
        for payload in self.handoff.pull(self.decoder.free_slots):
            self._activate(payload)
        # the queue's are older: a landing prompt goes once it is empty
        while self._landing and self.decoder.free_slots \
                and not len(self.handoff):
            seq_id = next(iter(self._landing))
            self._activate(self._payload(seq_id), self._landing.pop(seq_id))

    def _activate(self, payload: dict, first_row: int | None = None) -> None:
        meta = self._meta[payload["seq"]["seq_id"]]
        meta.slot = self.decoder.activate(meta.slot, payload, first_row)
        meta.handed = True
        # the prefill-produced first token spends 1 of the slot's budget;
        # credit it at the scheduler step of the boundary at which it is
        # home
        if first_row is None:
            self._first_credit[meta.slot] = 1

    def _finish(self, seq_id) -> np.ndarray:
        meta = self._meta.pop(seq_id)
        # its blocks and its state row go to whoever is admitted next
        # while a program may still fly: that one was dispatched after the
        # program that wrote this sequence's last row, and whatever
        # touches the blocks again is dispatched after both — the one
        # stream of the device keeps the order of dispatch
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
        union of both executors' AOT fingerprints. A program in flight
        comes home first (the sequences it finishes come out of the next
        :meth:`run_until_drained`)."""
        if self.decoder.flying is not None:
            self._done.update(self._settle(self.decoder.fetch()))
        self.prefiller.warm(prefill_windows)
        self.decoder.warm()
        if mark_steady:
            compile_tracker.mark_steady()
        return self.programs.aot_fingerprints()

    def run_until_drained(self) -> dict:
        """Step until every submitted sequence completes or expires —
        nothing flies then: a sequence is complete when its last token is
        home; returns ``seq_id -> [prompt + generated] int32 array``."""
        stalled = 0
        while self.sched.busy or self._to_prefill or len(self.handoff):
            before = len(self._done)
            for seq_id, toks in self.step():
                self._done[seq_id] = toks
            # deadlock guard: prefill permanently out of blocks with no
            # in-flight decode to release any is unrecoverable
            if len(self._done) == before and self._to_prefill and \
                    not self.decoder.active.any() and \
                    not len(self.handoff) and not self.prefiller.waiting:
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
