"""Whole-pipeline XLA compilation: fuse traceable stage runs into single
jitted/pjit'd computations.

Why: when a pipeline executes stage by stage, host↔device round trips
BETWEEN pipeline stages, not compute, can dominate end-to-end latency.
Following the Julia-to-TPU full-program compilation approach
(arXiv:1810.09868) and TVM's end-to-end operator fusion
(arXiv:1802.04799), a ``PipelineModel`` of featurize → model → postproc
should lower to ONE XLA computation with donated intermediate buffers,
not one dispatch (plus a host materialization) per stage.

How: :func:`compile_pipeline` walks the stage list with an example
frame, asking each stage :meth:`~.pipeline.Transformer.supports_trace`
for the frame's schema at that point (schema propagation runs the
example eagerly — grouping needs every stage's OUTPUT schema). Maximal
runs of traceable stages become :class:`FusedSegment`\\ s — a single
``parallel.compat.jit`` call (CompileTracker-wired, so retraces land on
the scrape) over a dict of column arrays, with the input dict donated
so XLA reuses inter-stage buffers. Host-bound stages (HTTP, VW,
tokenizer string loops) split the run and execute eagerly, exactly as
today. ``graftcheck``'s ``analysis/traceability.json`` is the work-list
this consumes: every stage it flips TRACEABLE grows the fused spans.

Sharded pipelines fuse too: pass ``mesh`` + partition rules (the
``parallel/partition.py`` rule→``PartitionSpec`` engine, matched over
column names) and segments compile with ``in_shardings`` pinned.

Import is JAX-free and segments build their jitted callable lazily on
first execution. Plan construction over a pipeline with traceable
stages DOES touch the backend — schema propagation runs each stage's
``_trace`` eagerly on the example columns, a handful of tiny eager jnp
ops. Only an all-host plan (the no-JAX CI smoke's case) compiles
without jax in the process.
"""

from __future__ import annotations

import logging

import numpy as np

from .dataframe import DataFrame, jittable_dtype as jittable
from .pipeline import PipelineModel, Transformer

_LOG = logging.getLogger("mmlspark_tpu.core.compile")


def _registry():
    from ..obs.metrics import registry
    return registry


def trace_columns(df: DataFrame) -> dict:
    """The numeric column dict a fused segment operates on."""
    return {c: df[c] for c in df.columns if jittable(df[c].dtype)}


class _EagerStage:
    """Plan item: a host-bound stage (or raw ``df -> df`` callable)
    executed exactly as the un-compiled pipeline would."""

    __slots__ = ("stage", "name")

    def __init__(self, stage):
        self.stage = stage
        self.name = type(stage).__name__

    def run(self, df: DataFrame, profiler=None) -> DataFrame:
        fn = getattr(self.stage, "transform", None) or self.stage
        if profiler is None:
            return fn(df)
        with profiler.step(self.name) as h:
            return h.done(fn(df))


class FusedSegment:
    """Plan item: a maximal run of traceable stages lowered into ONE
    jitted computation over the frame's numeric columns.

    The jitted callable is built lazily on first run (plan construction
    stays JAX-free) through ``parallel.compat.jit`` so every retrace is
    counted by the obs :class:`~..obs.profile.CompileTracker` under
    this segment's name. Input columns that survive to the segment's
    output are donated (a dropped column's buffer cannot alias an
    output, so donating it would only earn jax's unusable-donation
    warning): device-resident survivors are reclaimed for the outputs,
    host numpy columns stream in during jit argument processing.

    A segment that fails at trace or execution time (a shape the static
    contract could not foresee — e.g. a mini-batcher hitting a
    non-divisible row count) falls back to eager per-stage execution
    for that call, counted in ``pipeline_fused_fallback_total``.
    """

    def __init__(self, stages, name: str, donate: bool = True,
                 mesh=None, rules=None, expected_host=frozenset(),
                 no_donate=frozenset()):
        self.stages = list(stages)
        self.name = name
        self.donate = donate
        self.mesh = mesh
        self.rules = rules
        # host (non-jittable) column names the EXAMPLE frame carried at
        # segment entry: the compile-time grouping contracts were
        # checked against exactly this set, so a runtime frame with a
        # different host-column set voids them (run() re-checks)
        self.expected_host = frozenset(expected_host)
        # input columns the segment DROPS (per the example propagation):
        # their buffers cannot alias any output, so donating them only
        # earns jax's unusable-donation warning — they ride the
        # non-donated argument instead
        self.no_donate = frozenset(no_donate)
        self._fn = None
        # per-bucket AOT executables: arg_sig -> jax.stages.Compiled
        # (store-loaded or backfilled); None = bucket known ineligible.
        # Filled by core.aot warm loading and the first-call store
        # lookup; absent sigs fall through to the plain jit path.
        self._exes: dict = {}
        reg = _registry()
        self._c_calls = reg.counter(
            "pipeline_fused_calls_total",
            "fused-segment executions, by segment")
        self._c_fallback = reg.counter(
            "pipeline_fused_fallback_total",
            "fused-segment calls that fell back to eager execution")

    # -- lazy jit ----------------------------------------------------------
    def _body(self, donated: dict, dropped: dict) -> dict:
        cols = dict(donated)
        cols.update(dropped)
        for stage in self.stages:
            cols = stage._trace(cols)
        return cols

    def _split(self, num: dict) -> tuple[dict, dict]:
        """Columns the segment's outputs can alias vs columns it drops
        (only the former are donated — no unusable-donation warnings)."""
        donated = {c: v for c, v in num.items() if c not in self.no_donate}
        dropped = {c: v for c, v in num.items() if c in self.no_donate}
        return donated, dropped

    def _ensure_fn(self, donated: dict, dropped: dict):
        if self._fn is not None:
            return self._fn
        from ..parallel import compat
        kwargs = {}
        if self.donate:
            # surviving columns only (see _split): host numpy inputs
            # donate silently (jax owns the transfer buffer),
            # device-resident inputs are genuinely reclaimed for the
            # segment's outputs
            kwargs["donate_argnums"] = (0,)
        if self.mesh is not None and self.rules is not None:
            from ..parallel.partition import (match_partition_rules,
                                              to_shardings)
            kwargs["in_shardings"] = tuple(
                to_shardings(self.mesh, cols,
                             match_partition_rules(self.rules, cols))
                for cols in (donated, dropped))
        self._fn = compat.jit(self._body, name=self.name, **kwargs)
        return self._fn

    def _aot_executable(self, donated: dict, dropped: dict):
        """The ahead-of-time path: a warm-loaded (or store-resident)
        executable for THIS bucket, or None → plain jit. A store miss
        compiles-and-backfills inside the store (loud counters), so a
        fresh process only ever pays each bucket's compile once across
        the whole fleet's lifetime. Failures degrade to the jit path —
        AOT is an accelerator, never a correctness gate."""
        from . import aot
        store = aot.active_store()
        if store is None and not self._exes:
            return None
        sig = aot.arg_sig(donated, dropped)
        if sig in self._exes:
            return self._exes[sig]
        if store is None:
            return None
        try:
            exe = store.load_or_compile(self, donated, dropped)
        except Exception:
            _LOG.warning("aot lookup failed for segment %s; using the "
                         "runtime jit path", self.name, exc_info=True)
            exe = None
        self._exes[sig] = exe
        return exe

    # -- execution ---------------------------------------------------------
    def _eager(self, df: DataFrame) -> DataFrame:
        self._c_fallback.inc(1, segment=self.name)
        cur = df
        for stage in self.stages:
            cur = stage.transform(cur)
        return cur

    def run(self, df: DataFrame, profiler=None) -> DataFrame:
        import jax
        num = trace_columns(df)
        carry = [(c, df[c]) for c in df.columns if c not in num]
        if {c for c, _ in carry} != self.expected_host:
            # the compile-time grouping contracts (row-change veto,
            # drop/select/rename completeness) were checked against the
            # EXAMPLE's host columns; this frame carries a different
            # host-column set, so the traced forms — which never see
            # host columns — could silently diverge from eager
            # semantics (a reshaped frame mis-aligning a carried
            # column, a SelectColumns leaking one). Eager is the
            # reference behavior; run it.
            _LOG.warning("fused segment %s: host columns %s differ "
                         "from the compile example's %s, running "
                         "eagerly", self.name,
                         sorted(c for c, _ in carry),
                         sorted(self.expected_host))
            return self._eager(df)
        # host columns go into the jitted call as-is: jax transfers them
        # during argument processing, which is measurably cheaper than a
        # Python-level jnp.asarray pass per column first
        donated, dropped = self._split(num)
        fn = self._aot_executable(donated, dropped) \
            or self._ensure_fn(donated, dropped)
        try:
            if profiler is None:
                out = fn(donated, dropped)
            else:
                # the single dispatch this segment replaced N per-stage
                # dispatches with — StepProfiler splits it into host-
                # dispatch vs device-execute via the block_until_ready
                # delta, attributed to THIS segment
                with profiler.step(self.name) as h:
                    out = h.done(fn(donated, dropped))
            # ONE batched device→host transfer for the whole segment
            # output; merging stays inside the fallback boundary so a
            # shape the static contract could not foresee degrades to
            # eager execution instead of a corrupt frame
            merged = _merge_traced(df, jax.device_get(out), carry,
                                   self.stages)
        except Exception:
            _LOG.warning("fused segment %s fell back to eager "
                         "execution", self.name, exc_info=True)
            return self._eager(df)
        self._c_calls.inc(1, segment=self.name)
        return merged

    def run_sharded(self, columns: dict) -> dict:
        """Execute the fused body on already-GLOBAL device arrays and
        return device outputs — the pod serving path.

        ``run()`` is host-mediated: numpy in, ``jax.device_get`` out.
        On a multi-process mesh both ends break — no single process
        holds a full row batch, and ``device_get`` on a non-fully-
        addressable array raises. Here the caller feeds global arrays
        (``parallel.feed_process_local`` / ``compat
        .make_array_from_process_local_data``) whose rows live on
        different hosts, every process executes the same program, and
        outputs stay sharded on device; gather explicitly via
        ``compat.process_allgather(..., tiled=True)`` when a host copy
        is wanted. No eager fallback: eager stage-by-stage transforms
        are host numpy code and cannot run on a sharded batch, so
        errors propagate.
        """
        donated, dropped = self._split(dict(columns))
        fn = self._aot_executable(donated, dropped) \
            or self._ensure_fn(donated, dropped)
        if self.mesh is not None:
            with self.mesh:
                out = fn(donated, dropped)
        else:
            out = fn(donated, dropped)
        self._c_calls.inc(1, segment=self.name)
        return out


def _merge_traced(df: DataFrame, out: dict, carry,
                  stages) -> DataFrame:
    """Traced output columns + host-carried columns → DataFrame. This
    is THE host materialization point of the whole segment (one sync,
    not one per stage — ``FusedSegment.run`` hands ``out`` through a
    single batched ``jax.device_get``, so the np.asarray below is a
    no-op there; the compile-time schema-propagation path still
    materializes here); column order follows the input frame,
    renamed/new columns append in ``_trace`` output order. Host
    metadata hooks (partition counts, column metadata) apply last."""
    host = {c: np.asarray(v) for c, v in out.items()}
    data: dict[str, np.ndarray] = {}
    carried = dict(carry)
    for c in df.columns:
        if c in host:
            data[c] = host.pop(c)
        elif c in carried:
            data[c] = carried[c]
    data.update(host)
    # DataFrame.__new__ below skips __init__'s validation — re-check the
    # one invariant that matters so a row-count mismatch (traced columns
    # reshaped, a carried column not) raises into the eager fallback
    # instead of building a silently mis-aligned frame
    lengths = {len(v) for v in data.values()}
    if len(lengths) > 1:
        raise ValueError(
            f"fused segment produced ragged column lengths {lengths}")
    new = DataFrame.__new__(DataFrame)
    new._data = data
    new.num_partitions = df.num_partitions
    for stage in stages:
        hooked = stage._post_host(new)
        # explicit None check: a 0-row DataFrame is falsy, and the
        # hook's result (metadata attach, repartition) must not be
        # dropped on legitimately empty runtime frames
        if hooked is not None:
            new = hooked
    return new


class CompiledPipeline:
    """A lowered pipeline: an ordered plan of :class:`FusedSegment` and
    :class:`_EagerStage` items. Duck-types a Transformer (``transform``
    / ``__call__``), so it drops into ``ServingQuery``, the serving
    DSL, or anywhere a stage fits."""

    def __init__(self, plan, service: str = "pipeline"):
        self.plan = list(plan)
        self.service = service

    # -- introspection -----------------------------------------------------
    @property
    def compiled_segments(self) -> int:
        """Fused-segment count — the dispatch count per call for the
        traced portion (FeatureLog records this per served request)."""
        return sum(1 for p in self.plan if isinstance(p, FusedSegment))

    @property
    def fused_stages(self) -> int:
        return sum(len(p.stages) for p in self.plan
                   if isinstance(p, FusedSegment))

    @property
    def eager_stages(self) -> int:
        return sum(1 for p in self.plan if isinstance(p, _EagerStage))

    def describe(self) -> list[dict]:
        """Human/bench-readable plan: one dict per item."""
        out = []
        for p in self.plan:
            if isinstance(p, FusedSegment):
                out.append({"kind": "fused", "segment": p.name,
                            "stages": [type(s).__name__
                                       for s in p.stages]})
            else:
                out.append({"kind": "eager", "stage": p.name})
        return out

    def warm_aot(self, store=None) -> int:
        """Preload every store-resident executable for this plan's
        fused segments (the scale-up warm boot — see ``core/aot.py``
        and ``docs/aot.md``). Returns executables loaded; 0 when no
        store is installed/on disk."""
        from . import aot
        if store is not None:
            aot.install(store)
        loaded = aot.maybe_warm(self, service=self.service)
        if loaded:
            # HBM watermark after the warm boot (obs.memory): what
            # preloading the executable store cost in device memory,
            # scrapeable as mem_event_watermark_bytes{event="aot_warm"}
            from ..obs.memory import memory_profiler
            memory_profiler.note_event("aot_warm")
        self.attribute_costs()
        return loaded

    def attribute_costs(self) -> int:
        """Export the roofline placement of every RESIDENT executable
        (obs.attribution): store-warmed entries already re-exported
        their persisted meta.json pair, so this pass covers what they
        cannot — runtime-backfilled buckets and live Compiled objects
        whose analysis never hit disk. Programs a backend refuses to
        analyze are counted (``profile_cost_analysis_missing_total``),
        never raised. Returns programs attributed."""
        from ..obs.attribution import cost_attribution
        n = 0
        for item in self.plan:
            if not isinstance(item, FusedSegment):
                continue
            for exe in item._exes.values():
                if exe is None:
                    continue
                if cost_attribution.record_compiled(
                        item.name, exe,
                        service=item.name.split(":", 1)[0]) is not None:
                    n += 1
                    break  # one bucket prices the segment's program
        return n

    # -- execution ---------------------------------------------------------
    def transform(self, df: DataFrame) -> DataFrame:
        from ..obs.profile import pipeline_profiler
        prof = pipeline_profiler()
        cur = df
        for item in self.plan:
            cur = item.run(cur, profiler=prof)
        return cur

    def __call__(self, df: DataFrame) -> DataFrame:
        return self.transform(df)


def compile_pipeline(model_or_stages, example_df: DataFrame, *,
                     mesh=None, rules=None, donate: bool = True,
                     service: str = "pipeline") -> CompiledPipeline:
    """Lower a ``PipelineModel`` (or stage list) into a
    :class:`CompiledPipeline`.

    Walks the stages with ``example_df``, greedily grouping maximal
    runs of stages whose :meth:`supports_trace` accepts the schema AT
    THAT POINT in the pipeline (the example is transformed eagerly once
    to propagate schemas). Stages whose ``_trace`` changes the row
    count only join a segment when every column is numeric — a
    host-carried string column cannot be re-attached to a reshaped
    frame. An all-host pipeline degrades to today's per-stage behavior
    exactly (plan of eager items, zero segments).
    """
    if isinstance(model_or_stages, PipelineModel):
        stages = list(model_or_stages.getOrDefault("stages"))
    else:
        stages = list(model_or_stages)
    plan: list = []
    run: list = []
    run_host: frozenset = frozenset()
    run_entry_cols: dict = {}
    seg_idx = 0
    cur = example_df

    def flush():
        nonlocal seg_idx, run
        if not run:
            return
        # only an entry column that reaches the segment output with the
        # SAME shape and dtype can alias an output buffer — anything
        # dropped, renamed, or reshaped (mini-batchers) is excluded
        # from donation (donating it would only earn jax's
        # unusable-donation warning)
        exit_cols = {c: (v.shape, v.dtype)
                     for c, v in trace_columns(cur).items()}
        plan.append(FusedSegment(
            run, f"{service}:seg{seg_idx}", donate=donate,
            mesh=mesh, rules=rules, expected_host=run_host,
            no_donate=frozenset(
                c for c, sig in run_entry_cols.items()
                if exit_cols.get(c) != sig)))
        seg_idx += 1
        run = []

    for stage in stages:
        ok = isinstance(stage, Transformer) and \
            stage.supports_trace(cur.schema, cur.num_rows)
        if ok and getattr(stage, "_trace_changes_rows", False):
            # row-count-changing stages need the WHOLE frame in the
            # traced dict; any host-carried column vetoes fusion here
            ok = all(jittable(dt) for dt, _ in cur.schema.values())
        if ok:
            if not run:
                # the host-column set the grouping contracts are being
                # checked against — run() re-validates it per call —
                # and the numeric entry set the donation split needs
                run_host = frozenset(
                    c for c, (dt, _) in cur.schema.items()
                    if not jittable(dt))
                run_entry_cols = {c: (v.shape, v.dtype)
                                  for c, v in trace_columns(cur).items()}
            run.append(stage)
            # propagate the example through the TRACED form (run
            # eagerly on the example columns): the fused layout — e.g.
            # a mini-batcher's [nb, size] numeric output vs its eager
            # object cells — is what the next stage's contract check
            # must see
            num = trace_columns(cur)
            carry = [(c, cur[c]) for c in cur.columns if c not in num]
            cur = _merge_traced(cur, stage._trace(num), carry, [stage])
        else:
            flush()
            plan.append(_EagerStage(stage))
            cur = (stage.transform(cur) if hasattr(stage, "transform")
                   else stage(cur))
    flush()
    return CompiledPipeline(plan, service=service)
