"""Sharded training step for the DL path (transfer learning / fine-tune).

The reference has no in-framework DL training (CNTK models arrive
pretrained; ``ImageFeaturizer`` only extracts features, with the classifier
trained by SparkML — see call stack SURVEY §3.2). Because the TPU framework
runs models natively, fine-tuning is first-class: a jitted SPMD train step
over the full mesh, with

- batch sharded over ``dp`` (and ``sp`` for sequence models),
- wide parameter matrices sharded over ``tp`` (GSPMD inserts the
  collectives),
- gradient psum handled by jit itself via sharding propagation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs.tracing import tracer as _tracer
from ..parallel import compat as _compat


@dataclasses.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    step: Any

    def tree_flatten(self):  # pragma: no cover - pytree plumbing
        return ((self.params, self.batch_stats, self.opt_state, self.step),
                None)

    @classmethod
    def tree_unflatten(cls, _, leaves):  # pragma: no cover
        return cls(*leaves)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def param_spec(path: tuple, leaf, tp_size: int) -> P:
    """Tensor-parallel sharding rule: shard the output-channel (last) dim of
    large kernels over ``tp``; replicate everything else.

    Keeping small tensors replicated avoids collectives that cost more than
    they save — the scaling-book recipe: pick a mesh, annotate only the big
    matmuls, let XLA do the rest.
    """
    if leaf.ndim >= 2 and leaf.shape[-1] % tp_size == 0 \
            and leaf.shape[-1] >= 2 * tp_size and leaf.size >= 4096:
        return P(*([None] * (leaf.ndim - 1) + ["tp"]))
    return P()


def shard_train_state(state: TrainState, mesh) -> TrainState:
    """device_put a TrainState with tp-sharded params over a mesh."""
    tp = mesh.shape.get("tp", 1)

    def put(path, leaf):
        arr = jnp.asarray(leaf)
        spec = param_spec(path, arr, tp) if tp > 1 else P()
        return jax.device_put(arr, NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map_with_path(put, state.params)
    rest = jax.tree.map(
        lambda l: jax.device_put(jnp.asarray(l), NamedSharding(mesh, P())),
        (state.batch_stats, state.opt_state, state.step))
    return TrainState(params, rest[0], rest[1], rest[2])


def init_train_state(module, rng, sample_input, tx) -> TrainState:
    variables = module.init(rng, jnp.asarray(sample_input), True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(params=params, batch_stats=batch_stats,
                      opt_state=tx.init(params), step=jnp.zeros((), jnp.int32))


def softmax_xent(logits, labels):
    logp = jax.nn.log_softmax(logits)
    onehot = jax.nn.one_hot(labels, logits.shape[-1])
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def _make_loss_of(module, loss_fn: Callable, fetch: str):
    """(params, stats, imgs, lbls) → (loss, new_model_state): the ONE
    forward+loss body shared by the jitted single-device step and the
    pjit'd partitioned step — the numerical-equivalence contract
    between them is this function being literally the same code."""

    def loss_of(params, stats, imgs, lbls):
        variables = {"params": params}
        if stats:
            variables["batch_stats"] = stats
            outputs, new_model_state = module.apply(
                variables, imgs, True, mutable=["batch_stats"])
        else:
            # no mutable kwarg at all: flax returns (out, state) for
            # ANY list-valued mutable, including []
            outputs = module.apply(variables, imgs, True)
            new_model_state = {}
        logits = outputs[fetch] if isinstance(outputs, dict) else outputs
        return loss_fn(logits, lbls), new_model_state

    return loss_of


def make_train_step(module, tx, mesh=None,
                    loss_fn: Callable = softmax_xent,
                    fetch: str = "logits",
                    batch_axes: tuple[str, ...] = ("dp",),
                    accum_steps: int = 1):
    """Build a jitted SPMD train step: (state, images, labels) → (state,
    loss). With a mesh, inputs are constrained batch-sharded and params
    follow their placed shardings (GSPMD adds the gradient reductions).

    ``accum_steps > 1``: the batch splits into that many microbatches
    whose gradients average under one ``lax.scan`` before a single
    optimizer update — the large-effective-batch pattern when one
    microbatch is all HBM affords. The batch dimension must divide by
    ``accum_steps`` (and, with a mesh, each microbatch must still divide
    the batch axes — otherwise GSPMD has to gather the unshardable
    remainder). BatchNorm-style mutable stats take the LAST microbatch's
    update (running averages, not exact-batch stats)."""

    def step(state: TrainState, images, labels):
        if mesh is None:
            return _body(state, images, labels)
        # trace under the mesh context so block-boundary activation
        # constraints inside the MODEL (partition.constrain_activation)
        # resolve against this mesh instead of no-op'ing
        with mesh:
            return _body(state, images, labels)

    def _body(state: TrainState, images, labels):
        if mesh is not None:
            bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
            images = _compat.with_sharding_constraint(
                images, NamedSharding(mesh, P(*bspec)))
            labels = _compat.with_sharding_constraint(
                labels, NamedSharding(mesh, P(*bspec)))

        loss_of = _make_loss_of(module, loss_fn, fetch)
        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        if accum_steps <= 1:
            (loss, new_model_state), grads = grad_fn(
                state.params, state.batch_stats, images, labels)
        else:
            n = images.shape[0]
            if n % accum_steps:
                raise ValueError(
                    f"batch size {n} must divide by accum_steps="
                    f"{accum_steps}")
            m = n // accum_steps
            imgs_mb = images.reshape(accum_steps, m, *images.shape[1:])
            lbls_mb = labels.reshape(accum_steps, m, *labels.shape[1:])
            if mesh is not None:
                # keep each microbatch dp-sharded: without the constraint
                # GSPMD all-gathers the split batch inside the scan,
                # growing memory+comms instead of shrinking them
                mb_axes = batch_axes if len(batch_axes) > 1 \
                    else (batch_axes[0],)
                imgs_mb = _compat.with_sharding_constraint(
                    imgs_mb, NamedSharding(mesh, P(None, *mb_axes)))
                lbls_mb = _compat.with_sharding_constraint(
                    lbls_mb, NamedSharding(mesh, P(None, *mb_axes)))

            def accum(carry, mb):
                g_acc, l_acc, stats = carry
                imgs, lbls = mb
                (loss_i, mstate), g_i = grad_fn(state.params, stats,
                                                imgs, lbls)
                g_acc = jax.tree.map(jnp.add, g_acc, g_i)
                stats = mstate.get("batch_stats", stats)
                return (g_acc, l_acc + loss_i, stats), None

            g0 = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss, stats), _ = jax.lax.scan(
                accum, (g0, jnp.float32(0.0), state.batch_stats),
                (imgs_mb, lbls_mb))
            inv = 1.0 / accum_steps
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            new_model_state = {"batch_stats": stats} if stats else {}
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        if mesh is not None:
            # pin output placements to the annotated layout: without the
            # constraint GSPMD may re-shard leaves it considers
            # profitable, so the returned state's placements drift from
            # shard_train_state's and every subsequent step recompiles
            tp = mesh.shape.get("tp", 1)
            new_params = jax.tree_util.tree_map_with_path(
                lambda path, leaf: _compat.with_sharding_constraint(
                    leaf, NamedSharding(
                        mesh, param_spec(path, leaf, tp) if tp > 1
                        else P())),
                new_params)
            # optimizer state is placed replicated by shard_train_state —
            # pin it too, or the drift problem just moves into opt_state
            new_opt = jax.tree.map(
                lambda leaf: _compat.with_sharding_constraint(
                    leaf, NamedSharding(mesh, P())),
                new_opt)
        new_state = TrainState(
            params=new_params,
            batch_stats=new_model_state.get("batch_stats",
                                            state.batch_stats),
            opt_state=new_opt, step=state.step + 1)
        return new_state, loss

    # compat.jit = jax.jit + the obs CompileTracker: a train step that
    # recompiles mid-run (shape drift, sharding drift) shows up in
    # profile_compiles_total{fn="train_step"} instead of as silent
    # multi-second stalls
    return _compat.jit(step, name="train_step", donate_argnums=(0,))


def partition_train_state(state: TrainState, mesh, rules, *,
                          dtype_policy=None, on_unmatched="replicate"):
    """Place a TrainState onto a mesh per a model's partition rules.

    The rules match over the FULL state pytree: optax optimizer states
    nest the param tree, so ``.../mu/block0/qkv/kernel`` hits the same
    rule as the param and the moments co-locate with their weights (the
    fmengine TrainState pattern, SNIPPETS.md [2]). Scalars (``step``,
    adam ``count``) replicate automatically; BatchNorm ``batch_stats``
    need their own rules (the ResNet set carries them).

    Returns ``(sharded_state, state_shardings)`` — feed the shardings
    to :func:`make_partitioned_train_step` so the compiled step's
    in/out layouts pin to this placement.
    """
    from ..parallel.partition import match_partition_rules, shard_params
    specs = match_partition_rules(rules, state,
                                  on_unmatched=on_unmatched)
    state = jax.tree.map(jnp.asarray, state)
    if dtype_policy is not None:
        # params and their optimizer moments share the storage dtype;
        # batch_stats ride along (float running stats), step/count are
        # ints and pass through untouched
        state = dtype_policy.cast_params(state)
    return shard_params(mesh, state, specs)


def make_partitioned_train_step(module, tx, mesh, state_shardings, *,
                                loss_fn: Callable = softmax_xent,
                                fetch: str = "logits",
                                batch_axes: tuple[str, ...] = ("dp",),
                                accum_steps: int = 1,
                                dtype_policy=None):
    """The pjit'd twin of :func:`make_train_step`: one SPMD train step
    over a dp×tp mesh, driven by rule-derived shardings instead of the
    per-leaf heuristic.

    ``state_shardings`` (from :func:`partition_train_state`) become the
    step's in/out shardings, so GSPMD can never drift the state layout
    between steps, and the input state buffer is DONATED — at tp>1 the
    param shards update in place. Batches shard over ``batch_axes``;
    gradients reduce over the batch axes by sharding propagation (the
    psum GSPMD inserts), exactly as the heuristic step.

    Math is :func:`_make_loss_of` + the same optax update as
    ``make_train_step`` — on a 1-device mesh the two produce the same
    loss trajectory to float tolerance (pinned by test).

    ``dtype_policy``: float inputs cast to ``compute_dtype`` on entry;
    with ``accum_steps > 1`` the gradient accumulator carries
    ``grad_accum_dtype`` (the arXiv:2008.01040 mixed-precision knob —
    bf16 grads accumulate badly over many microbatches; f32 costs HBM).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    batch_sh = NamedSharding(mesh, bspec)
    repl = NamedSharding(mesh, P())

    def step(state: TrainState, images, labels):
        # mesh context for the whole traced body: model-internal
        # block-boundary constraints (partition.constrain_activation)
        # resolve against the step's mesh
        with mesh:
            return _body(state, images, labels)

    def _body(state: TrainState, images, labels):
        if dtype_policy is not None and jnp.issubdtype(
                images.dtype, jnp.floating):
            images = dtype_policy.cast_compute(images)
        loss_of = _make_loss_of(module, loss_fn, fetch)
        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        if accum_steps <= 1:
            (loss, new_model_state), grads = grad_fn(
                state.params, state.batch_stats, images, labels)
        else:
            n = images.shape[0]
            if n % accum_steps:
                raise ValueError(
                    f"batch size {n} must divide by accum_steps="
                    f"{accum_steps}")
            m = n // accum_steps
            imgs_mb = images.reshape(accum_steps, m, *images.shape[1:])
            lbls_mb = labels.reshape(accum_steps, m, *labels.shape[1:])
            # keep each microbatch batch-sharded inside the scan (the
            # same GSPMD gather hazard make_train_step documents)
            mb_sh = NamedSharding(mesh, P(None, *bspec))
            imgs_mb = _compat.with_sharding_constraint(imgs_mb, mb_sh)
            lbls_mb = _compat.with_sharding_constraint(lbls_mb, mb_sh)

            def accum(carry, mb):
                g_acc, l_acc, stats = carry
                imgs, lbls = mb
                (loss_i, mstate), g_i = grad_fn(state.params, stats,
                                                imgs, lbls)
                # cast INTO the accumulator dtype: with a lower-precision
                # grad_accum_dtype the bare add would promote the scan
                # carry and lax.scan rejects the carry-dtype drift
                g_acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                     g_acc, g_i)
                stats = mstate.get("batch_stats", stats)
                return (g_acc, l_acc + loss_i, stats), None

            def zeros_accum(p):
                if dtype_policy is not None and \
                        dtype_policy.grad_accum_dtype is not None and \
                        jnp.issubdtype(p.dtype, jnp.floating):
                    return jnp.zeros(
                        p.shape, jnp.dtype(dtype_policy.grad_accum_dtype))
                return jnp.zeros_like(p)

            g0 = jax.tree.map(zeros_accum, state.params)
            (grads, loss, stats), _ = jax.lax.scan(
                accum, (g0, jnp.float32(0.0), state.batch_stats),
                (imgs_mb, lbls_mb))
            inv = 1.0 / accum_steps
            grads = jax.tree.map(
                lambda g, p: (g * inv).astype(p.dtype),
                grads, state.params)
            loss = loss * inv
            new_model_state = {"batch_stats": stats} if stats else {}
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params,
            batch_stats=new_model_state.get("batch_stats",
                                            state.batch_stats),
            opt_state=new_opt, step=state.step + 1)
        return new_state, loss

    return _compat.jit(step, name="partitioned_train_step",
                       in_shardings=(state_shardings, batch_sh, batch_sh),
                       out_shardings=(state_shardings, repl),
                       donate_argnums=(0,))


def train_epoch(step, state, batches, placement=None):
    """Drive a jitted train step over HOST-resident (x, y) batches,
    overlapping each batch's host→device transfer with the previous
    step's execution: dispatch is asynchronous, so the ``device_put`` of
    batch i+1 runs while step i computes. This is the input-pipeline
    half the resident-buffer benchmarks skip — without it a training
    loop serializes transfer → compute → transfer (the reference hides
    the same cost inside Spark's partition iterator + CNTK minibatch
    pump, ``cntk/CNTKModel.scala:499-541``).

    ``placement``: a Device or Sharding for the batches (defaults to the
    first device; pass a NamedSharding for mesh training). Returns
    (final_state, per-batch losses as floats) — losses are fetched once
    at the end so the loop never blocks on a scalar.

    The input ``state`` is CONSUMED when ``batches`` is non-empty:
    ``make_train_step`` donates its state argument, so the caller must
    use the returned state (keeping a reference to the old one and
    touching it raises a donated-buffer error)."""
    if placement is None:
        placement = jax.devices()[0]
    losses = []

    def nbytes(x, y):
        return getattr(x, "nbytes", 0) + getattr(y, "nbytes", 0)

    def put(x, y, k):
        with _tracer.span("train.put", step=k, bytes=nbytes(x, y)):
            return (jax.device_put(x, placement),
                    jax.device_put(y, placement))

    # spans on the tracer's ring: ``train.epoch`` ⊃ per step ``put`` (the
    # two device_put calls) and ``launch`` (the call of ``step``), then
    # ``fetch``, where the host waits for the device
    with _tracer.span("train.epoch", steps=0, bytes_per_step=0) as root:
        it = iter(batches)
        try:
            x, y = next(it)
        except StopIteration:
            return state, []
        root.set_attr("bytes_per_step", nbytes(x, y))
        cur = put(x, y, 0)
        while cur is not None:
            with _tracer.span("train.launch", step=len(losses)):
                state, loss = step(state, *cur)     # async dispatch
            try:
                x, y = next(it)             # transfer overlaps the step
                cur = put(x, y, len(losses) + 1)
            except StopIteration:
                cur = None
            losses.append(loss)
        with _tracer.span("train.fetch"):
            losses = [float(l) for l in jax.device_get(losses)]
        root.set_attr("steps", len(losses))
    return state, losses
