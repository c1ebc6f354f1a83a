"""In-framework masked-LM pretraining for the text encoder.

The reference ships pretrained models through its downloader
(``downloader/ModelDownloader.scala:37-60``) and never trains one; this
build is zero-egress, so pretrained text representations are produced
IN the framework: BERT-style masked-token prediction over any corpus,
yielding encoder weights the zoo serves to ``TextEncoderFeaturizer``
exactly like the vision checkpoints (``image/ImageFeaturizer.scala:81-85``
is the consumption pattern being mirrored).

TPU shape notes: the whole step is one jitted graph (embedding + blocks
+ LM head + masked xent), masking is host-side numpy (cheap, keeps the
graph static), batches stream through ``train_epoch``'s overlapped
transfer loop.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from ..parallel.partition import (DtypePolicy, activation_spec_for,
                                  dtype_policy_for, partition_rules_for,
                                  register_partition_rules)
from .text_encoder import TextEncoder
from .train import (TrainState, init_train_state,
                    make_partitioned_train_step, make_train_step,
                    partition_train_state, train_epoch)


class MaskedLMModel(nn.Module):
    """Encoder trunk + token-level LM head. Params nest under
    ``params["encoder"]`` / ``params["lm_head"]``, so the trunk's
    weights lift out cleanly for zoo publication
    (:func:`encoder_variables`)."""
    encoder: TextEncoder

    def setup(self):
        self.lm_head = nn.Dense(self.encoder.vocab, dtype=jnp.float32,
                                name="lm_head")

    def __call__(self, ids, train: bool = False):
        out = self.encoder(ids, train)
        return {"logits": self.lm_head(out["tokens"]), **out}

    def decode_step(self, tok, caches, pos):
        """One cached autoregressive step: [B] token ids at (traced)
        position ``pos`` → ([B, V] logits, updated per-block KV
        caches). Same params/math as the full forward restricted to the
        causal row (``dl.generate`` uses this; equivalence pinned by
        test)."""
        x = self.encoder.embed_token(tok, pos)
        x, caches = self.encoder.decode_blocks(x, caches, pos)
        return self.lm_head(x)[:, 0], caches

    def prefill(self, ids_prefix, caches):
        """Batched prompt prefill: seed the KV caches for positions
        ``[0, P)`` in one causal forward (``TextEncoder.prefill_caches``)
        so ``dl.generate`` scans only from the first writable position
        instead of streaming the whole prompt token-by-token."""
        return self.encoder.prefill_caches(ids_prefix, caches)

    def decode_window(self, toks, caches, pos):
        """Cached forward over a w-position window: [B, w] token ids
        at global positions ``[pos, pos+w)`` → ([B, w, V] logits,
        updated caches). Speculative decoding's verify pass — the
        target scores every draft position in ONE call
        (``dl.speculative``)."""
        x = self.encoder.embed_window(toks, pos)
        x, caches = self.encoder.decode_window_blocks(x, caches, pos)
        return self.lm_head(x), caches


    # -- the decoder interface of ``serving.llm.LLMEngine`` ----------------
    #: counts a walk returns beside its hidden rows (none for this decoder)
    walk_stats = ()

    def cache_spec(self) -> tuple:
        """For each layer, the arrays one token takes in the paged cache
        as ``(trailing shape, dtype)``: a key and a value of ``[heads *
        head_dim]``, the heads side by side, which is how the paged
        kernel reads a block (positions on sublanes, a head a
        lane-aligned slice)."""
        enc = self.encoder
        kv = ((enc.width,), enc.dtype)
        return ((kv, kv),) * enc.depth

    def max_window(self) -> int:
        """The widest window :meth:`walk` takes (the paged kernel holds
        a slot's whole window in fast memory)."""
        from .pallas_paged_attention import max_window
        enc = self.encoder
        return max_window(enc.heads, enc.width // enc.heads, enc.dtype)

    def program_key(self) -> dict:
        """Everything that changes a compiled walk besides the batch
        shapes: the static fragment of the engine's AOT fingerprints."""
        enc = self.encoder
        return {"vocab": enc.vocab, "width": enc.width,
                "depth": enc.depth, "heads": enc.heads,
                "mlp_dim": enc.mlp_dim, "dtype": np.dtype(enc.dtype).name}

    #: the walk takes any number of windows in one call
    several_windows = True

    def walk(self, windows, pools):
        """The paged decode forward WITHOUT the head over a tuple of
        WINDOWS, each ``(toks [S, w], rows [S, max_blocks], pos [S], valid
        [S, w] or [S, 1])``: [S, w] token ids at per-slot global positions
        ``[pos[s], pos[s]+w)`` → (a tuple of [S, w, width] hidden rows
        after the last block, one a window; updated pools; None),
        reading/writing the pools IN PLACE through each window's block
        table. The engine hands over the decoding rows (``w`` = 1), a
        prefill window, or both at a boundary where a prompt's window
        rides with the decoding rows. The caller picks the rows a token
        is sampled from and asks :meth:`logits` for those alone.

        What acts a row at a time (embedding, norms, the qkv and output
        projections, the feed-forward) runs ONCE over all the windows'
        rows laid end to end (``decoder_blocks.lay_rows``), so each weight
        is read once a call. Per block and window: scatter the window's
        kv through its table (write-then-attend, the order
        ``decode_step``/``decode_window`` keep; ``valid`` False redirects
        a row's writes to the trash block), then
        ``dl.paged_window_attention`` over each slot's own chain — no
        dense gather anywhere. The embed/projection/attention/ffn math,
        with :meth:`logits` after it, is element-for-element the
        ``embed_window → decode_window_blocks → lm_head`` composition
        (the lax attention path shares ``decode_window``'s exact
        formulation), so greedy tokens stay byte-identical to
        ``dl.generate`` on CPU tier-1.

        Runs under ``module.apply(..., method="walk")``."""
        from .decoder_blocks import lay_rows, split_rows, window_positions
        from .paged_kv import scatter_positions
        from .pallas_paged_attention import paged_window_attention

        enc = self.encoder
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                   # [S, w] each
        # batched embed_window: same constants/ops per element, positions
        # per row instead of one traced scalar
        x = enc.embed_layer(lay_rows([win[0] for win in windows]))  # [T, W]
        dim = jnp.arange(enc.width // 2)[None, :]
        p = lay_rows(wrote).astype(jnp.float32)[:, None]
        ang = p / (10000.0 ** (2 * dim / enc.width))
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = (x + pe.astype(enc.dtype))[None]                # [1, T, W]
        # ONE jitted function serves every block (same shapes, each its
        # own parameters): a program of 24 blocks traces and lowers one
        # block, not 24, which is most of what ``engine.warm`` takes a
        # program once the compile cache holds it
        block = type(enc.blocks[0])(
            enc.heads, enc.mlp_dim, enc.width,
            attention_fn=enc.attention_fn, dtype=enc.dtype, parent=None)
        tables = tuple((rows, pos, valid, at) for (_, rows, pos, valid), at
                       in zip(windows, wrote))

        @jax.jit
        def layer(params, x, kp, vp, tables):
            def call(method, *args):
                return block.apply({"params": params}, *args, method=method)

            q, k, v = (jnp.moveaxis(a[0], 0, 1)             # [T, H, hd]
                       for a in call("_project_qkv", x))
            # a token's heads side by side, as the pools hold them
            ks, vs = (split_rows(a.reshape(-1, enc.width).astype(kp.dtype),
                                 shapes) for a in (k, v))   # [S, w, W]
            outs = []
            for (rows, pos, valid, at), qw, kw, vw in zip(
                    tables, split_rows(q, shapes), ks, vs):
                (kp, vp), = scatter_positions(
                    ((kp, vp),), rows, at, ((kw, vw),), valid=valid)
                o = paged_window_attention(
                    qw.transpose(0, 2, 1, 3), kp, vp, rows, pos)
                outs.append(o.transpose(0, 2, 1, 3))        # [S, w, H, hd]
            o = jnp.moveaxis(lay_rows(outs), 0, 1)[None]    # [1, H, T, hd]
            return call("ffn", x + call("_merge_out", o)), kp, vp

        new_pools = []
        for blk, (kp, vp) in zip(enc.blocks, pools):
            x, kp, vp = layer(blk.variables["params"], x, kp, vp, tables)
            new_pools.append((kp, vp))
        return split_rows(x[0], shapes), tuple(new_pools), None

    def logits(self, hidden):
        """The head over the rows the caller picked out of a walk's
        hidden rows: [..., width] → [..., V] float32 logits (the final
        norm, per row, then ``lm_head``). Runs under
        ``module.apply(..., method="logits")``."""
        return self.lm_head(self.encoder.final_ln(hidden))


# Partition rules for the pretraining LM: the encoder trunk's rules
# (paths under ``encoder/`` still hit them — re.search is unanchored)
# plus the LM head, column-parallel like every other vocab-sized
# projection. Registered here, next to MaskedLMModel, so the rule set
# lives beside the architecture it describes.
register_partition_rules("TextEncoderLM", (
    *partition_rules_for("TextEncoder"),
    (r"lm_head/kernel", (None, "tp")),
    (r"lm_head/bias", ("tp",)),
),
    # inherit the trunk's chip defaults (bf16 compute / fp32 accum,
    # dp-sharded block-boundary activations)
    dtype_policy=dtype_policy_for("TextEncoder") or DtypePolicy(
        param_dtype="float32", compute_dtype="bfloat16",
        grad_accum_dtype="float32"),
    activation_spec=activation_spec_for("TextEncoder") or ("dp",))


def _mesh_step_and_state(module, tx, state, mesh, dtype_policy,
                         batch_size):
    """Shared mesh plumbing for both pretraining objectives: validate
    the mesh/batch pairing, shard the LM TrainState per the
    TextEncoderLM rules, build the pjit'd step, and return the batch
    placement ``train_epoch`` should device_put host batches with
    (rows over ``dp``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    if mesh is None:
        step = make_train_step(module, tx, fetch="logits",
                               loss_fn=masked_xent)
        return step, jax.tree.map(jnp.asarray, state), None
    if "dp" not in mesh.shape:
        raise ValueError(
            f"pretraining shards batches over axis 'dp'; mesh has "
            f"{tuple(mesh.shape)}")
    if batch_size % mesh.shape["dp"]:
        raise ValueError(
            f"batch_size={batch_size} must divide by the dp axis "
            f"({mesh.shape['dp']})")
    state, shardings = partition_train_state(
        state, mesh, partition_rules_for("TextEncoderLM"),
        dtype_policy=dtype_policy)
    step = make_partitioned_train_step(
        module, tx, mesh, shardings, fetch="logits",
        loss_fn=masked_xent, dtype_policy=dtype_policy)
    # spec spelled exactly like the step's batch in_shardings so the
    # device_put in train_epoch and the compiled signature agree
    return step, state, NamedSharding(mesh, P("dp"))


def masked_xent(logits, labels):
    """Cross-entropy over positions with ``labels >= 0`` (−1 = ignore:
    unmasked or pad). Mean over masked positions only."""
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1)


def assert_causal(module, variables, sample_ids: np.ndarray,
                  vocab: int) -> None:
    """Causality probe: perturb the LAST position of ``sample_ids``
    [1, T]; logits at earlier positions must not move. Catches a
    bidirectional encoder passed where causality is required
    (pretraining, generation) — the failure mode is silent
    otherwise."""
    probe = np.asarray(sample_ids, np.int32)[:1].copy()
    if probe.shape[1] < 2:
        return
    base = module.apply(variables, jnp.asarray(probe))["logits"]
    probe2 = probe.copy()
    probe2[0, -1] = (probe2[0, -1] % (vocab - 2)) + 1
    alt = module.apply(variables, jnp.asarray(probe2))["logits"]
    drift = float(jnp.abs(base[0, :-1] - alt[0, :-1]).max())
    if drift > 1e-4:
        raise ValueError(
            "encoder attends to FUTURE positions (logit drift "
            f"{drift:.2e} after perturbing the last token) — build it "
            "with make_attention_fn(..., causal=True)")


def mask_batch(ids: np.ndarray, rng: np.random.Generator, *,
               mask_id: int, mask_frac: float = 0.15,
               pad_id: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """BERT-style corruption: ``mask_frac`` of non-pad positions are
    replaced by ``mask_id``; labels carry the original id there and −1
    everywhere else."""
    maskable = ids != pad_id
    pick = (rng.random(ids.shape) < mask_frac) & maskable
    x = np.where(pick, mask_id, ids).astype(np.int32)
    y = np.where(pick, ids, -1).astype(np.int32)
    return x, y


def pretrain_masked_lm(encoder: TextEncoder, ids: np.ndarray, *,
                       steps: int = 200, batch_size: int = 32,
                       learning_rate: float = 1e-3,
                       mask_frac: float = 0.15, mask_id: int | None = None,
                       seed: int = 0, mesh=None, dtype_policy=None,
                       tx: Any = None) -> tuple[TrainState, list[float]]:
    """Pretrain ``encoder`` on token-id rows ``ids`` [N, T] (pad id 0).

    ``mask_id`` defaults to the encoder's top vocab slot — reserve it
    when fitting the tokenizer (``BpeTokenizer`` never emits an id ≥ its
    ``vocabSize``, so an encoder ``vocab`` of ``vocabSize + 1`` leaves
    the slot free). Returns the full LM train state (resumable via
    ``CheckpointManager``) and per-batch losses; lift the trunk with
    :func:`encoder_variables` for zoo publication.

    ``mesh``: pjit the step over it (batch over ``dp``, weights per the
    TextEncoderLM partition rules; ``dtype_policy`` rides along) —
    ``batch_size`` must divide by the ``dp`` axis size."""
    ids = np.asarray(ids, np.int32)
    if mask_id is None:
        mask_id = encoder.vocab - 1
    if ids.max(initial=0) >= mask_id:
        raise ValueError(
            f"corpus uses id {ids.max()} but mask_id={mask_id}; give the "
            "encoder a spare top slot (vocab >= tokenizer vocab + 1)")
    module = MaskedLMModel(encoder)
    tx = tx or optax.adamw(learning_rate)
    state = init_train_state(module, jax.random.PRNGKey(seed), ids[:1],
                             tx)
    rng = np.random.default_rng(seed)

    def batches():
        for _ in range(steps):
            rows = ids[rng.integers(0, len(ids), size=batch_size)]
            yield mask_batch(rows, rng, mask_id=mask_id,
                             mask_frac=mask_frac)

    step, state, placement = _mesh_step_and_state(
        module, tx, state, mesh, dtype_policy, batch_size)
    return train_epoch(step, state, batches(), placement=placement)


def encoder_variables(state: TrainState) -> dict:
    """Extract the encoder trunk's variables from an LM train state, in
    the shape ``TextEncoder.apply`` (and the zoo checkpoint format)
    expects."""
    return {"params": state.params["encoder"]}


def pretrain_causal_lm(encoder: TextEncoder, ids: np.ndarray, *,
                       steps: int = 200, batch_size: int = 32,
                       learning_rate: float = 1e-3, seed: int = 0,
                       mesh=None, dtype_policy=None,
                       tx: Any = None) -> tuple[TrainState, list[float]]:
    """Next-token pretraining (the decoder-side twin of
    :func:`pretrain_masked_lm`): logits at position t predict token
    t+1, pad targets ignored. Pad id is 0 — the framework-wide
    convention ``TextEncoder`` hardcodes for its attention key mask and
    mean-pool (a configurable pad id here would silently desynchronize
    from the encoder's).

    The ``encoder`` MUST run causal attention (build it with
    ``make_attention_fn(impl, causal=True)``) — with bidirectional
    attention the objective is trivially cheatable by copying the next
    token, and the check below rejects it: position i's logits must be
    invariant to tokens at positions > i.

    ``mesh``/``dtype_policy``: same pjit contract as
    :func:`pretrain_masked_lm`."""
    ids = np.asarray(ids, np.int32)
    module = MaskedLMModel(encoder)  # same trunk + token head
    tx = tx or optax.adamw(learning_rate)
    state = init_train_state(module, jax.random.PRNGKey(seed), ids[:1],
                             tx)
    assert_causal(module, {"params": state.params}, ids[:1],
                  encoder.vocab)
    rng = np.random.default_rng(seed)

    def batches():
        for _ in range(steps):
            rows = ids[rng.integers(0, len(ids), size=batch_size)]
            x = rows[:, :-1]
            y = np.where(rows[:, 1:] != 0, rows[:, 1:],
                         -1).astype(np.int32)
            yield x.astype(np.int32), y

    step, state, placement = _mesh_step_and_state(
        module, tx, state, mesh, dtype_policy, batch_size)
    return train_epoch(step, state, batches(), placement=placement)
