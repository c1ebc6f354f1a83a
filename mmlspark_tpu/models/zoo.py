"""Model registry + downloader.

Reference: ``downloader/ModelDownloader.scala`` + ``downloader/Schema.scala``
— a catalogue of pretrained CNNs (``ModelSchema``: uri, hash, inputNode,
numLayers, layerNames) fetched from Azure blob with hash verification and
retry (``FaultToleranceUtils.retryWithTimeout``,
``ModelDownloader.scala:37-60``).

TPU-native version: the schema survives; weights come from a local path or
an orbax checkpoint. In a zero-egress build remote URIs are gated — models
not found locally are initialized from the flax init (random weights), which
keeps every downstream pipeline runnable and shape-correct; swap in real
checkpoints by pointing ``MMLSPARK_TPU_MODEL_DIR`` at a checkpoint tree.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Any, Callable

import jax
import numpy as np

from ..core.utils import retry_with_timeout


@dataclasses.dataclass
class ModelSchema:
    """Catalogue entry (reference ``downloader/Schema.scala``)."""
    name: str
    dataset: str = "ImageNet"
    model_type: str = "image"
    uri: str | None = None
    hash: str | None = None
    input_node: str = "image"
    num_layers: int = 0
    layer_names: tuple[str, ...] = ()
    input_size: int = 224
    num_classes: int = 1000
    builder: Callable[..., Any] | None = None


_REGISTRY: dict[str, ModelSchema] = {}


def register_model(schema: ModelSchema) -> ModelSchema:
    _REGISTRY[schema.name] = schema
    return schema


def _register_builtins():
    from .resnet import ResNet18, ResNet34, ResNet50, ResNet101
    for name, builder, layers in [
            ("ResNet18", ResNet18, 18), ("ResNet34", ResNet34, 34),
            ("ResNet50", ResNet50, 50), ("ResNet101", ResNet101, 101)]:
        register_model(ModelSchema(
            name=name, num_layers=layers, builder=builder,
            layer_names=("stage1", "stage2", "stage3", "stage4",
                         "pooled", "logits")))
    from .vit import ViT_B_16, ViT_L_16
    for name, builder, depth in [("ViT_B_16", ViT_B_16, 12),
                                 ("ViT_L_16", ViT_L_16, 24)]:
        register_model(ModelSchema(
            name=name, num_layers=depth, builder=builder,
            layer_names=tuple(f"block{i + 1}" for i in range(depth))
            + ("pooled", "logits")))
    # default text entry: the in-framework pretraining target
    # (dl/pretrain.py) — the text counterpart of the CNN catalogue
    register_text_encoder("TextEncoderBase", vocab=32768, width=256,
                          depth=4, heads=8, mlp_dim=1024)


class _TextEncoderBuilder:
    """Picklable text-encoder factory (a closure here would break
    ComplexParam persistence of any stage holding the LoadedModel —
    e.g. ``TextEncoderFeaturizer(model=...).save()``)."""

    def __init__(self, vocab: int, width: int, depth: int, heads: int,
                 mlp_dim: int):
        self.vocab, self.width, self.depth = vocab, width, depth
        self.heads, self.mlp_dim = heads, mlp_dim

    def __call__(self, **kwargs):
        from ..dl.text_encoder import TextEncoder
        return TextEncoder(vocab=self.vocab, width=self.width,
                           depth=self.depth, heads=self.heads,
                           mlp_dim=self.mlp_dim, **kwargs)


def register_text_encoder(name: str, *, vocab: int, width: int,
                          depth: int, heads: int,
                          mlp_dim: int | None = None,
                          seq_len: int = 128) -> ModelSchema:
    """Register a text-encoder catalogue entry. The reference catalogue
    is CNN-only (``downloader/Schema.scala``); text entries carry the
    encoder hyperparameters so a zoo checkpoint (e.g. from
    ``dl.pretrain.pretrain_masked_lm`` + ``models.convert
    .save_converted``) reloads into the exact architecture that
    produced it. ``seq_len`` only sizes the random-init dummy."""
    return register_model(ModelSchema(
        name=name, dataset="custom", model_type="text",
        num_layers=depth, input_node="tokens", input_size=seq_len,
        num_classes=0,
        builder=_TextEncoderBuilder(vocab, width, depth, heads,
                                    mlp_dim or 4 * width),
        layer_names=tuple(f"block{i}" for i in range(depth))
        + ("tokens", "pooled")))


_register_builtins()


class _BertEncoderBuilder:
    """Picklable BERT-encoder factory (mirrors ``_TextEncoderBuilder``
    — a closure would break ComplexParam persistence)."""

    def __init__(self, **arch):
        self.arch = dict(arch)

    def __call__(self, **kwargs):
        from ..dl.bert import BertEncoder
        return BertEncoder(**self.arch, **kwargs)


def register_bert_encoder(name: str, *, vocab: int, width: int,
                          depth: int, heads: int, mlp_dim: int,
                          max_len: int = 512, type_vocab: int = 2,
                          pooler: bool = True,
                          seq_len: int = 128) -> ModelSchema:
    """Register an ingested-BERT catalogue entry (the text counterpart
    of the reference's downloaded-CNTK-model entries,
    ``downloader/Schema.scala``): a foreign checkpoint converted by
    ``models.convert.torch_bert_to_flax`` + ``save_converted`` reloads
    into the exact BERT architecture that produced it."""
    return register_model(ModelSchema(
        name=name, dataset="custom", model_type="text",
        num_layers=depth, input_node="tokens",
        # clamp: the random-init dummy must fit the checkpoint's
        # learned position table or module.init raises
        input_size=min(seq_len, max_len),
        num_classes=0,
        builder=_BertEncoderBuilder(vocab=vocab, width=width,
                                    depth=depth, heads=heads,
                                    mlp_dim=mlp_dim, max_len=max_len,
                                    type_vocab=type_vocab,
                                    pooler=pooler),
        layer_names=tuple(f"block{i}" for i in range(depth))
        + ("tokens", "pooled", "cls")))


def get_model(name: str) -> ModelSchema:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


@dataclasses.dataclass
class LoadedModel:
    """A model ready for inference: module + variables + schema."""
    schema: ModelSchema
    module: Any
    variables: dict

    @property
    def layer_names(self) -> list[str]:
        return list(self.schema.layer_names)


class ModelDownloader:
    """Resolve a catalogue model to weights (reference
    ``ModelDownloader.downloadByName``). Local checkpoint dir → orbax
    restore; otherwise deterministic random init (zero-egress fallback).
    """

    def __init__(self, local_dir: str | None = None):
        self.local_dir = local_dir or os.environ.get(
            "MMLSPARK_TPU_MODEL_DIR", "")

    def download_by_name(self, name: str, *, num_classes: int | None = None,
                         dtype=None, remat: bool | None = None,
                         allow_random_init: bool | None = None) -> LoadedModel:
        """Resolve ``name`` to a ready model.

        ``remat``: rematerialize blocks in the backward
        (``jax.checkpoint``) — the fine-tune memory lever; param names
        are unchanged, so checkpoints load identically.

        ``allow_random_init``: when no checkpoint is found locally, True
        falls back to deterministic random init (useful for shape checks
        and architecture tests); False raises; None (default) reads the
        ``MMLSPARK_TPU_ALLOW_RANDOM_INIT`` env toggle (default allow,
        with a warning). The reference fails loudly when its download
        cannot be verified (``ModelDownloader.scala:37-60``).
        """
        schema = get_model(name)
        kwargs = {}
        if num_classes is not None:
            kwargs["num_classes"] = num_classes
        if dtype is not None:
            kwargs["dtype"] = dtype
        if remat is not None:
            # the fine-tune memory lever (ResNet/ViT/TextEncoder remat
            # flags); param names are unchanged, so checkpoints load
            # identically whether or not blocks rematerialize
            kwargs["remat"] = remat
        module = schema.builder(**kwargs)
        variables = self._load_or_init(schema, module, allow_random_init)
        return LoadedModel(schema=schema, module=module, variables=variables)

    # -- weights ------------------------------------------------------------
    def _ckpt_path(self, schema: ModelSchema) -> str | None:
        if not self.local_dir:
            return None
        path = os.path.join(self.local_dir, schema.name)
        return path if os.path.isdir(path) else None

    def _load_or_init(self, schema: ModelSchema, module,
                      allow_random_init: bool | None = None) -> dict:
        path = self._ckpt_path(schema)
        if path:
            def restore():
                import orbax.checkpoint as ocp
                with ocp.PyTreeCheckpointer() as ck:
                    return ck.restore(path)
            # reference retries downloads with backoff; hash verification
            # is deterministic, so it runs once OUTSIDE the retry loop
            variables = retry_with_timeout(restore, backoffs_ms=(0, 100, 200))
            manifest = os.path.join(self.local_dir,
                                    f"{schema.name}.manifest.json")
            if os.path.exists(manifest):
                # reference verifies the downloaded artifact's hash
                # (ModelDownloader.scala:37-60); corrupted weights fail loud
                from .convert import verify_checkpoint
                verify_checkpoint(variables, manifest)
            return variables
        if allow_random_init is None:
            allow_random_init = os.environ.get(
                "MMLSPARK_TPU_ALLOW_RANDOM_INIT", "1") != "0"
            if allow_random_init:
                import warnings
                warnings.warn(
                    f"no checkpoint for {schema.name!r} under "
                    f"{self.local_dir or '<unset MMLSPARK_TPU_MODEL_DIR>'}; "
                    "initializing RANDOM weights (shape-correct, not "
                    "pretrained). Pass allow_random_init=True to silence, "
                    "or point MMLSPARK_TPU_MODEL_DIR at a checkpoint tree.",
                    stacklevel=3)
        if not allow_random_init:
            raise FileNotFoundError(
                f"no local checkpoint for model {schema.name!r} "
                f"(looked under {self.local_dir or '<unset>'}) and "
                "allow_random_init is False; convert weights with "
                "mmlspark_tpu.models.convert and set MMLSPARK_TPU_MODEL_DIR")
        rng = jax.random.PRNGKey(
            int(hashlib.md5(schema.name.encode()).hexdigest()[:8], 16))
        if schema.model_type == "text":
            dummy = np.zeros((1, schema.input_size), np.int32)
        else:
            dummy = np.zeros((1, schema.input_size, schema.input_size, 3),
                             np.float32)
        # init on host CPU when it is addressable beside the accelerator:
        # weights move to the device on the first jitted apply (or an
        # explicit device_put). JAX_PLATFORMS may exclude cpu, in which
        # case init runs on the default device.
        import contextlib
        try:
            ctx = jax.default_device(jax.local_devices(backend="cpu")[0])
        except RuntimeError:
            ctx = contextlib.nullcontext()
        with ctx:
            return jax.jit(module.init, static_argnums=2)(rng, dummy, False)
