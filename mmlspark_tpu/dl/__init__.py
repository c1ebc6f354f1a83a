"""Deep-learning runtime: device-resident model transformer + sharded
training.

Replaces the reference's CNTK-on-Spark layer (``cntk/CNTKModel.scala``,
``com/microsoft/CNTK/SerializableFunction.scala``): instead of broadcasting
serialized native graphs to executor JVMs and crossing JNI per batch, models
are flax modules jitted once, with weights living in device memory, sharded
by ``jax.sharding`` over the mesh.

The package __init__ is LAZY (no jax at import time): the LLM-serving
control plane imports the paged-KV bookkeeping half (``dl.paged_kv``)
from handler threads and host-only processes, and a submodule import
must not drag flax/backend bring-up into every importer (the same
no-JAX discipline ``sched``/``obs``/``perf`` keep, asserted by the CI
style smoke). Heavy submodules load on first attribute access;
``import mmlspark_tpu.dl.paged_kv`` alone stays jax-free.
"""

from __future__ import annotations

import importlib
import sys
import types

# public name -> defining submodule. Resolution is lazy: the submodule
# imports (and its partition-rule registration runs) on first access.
_EXPORTS = {
    "BertEncoder": ".bert",
    "ContinuousGenerator": ".generate",
    "TextGenerator": ".generate",
    "generate": ".generate",
    "generate_speculative": ".speculative",
    "TPUModel": ".model",
    "MaskedLMModel": ".pretrain",
    "encoder_variables": ".pretrain",
    "pretrain_causal_lm": ".pretrain",
    "pretrain_masked_lm": ".pretrain",
    "TextEncoder": ".text_encoder",
    "TextEncoderFeaturizer": ".text_encoder",
    "make_attention_fn": ".text_encoder",
    "TrainState": ".train",
    "make_train_step": ".train",
    "shard_train_state": ".train",
    "train_epoch": ".train",
    "PagedKVManager": ".paged_kv",
    "SequenceHandle": ".paged_kv",
    "paged_attention": ".pallas_paged_attention",
    "paged_window_attention": ".pallas_paged_attention",
    "paged_latent_attention": ".pallas_paged_attention",
    "LatentMoEDecoder": ".latent_moe_decoder",
    "SparseLinearDecoder": ".sparse_linear_decoder",
    "GatedDeltaMoEDecoder": ".gated_delta_moe_decoder",
    "gated_delta_rule": ".pallas_gated_delta",
}

__all__ = sorted(_EXPORTS)


class _LazyDlModule(types.ModuleType):
    """Module class carrying the lazy exports.

    ``generate`` needs special care: it is BOTH a submodule
    (``dl/generate.py``) and an exported function. The import system
    unconditionally ``setattr``\\ s a submodule onto its parent package
    on first import — so a plain lazy ``__getattr__`` would race:
    whichever of ``from mmlspark_tpu.dl import generate`` and an import
    of ``dl.speculative`` (whose ``from .generate import ...`` triggers
    that setattr) runs first would decide whether the attribute is the
    function or the module. A data descriptor (property) on the module
    CLASS always wins attribute lookup over the instance ``__dict__``,
    so reads deterministically get the function no matter the import
    order; the setter swallows the import system's module setattr.
    """

    @property
    def generate(self):
        mod = importlib.import_module(".generate", __name__)
        return mod.generate

    @generate.setter
    def generate(self, value):
        # the import system setattr()s the freshly imported submodule
        # here; the property getter shadows it either way, so nothing
        # to store — rebinding the public name to anything else is a
        # programming error worth surfacing
        if not isinstance(value, types.ModuleType):
            raise AttributeError(
                "mmlspark_tpu.dl.generate is a lazy export; import "
                "the submodule to patch its contents instead")

    def __getattr__(self, name):
        try:
            modname = _EXPORTS[name]
        except KeyError:
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}"
            ) from None
        mod = importlib.import_module(modname, __name__)
        value = getattr(mod, name)
        # cache everything except the descriptor-managed name (its
        # property must keep winning over the instance __dict__)
        if name != "generate":
            setattr(self, name, value)
        return value

    def __dir__(self):
        return sorted(set(super().__dir__()) | set(__all__))


sys.modules[__name__].__class__ = _LazyDlModule
