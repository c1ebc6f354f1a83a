"""TPUModel — the DL inference transformer.

Reference ``cntk/CNTKModel.scala:145-543``: broadcast a serialized CNTK
graph, minibatch rows, cross JNI per batch, unbatch, coerce to vectors.
TPU-native equivalent:

- the model is a flax module + variables (a :class:`LoadedModel` from the
  zoo or any (module, variables) pair);
- ``feedDict``/``fetchDict`` map dataframe columns to model inputs and named
  endpoints to output columns (reference ``setFeedDict``/``setFetchDict``,
  ``CNTKModel.scala:207-227``);
- batching pads the last partial batch to a fixed shape so ONE compiled
  program serves the whole column (the reference's
  ``FixedMiniBatchTransformer(10)`` default, ``CNTKModel.scala:377``, exists
  to bound JNI churn; here fixed shapes exist to avoid recompilation);
- inference is sharded over the ``dp`` mesh axis when a mesh is supplied.
"""

from __future__ import annotations

import collections
import contextlib
import math

import jax.numpy as jnp
import numpy as np

from ..core import ComplexParam, Model, Param, TypeConverters as TC
from ..core.contracts import HasInputCol, HasOutputCol
from ..models.zoo import LoadedModel
from ..obs.tracing import tracer as _tracer
from ..parallel.compat import jit as _jit


class TPUModel(Model, HasInputCol, HasOutputCol):
    """Run a flax model over a feature/image column.

    minibatchSize: device batch; the column is chunked to this size and the
    tail padded (mask-dropped on output), so exactly one XLA program is
    compiled per (model, batch-size).
    """

    model = ComplexParam("model", "LoadedModel or (module, variables)")
    fetchDict = Param("fetchDict", "endpoint name -> output column",
                      TC.identity, default=None, has_default=True)
    minibatchSize = Param("minibatchSize", "device batch size", TC.toInt,
                          default=64, has_default=True)
    outputNode = Param("outputNode", "single endpoint to fetch",
                       TC.toString, default="pooled", has_default=True)
    convertOutputToDenseVector = Param(
        "convertOutputToDenseVector",
        "flatten non-vector outputs to 2-D float vectors", TC.toBoolean,
        default=True, has_default=True)
    inputShape = Param("inputShape", "per-row input shape (tuple), e.g. "
                       "(224, 224, 3) for NHWC images", TC.identity,
                       default=None, has_default=True)
    transferDtype = Param(
        "transferDtype",
        "host->device wire dtype: 'auto' keeps uint8 columns as uint8 "
        "(4x fewer bytes than float32; the model's on-device cast "
        "handles widening), 'uint8' ditto (explicit), 'bfloat16' "
        "additionally halves float transfer — lossless when the "
        "model's first op casts to bf16 anyway — and 'float32' always "
        "widens on host (pre-round-3 behavior)", TC.toString,
        default="auto", has_default=True)
    pipelineDepth = Param(
        "pipelineDepth",
        "max in-flight dispatched batches before draining (>= 2). The "
        "default keeps one batch computing while one drains; raise it "
        "when host-to-device transfers are slow next to the step, so "
        "more of them overlap — at the cost of holding that many batches' outputs in device memory",
        TC.toInt, default=2, has_default=True)

    # class-level fallbacks: the serializer reconstructs instances
    # without running __init__
    _run_cache = None
    # (buffer, dirty): one zero-padded host minibatch for the tail, whose
    # rows from ``dirty`` on are zero. A transform takes it off the
    # instance while it runs (``_padded_tail``), so no two share it.
    _tail_buffer = None
    # per-transform timing breakdown, so transfer and device wait can be
    # told from framework overhead in e2e numbers. Keys:
    # prep_ms (host coercion), dispatch_ms (batch slicing + async
    # submit incl. transfer enqueue), drain_ms (waiting on device
    # compute + output pull), total_ms. Overwritten by every transform;
    # summed from the transform's spans (``tpu_model.*``), so the two
    # can never disagree.
    last_stats: dict | None = None

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._setDefault(inputCol="features", outputCol="output")
        self._run_cache = None
        self._tail_buffer = None
        self.last_stats = None

    def copy(self, extra=None):
        out = super().copy(extra)
        # the shallow copy would share the held buffer between instances
        out.__dict__.pop("_tail_buffer", None)
        return out

    # ------------------------------------------------------------------
    def _loaded(self) -> tuple:
        m = self.get("model")
        if isinstance(m, LoadedModel):
            return m.module, m.variables
        return m  # (module, variables)

    def _apply_fn(self):
        """The jitted apply, cached per (module, variables) identity: a
        fresh closure per transform would RETRACE the model every call.

        Identity keying means weight UPDATES must arrive by reassignment
        (``set("model", ...)`` / a new LoadedModel), never by mutating
        the cached variables pytree in place — in-place writes would
        silently serve the stale compiled weights."""
        module, variables = self._loaded()
        key = (id(module), id(variables))
        if self._run_cache is None or self._run_cache[0] != key:
            def run(batch):
                return module.apply(variables, batch, False)
            self._run_cache = (key, _jit(run, name="tpu_model_apply"))
        return self._run_cache[1]

    def _transform(self, df):
        """One transform as a tree of spans on the tracer's ring:
        ``tpu_model.transform`` ⊃ ``prep``, then per minibatch ``stage``
        (slice, tail pad), ``put`` (host-to-device call), ``launch`` (the
        jitted call) and ``drain`` (wait for the device, copy out and write
        the rows into the output column), then ``collect`` (the columns
        join the frame). The children name the root as their parent and leave
        the ambient context alone (the drain of minibatch k runs inside
        iteration k+1)."""
        root = _tracer.start_span("tpu_model.transform")
        sums: dict[str, float] = collections.defaultdict(float)

        @contextlib.contextmanager
        def child(what, **attrs):
            span = _tracer.start_span(f"tpu_model.{what}", parent=root,
                                      current=False, **attrs)
            try:
                yield span
            except BaseException as e:
                _tracer.end_span(span, error=e)
                raise
            _tracer.end_span(span)
            sums[span.name] += span.seconds

        try:
            df = self._transform_spanned(df, root, child)
        except BaseException as e:
            _tracer.end_span(root, error=e)
            raise
        _tracer.end_span(root)
        self.last_stats = {
            "prep_ms": round(sums["tpu_model.prep"] * 1e3, 3),
            "dispatch_ms": round(
                (sums["tpu_model.stage"] + sums["tpu_model.put"]
                 + sums["tpu_model.launch"]) * 1e3, 3),
            "drain_ms": round(sums["tpu_model.drain"] * 1e3, 3),
            "total_ms": round(root.seconds * 1e3, 3),
        }
        return df

    def _padded_tail(self, piece: np.ndarray, bs: int):
        """``piece`` zero-padded to ``bs`` rows, in the held buffer when
        it fits (else a new one), and which of the two it was. The caller
        owns the buffer until it hands it back through ``_tail_buffer``,
        and does so only once every transfer out of it has ended: the
        host-to-device copy reads it after the ``put`` call returns."""
        real = piece.shape[0]
        shape = (bs,) + piece.shape[1:]
        # dict.pop is one atomic step: of two concurrent transforms one
        # gets the buffer and the other allocates
        held = self.__dict__.pop("_tail_buffer", None)
        if (held is not None and held[0].shape == shape
                and held[0].dtype == piece.dtype):
            buf, dirty = held
            # padded rows are zeros, always: a model may read the whole
            # batch (the int8 path's batch-wide activation scale)
            buf[real:dirty] = 0
            how = "held"
        else:
            buf, how = np.zeros(shape, piece.dtype), "new"
        buf[:real] = piece
        return buf, how

    def _transform_spanned(self, df, root, child):
        with child("prep"):
            x = self._coerce_input(df[self.getInputCol()])
        n = x.shape[0]
        if n == 0:
            raise ValueError("TPUModel.transform: the input column has "
                             "no rows")
        bs = self.get("minibatchSize")
        run = self._apply_fn()

        fetch = self.get("fetchDict") or {
            self.get("outputNode"): self.getOutputCol()}
        flatten = self.get("convertOutputToDenseVector")

        # the output columns, one array per endpoint: allocated when
        # minibatch 0 is launched (its shapes are known without waiting)
        # and written by each drain while the next minibatch computes
        columns: dict[str, np.ndarray] = {}
        tail, tail_how = None, "none"
        bytes_in = bytes_out = 0

        def drain(entry):
            nonlocal bytes_out
            k, real, out = entry
            start = k * bs
            with child("drain", minibatch=k) as span:
                pulled = 0
                for endpoint in fetch:
                    host = np.asarray(out[endpoint])
                    pulled += host.nbytes
                    dst = columns[endpoint]
                    # the assignment casts to the column's float32
                    dst[start:start + real] = host[:real].reshape(
                        (real,) + dst.shape[1:])
                span.set_attr("bytes", pulled)
            bytes_out += pulled

        # pipelined dispatch: pulling a batch's outputs blocks the
        # host, so keep the next batch(es) already dispatched before
        # pulling — device compute overlaps the host-side pull + prep
        # (the input-pipeline overlap a per-batch sync loop forfeits)
        depth = int(self.get("pipelineDepth"))
        if depth < 2:
            raise ValueError(
                f"pipelineDepth={depth} must be >= 2 (one batch "
                "computing while one drains); there is no synchronous "
                "mode")
        inflight: list[tuple[int, int, dict]] = []
        for k, start in enumerate(range(0, n, bs)):
            with child("stage", minibatch=k):
                piece = x[start:start + bs]
                real = piece.shape[0]
                if real < bs:  # pad tail to the compiled shape
                    piece, tail_how = self._padded_tail(piece, bs)
                    tail = piece
            with child("put", minibatch=k, bytes=piece.nbytes):
                batch = jnp.asarray(piece)
            with child("launch", minibatch=k):
                out = run(batch)
                del batch
                if not isinstance(out, dict):
                    out = {self.get("outputNode"): out}
                for endpoint in fetch:
                    if endpoint not in out:
                        raise KeyError(
                            f"endpoint {endpoint!r} not in model outputs "
                            f"{sorted(out)}")
                    if k == 0:
                        row = out[endpoint].shape[1:]
                        if flatten and len(row) > 1:
                            row = (math.prod(row),)
                        columns[endpoint] = np.empty((n,) + row, np.float32)
            bytes_in += piece.nbytes
            inflight.append((k, real, out))
            if len(inflight) >= depth:
                drain(inflight.pop(0))
        for entry in inflight:
            drain(entry)
        if tail is not None:
            # the tail was the last minibatch and is drained: nothing
            # reads the buffer any more (an error above drops it instead)
            self._tail_buffer = (tail, n % bs)

        copied = 0
        with child("collect"):
            for endpoint, out_col in fetch.items():
                df = df.with_column(out_col, columns[endpoint])
                if not np.may_share_memory(df[out_col], columns[endpoint]):
                    copied += columns[endpoint].nbytes
        minibatches = -(-n // bs)
        root.attrs.update(rows=n, minibatches=minibatches,
                          padded_rows=minibatches * bs,
                          bytes_in=bytes_in, bytes_out=bytes_out,
                          collect_copied_bytes=copied, tail_buffer=tail_how)
        return df

    def _coerce_input(self, col) -> np.ndarray:
        mode = self.get("transferDtype")
        if mode not in ("auto", "uint8", "bfloat16", "float32"):
            raise ValueError(
                f"unknown transferDtype {mode!r}; expected "
                "auto|uint8|bfloat16|float32")
        if isinstance(col, np.ndarray) and col.dtype != object:
            # uint8 survives every narrowing mode: bfloat16 would DOUBLE
            # a uint8 column's wire bytes if it forced the float path
            keep_u8 = mode in ("auto", "uint8", "bfloat16") \
                and col.dtype == np.uint8
            x = col if keep_u8 else np.asarray(col, np.float32)
        else:
            x = np.stack([np.asarray(a, np.float32) for a in col])
        if mode == "bfloat16" and x.dtype == np.float32:
            # device compute is bf16 in every zoo model, so narrowing on
            # the host wire loses nothing the MXU would have kept — and
            # host->device bytes halve
            import ml_dtypes
            x = x.astype(ml_dtypes.bfloat16)
        shape = self.get("inputShape")
        if shape is not None and x.ndim == 2:
            # unrolled CHW vectors → NHWC images (undo UnrollImage)
            H, W, C = shape
            x = x.reshape(x.shape[0], C, H, W).transpose(0, 2, 3, 1)
        return x
