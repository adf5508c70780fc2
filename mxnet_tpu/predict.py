"""Predict-only API + standalone deploy artifacts.

TPU-native rebuild of the reference's predict mini-API and amalgamation
deploy story:

- ``Predictor`` mirrors the C predict API surface
  (include/mxnet/c_predict_api.h, src/c_api/c_predict_api.cc:1-305):
  create from symbol JSON + a param blob, set named inputs, ``forward``,
  ``partial_forward``, fetch output shapes/values, ``reshape`` to new
  input shapes.  Where the reference forces the Naive engine under
  ``MXNET_PREDICT_ONLY`` (base.h:68, engine.cc:28-30), here inference is
  a single fused, donation-friendly XLA program — there is no scheduler
  to strip out.
- ``export_model`` / ``ExportedPredictor`` replace amalgamation
  (amalgamation/: one-file predict-only build for mobile/JS): the
  deployable artifact is a serialized StableHLO executable
  (``jax.export``) plus the param tree.  Loading it needs only jax —
  none of the Symbol/graph machinery — which is the XLA-era equivalent
  of compiling the mini predict runtime into one object.
"""

from __future__ import annotations

import io
import json
import os
import zipfile

import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError, np_dtype
from .context import current_context

__all__ = ["Predictor", "create", "export_model", "load_exported",
           "ExportedPredictor"]


def _split_params(param_dict):
    """Split an ``arg:``/``aux:`` prefixed blob (model.save_checkpoint
    naming, reference model.py:318-347) into (arg_params, aux_params)."""
    arg_params, aux_params = {}, {}
    for k, v in param_dict.items():
        if k.startswith("arg:"):
            arg_params[k[4:]] = v
        elif k.startswith("aux:"):
            aux_params[k[4:]] = v
        else:  # unprefixed blobs are treated as args (c_predict_api.cc:88-104)
            arg_params[k] = v
    return arg_params, aux_params


class Predictor:
    """Inference-only executor (reference ``MXPredCreate`` family).

    Parameters
    ----------
    symbol_json : str
        Symbol JSON string (or a path to one).
    params : dict | str | bytes
        ``arg:``/``aux:``-prefixed param dict, or the path of a
        ``.params`` blob saved by ``save_checkpoint``.
    input_shapes : dict(name -> shape)
        Shapes for the data inputs; remaining shapes are inferred
        (partial-shape support, c_predict_api.h MXPredCreatePartialOut).
    ctx : Context, optional
    dtype : optional
        Cast parameters to this dtype (e.g. ``"bfloat16"`` for MXU-
        friendly serving).
    """

    def __init__(self, symbol_json, params, input_shapes, ctx=None, dtype=None):
        ctx = ctx or current_context()
        if os.path.exists(symbol_json):
            with open(symbol_json) as f:
                symbol_json = f.read()
        self.symbol = sym_mod.load_json(symbol_json)
        if isinstance(params, (str, os.PathLike)):
            params = nd.load(params)
        elif isinstance(params, bytes):
            params = nd.load(io.BytesIO(params))
        arg_params, aux_params = _split_params(params)
        self._arg_params = {k: (v if isinstance(v, nd.NDArray)
                                else nd.array(v, ctx=ctx)) for k, v in arg_params.items()}
        self._aux_params = {k: (v if isinstance(v, nd.NDArray)
                                else nd.array(v, ctx=ctx)) for k, v in aux_params.items()}
        if dtype is not None:
            dt = np_dtype(dtype)
            self._arg_params = {k: v.astype(dt) for k, v in self._arg_params.items()}
        self._ctx = ctx
        self._dtype = dtype
        self.output_names = self.symbol.list_outputs()
        self._bind(dict(input_shapes))

    def _bind(self, input_shapes):
        self._input_shapes = dict(input_shapes)
        arg_names = self.symbol.list_arguments()
        free_names = [n for n in arg_names if n not in self._arg_params]
        # like MXPredCreate, only data inputs need shapes; other free
        # variables (e.g. output-layer labels) are inferred and zero-filled
        # (c_predict_api.cc partial-shape handling)
        self._data_names = [n for n in free_names if n in input_shapes]
        if not self._data_names:
            raise MXNetError(
                f"input_shapes must cover at least one data input "
                f"(free inputs: {free_names})")
        arg_shapes, _, aux_shapes = self.symbol.infer_shape_partial(
            **input_shapes)
        unknown = [n for n, s in zip(arg_names, arg_shapes)
                   if n in free_names and n not in input_shapes
                   and (s is None or any(d == 0 for d in s))]
        if unknown:
            raise MXNetError(
                f"input_shapes missing entries for data inputs {unknown}")
        args = {}
        for name, shape in zip(arg_names, arg_shapes):
            if name in self._arg_params:
                p = self._arg_params[name]
                if tuple(p.shape) != tuple(shape):
                    raise MXNetError(
                        f"param {name!r} shape {p.shape} != inferred {shape}")
                args[name] = p
            else:
                dt = np_dtype(self._dtype) if self._dtype else np.float32
                args[name] = nd.zeros(shape, ctx=self._ctx, dtype=dt)
        aux = {}
        for name, shape in zip(self.symbol.list_auxiliary_states(), aux_shapes):
            if name in self._aux_params:
                aux[name] = self._aux_params[name]
            else:
                aux[name] = nd.zeros(shape, ctx=self._ctx)
        self._exec = self.symbol.bind(self._ctx, args, aux_states=aux,
                                      grad_req="null")
        self._internals_exec = None
        self._partial_step = 0

    # -- C predict API surface ----------------------------------------------
    def set_input(self, name, value):
        """``MXPredSetInput``: copy a named input into the bound array."""
        if name not in self._data_names:
            raise MXNetError(f"{name!r} is not a data input "
                             f"(inputs: {self._data_names})")
        self._exec.arg_dict[name][:] = value

    def forward(self, **kwargs):
        """``MXPredForward``; kwargs set inputs first."""
        for k, v in kwargs.items():
            self.set_input(k, v)
        self._exec.forward(is_train=False)
        self._partial_step = 0
        return self._exec.outputs

    def partial_forward(self, step):
        """``MXPredPartialForward``: run through internal head ``step``.

        Returns the number of remaining steps (0 when the whole graph has
        run).  Internal outputs become available via ``get_internal``.
        """
        if self._internals_exec is None:
            internals = self.symbol.get_internals()
            arg_names = internals.list_arguments()
            args = {}
            for name in arg_names:
                if name in self._arg_params:
                    args[name] = self._arg_params[name]
                else:
                    args[name] = self._exec.arg_dict[name]
            aux = {name: self._aux_params.get(
                name, self._exec.aux_dict.get(name))
                for name in internals.list_auxiliary_states()}
            self._internals = internals
            self._internals_exec = internals.bind(
                self._ctx, args, aux_states=aux, grad_req="null")
        n = len(self._internals.list_outputs())
        if not 0 <= step < n:
            raise MXNetError(f"step {step} out of range [0, {n})")
        self._internals_exec.forward(is_train=False)
        self._partial_step = step
        return n - step - 1

    def get_internal(self, step=None):
        """Output of internal head ``step`` after ``partial_forward``."""
        if self._internals_exec is None:
            raise MXNetError("call partial_forward first")
        step = self._partial_step if step is None else step
        return self._internals_exec.outputs[step]

    def get_output_shape(self, index=0):
        """``MXPredGetOutputShape`` without running forward."""
        _, out_shapes, _ = self.symbol.infer_shape(**self._input_shapes)
        return tuple(out_shapes[index])

    def get_output(self, index=0):
        """``MXPredGetOutput``: copy output ``index`` to host numpy."""
        return self._exec.outputs[index].asnumpy()

    # -- flat-buffer accessors for the C predict API (src/predict_capi.cc)
    def set_input_flat(self, name, values):
        """Set input ``name`` from raw float32 bytes (or any flat float
        sequence) — the zero-boxing C ABI path."""
        shape = self._input_shapes[name]
        if isinstance(values, (bytes, bytearray, memoryview)):
            arr = np.frombuffer(values, dtype=np.float32).reshape(shape)
        else:
            arr = np.asarray(values, dtype=np.float32).reshape(shape)
        self.set_input(name, arr)

    def get_output_flat(self, index=0):
        """Output ``index`` as raw float32 bytes (C ABI path)."""
        return np.ascontiguousarray(
            self.get_output(index), dtype=np.float32).tobytes()

    def reshape(self, input_shapes):
        """``MXPredReshape``: rebind with new input shapes (weights kept)."""
        self._bind(dict(input_shapes))

    @property
    def data_names(self):
        return list(self._data_names)

    # -- deploy -------------------------------------------------------------
    def export(self, path, platforms=None):
        """Serialize this predictor into a standalone artifact (see
        ``export_model``)."""
        export_model(path, self.symbol, self._arg_params, self._aux_params,
                     self._input_shapes, dtype=self._dtype,
                     platforms=platforms)


def create(symbol_json, params, input_shapes, ctx=None, **kwargs):
    """``MXPredCreate`` analog."""
    return Predictor(symbol_json, params, input_shapes, ctx=ctx, **kwargs)


# ---------------------------------------------------------------------------
# Standalone deploy artifact (amalgamation analog)
# ---------------------------------------------------------------------------

_MANIFEST = "manifest.json"
_STABLEHLO = "model.stablehlo"
_PARAMS = "params.npz"
_SYMBOL = "symbol.json"


def export_model(path, symbol, arg_params, aux_params, input_shapes,
                 dtype=None, platforms=None):
    """Export (symbol, params) as one self-contained inference artifact.

    The artifact is a zip holding serialized StableHLO (``jax.export``)
    of the fused inference program, the flattened parameters, and a
    manifest — loadable with only jax + numpy (``load_exported``).  This
    is the TPU-era replacement for the amalgamation predict-only build
    (reference amalgamation/README; c_predict_api consumed by it).

    ``platforms`` (e.g. ``["cpu", "tpu"]``) lowers the artifact for
    several backends — the cross-compile analog of amalgamation's
    mobile targets.  Default: the current default jax backend only.
    Note the backends' numerics differ slightly (TPU matmuls default to
    bf16-accumulated passes), so outputs match per-platform, not across.
    """
    import jax

    from .executor import _CompiledGraph

    graph = _CompiledGraph(symbol)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    free_names = [n for n in arg_names if n not in arg_params]
    data_names = [n for n in free_names if n in input_shapes]
    arg_shapes, _, aux_shapes = symbol.infer_shape_partial(**input_shapes)
    shape_of = dict(zip(arg_names, arg_shapes))

    def as_np(v):
        return v.asnumpy() if isinstance(v, nd.NDArray) else np.asarray(v)

    params_np = {f"arg:{k}": as_np(v) for k, v in arg_params.items()}
    # non-data free inputs (labels) are baked in as zeros — unused at eval
    for n in free_names:
        if n not in data_names:
            params_np[f"arg:{n}"] = np.zeros(tuple(shape_of[n]), np.float32)
    params_np.update({f"aux:{k}": as_np(v) for k, v in aux_params.items()})
    if dtype is not None:
        dt = np_dtype(dtype)
        params_np = {k: (v.astype(dt) if k.startswith("arg:") else v)
                     for k, v in params_np.items()}

    def infer_fn(data, params):
        key = jax.random.PRNGKey(0)
        args = {k: params[f"arg:{k}"] for k in arg_names if k not in data_names}
        args.update(data)
        aux = {k: params[f"aux:{k}"] for k in aux_names}
        outs, _ = graph(args, aux, key, False)
        return outs

    data_dt = np_dtype(dtype) if dtype else np.float32
    data_spec = {n: jax.ShapeDtypeStruct(tuple(shape_of[n]), data_dt)
                 for n in data_names}
    param_spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in params_np.items()}
    kw = {"platforms": list(platforms)} if platforms else {}
    exported = jax.export.export(jax.jit(infer_fn), **kw)(
        data_spec, param_spec)
    manifest = {
        "format": "mxnet_tpu.exported_model.v1",
        "data_names": data_names,
        "input_shapes": {n: list(shape_of[n]) for n in data_names},
        "output_names": symbol.list_outputs(),
        "dtype": str(np.dtype(data_dt)),
    }
    from .ndarray import _encode_bf16

    buf = io.BytesIO()
    np.savez(buf, **_encode_bf16(params_np))
    # entries deliberately STORED (no deflate): the amalgamation C
    # runtime (amalgamation/mxtpu_predict.c) parses the zip + npz with
    # no zlib — one artifact serves both the jax loader and the
    # Python-free deploy target
    with zipfile.ZipFile(path, "w", compression=zipfile.ZIP_STORED) as zf:
        zf.writestr(_MANIFEST, json.dumps(manifest, indent=1))
        zf.writestr(_STABLEHLO, exported.serialize())
        zf.writestr(_PARAMS, buf.getvalue())
        zf.writestr(_SYMBOL, symbol.tojson())


class ExportedPredictor:
    """Runs an ``export_model`` artifact.  Needs only jax/numpy at load
    time — the graph is already compiled to StableHLO."""

    def __init__(self, path):
        import jax

        with zipfile.ZipFile(path) as zf:
            self.manifest = json.loads(zf.read(_MANIFEST))
            self._exported = jax.export.deserialize(
                zf.read(_STABLEHLO))
            from .ndarray import _decode_bf16

            with np.load(io.BytesIO(zf.read(_PARAMS))) as pz:
                self._params = _decode_bf16({k: pz[k] for k in pz.files})
        self.data_names = self.manifest["data_names"]
        self.output_names = self.manifest["output_names"]
        self._inputs = {}

    def set_input(self, name, value):
        if name not in self.data_names:
            raise MXNetError(f"{name!r} not an input ({self.data_names})")
        dt = np.dtype(self.manifest["dtype"]) if self.manifest["dtype"] != "bfloat16" \
            else np_dtype("bfloat16")
        self._inputs[name] = np.asarray(value, dtype=dt)

    def forward(self, **kwargs):
        for k, v in kwargs.items():
            self.set_input(k, v)
        missing = [n for n in self.data_names if n not in self._inputs]
        if missing:
            raise MXNetError(f"inputs not set: {missing}")
        self._outputs = self._exported.call(
            {n: self._inputs[n] for n in self.data_names}, self._params)
        return self._outputs

    def get_output(self, index=0):
        return np.asarray(self._outputs[index])


def load_exported(path):
    return ExportedPredictor(path)
