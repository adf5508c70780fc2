"""mxnet_tpu: a TPU-native deep-learning framework with classic-MXNet
capabilities (NDArray, Symbol/Executor, Module, KVStore, data iterators)
rebuilt idiomatically on JAX/XLA/Pallas.  See SURVEY.md for the mapping
to the reference architecture."""

import os as _os

import jax as _jax

# The reference framework supports float64 end to end (mshadow type switch);
# enable x64 so dtype parity holds.  Weak-typed python scalars still keep
# float32 results in f32 graphs, so TPU perf paths are unaffected.
_jax.config.update("jax_enable_x64", True)

from . import base
from .base import MXNetError
from . import aot

# Persist XLA compiles across processes (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache; off for a CPU-pinned process).  Wired before
# any jit can run so the first compile of the process already
# reads/writes the cache (docs/how_to/startup.md).
aot.enable_from_env()
from .context import Context, cpu, cpu_pinned, current_context, gpu, tpu, num_devices
from . import engine
from . import random
from . import telemetry
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from .symbol import AttrScope, Variable, Group
from . import attribute
from . import executor
from . import executor_manager
from .executor import Executor
from . import initializer
from . import initializer as init  # reference: mx.init.Xavier() etc.
from . import optimizer
from . import optimizer as opt
from . import metric
from . import lr_scheduler
from . import callback
from . import misc
from . import monitor
from . import monitor as mon  # reference: mx.mon.Monitor
from . import profiler
from . import io
from . import recordio
from . import rnn_io
from . import image_io
from .image_io import ImageRecordIter
from . import cv

io.ImageRecordIter = ImageRecordIter  # reference exposes it under mx.io
from . import kvstore
from . import kvstore as kv
from . import kvstore_server
from . import model
from .model import FeedForward
from . import module
from . import module as mod
from . import visualization
from . import visualization as viz
# notebook (PandasLogger/LiveLearningCurve) is imported on demand, like
# the reference: `from mxnet_tpu.notebook import callback`
from . import test_utils
from . import operator
from . import rtc
from . import resource
from . import caffe
from . import sframe
from . import symbol_doc
from . import parallel
from . import models
from . import predict
from . import serve
from . import fleet
from . import torch_bridge
from . import c_api

# publish the op registry through the native C ABI so in-process
# non-Python frontends can discover ops (reference: frontends enumerate
# ops via MXSymbolListAtomicSymbolCreators at import)
try:
    c_api.publish_registry()
# mxtpu-lint: disable=swallowed-exception (native lib is optional;
# frontends fall back to the pure-Python registry)
except Exception:
    pass

__version__ = "0.1.0"
