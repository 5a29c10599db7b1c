"""paddle_tpu: a TPU-native deep-learning framework with the capabilities
of Fluid-era PaddlePaddle (reference: breeze1982/Paddle, read-only at
/root/reference — studied for behavior/API, re-designed for TPU).

Architecture (vs. the reference, SURVEY.md §7):
  * Program IR (paddle_tpu/fluid/framework.py) — pure-Python serializable
    graph instead of a C++ protobuf + Python mirror pair.
  * Op lowering registry (paddle_tpu/ops/) — op -> jax/XLA emitter instead
    of per-(place,dtype,layout) kernel registries.
  * Executor (paddle_tpu/fluid/executor.py) — whole-block jit compilation
    instead of a per-op interpreter.
  * append_backward (paddle_tpu/fluid/backward.py) — grad-op synthesis via
    cached jax.vjp instead of 650 hand-written GradOpMakers.
  * Distributed (paddle_tpu/parallel/, paddle_tpu/distributed/) — device
    meshes + XLA collectives over ICI instead of NCCL rings + program
    transpilers.
"""

from __future__ import annotations

import time as _time

# the phase `setup.import` runs from here to this file's last line
# (`profiler.add_phase` there: no stage can open before `profiler` is
# imported); `_imported` closes the child phase of a subpackage's line
_IMPORT_MARKS = [("", _time.perf_counter())]


def _imported(name):
    _IMPORT_MARKS.append((name, _time.perf_counter()))


__version__ = "0.1.0"

from . import fluid
_imported("fluid")
from . import ops
_imported("ops")
from . import nn
_imported("nn")
from . import optimizer
_imported("optimizer")
from . import tensor
_imported("tensor")
from . import jit
_imported("jit")
from . import models
_imported("models")
from . import amp
_imported("amp")
from . import io
_imported("io")
from . import metric
_imported("metric")
from . import hapi
_imported("hapi")
from .hapi import Model, summary
from .framework_io import load, save
_imported("framework_io")
from . import distribution
_imported("distribution")
from . import vision
_imported("vision")
from . import text
_imported("text")
from . import dataset
_imported("dataset")
from . import inference
_imported("inference")
from . import transforms
_imported("transforms")
from . import profiler
_imported("profiler")
from . import obs
_imported("obs")
from . import ckpt
_imported("ckpt")
from . import utils
_imported("utils")
from . import reader
_imported("reader")
from .batch import batch
from . import static
_imported("static")
from . import onnx
_imported("onnx")
from .fluid.flags import get_flags, set_flags
from .nn.layer.layers import Layer  # 2.0 alias: paddle.nn.Layer
from .tensor import (to_tensor, zeros, ones, full, zeros_like, ones_like,
                     full_like, arange, linspace, eye, rand, randn, randint,
                     randperm, uniform, normal, bernoulli, multinomial,
                     seed, concat, stack, split, squeeze, unsqueeze,
                     reshape, transpose, flatten, cast, matmul, bmm, dot,
                     mv, t, kron, addmm, tril, triu, diag, meshgrid, where,
                     nonzero, unique, flip, roll, tile, expand, expand_as,
                     broadcast_to, gather, gather_nd, scatter,
                     scatter_nd_add, index_select, index_sample,
                     masked_select, argmax, argmin, argsort, sort, topk,
                     add, subtract, multiply, divide, pow, clip, scale,
                     isnan, isinf, isfinite, norm, dist, equal, not_equal,
                     greater_than, greater_equal, less_than, less_equal,
                     logical_and, logical_or, logical_not, logical_xor,
                     equal_all, allclose, cumsum, cumprod, assign, clone,
                     numel, std, var, median, logsumexp, sum, mean, prod,
                     exp, log, sqrt, rsqrt, abs, ceil, floor, round, sin,
                     cos, tan, tanh, reciprocal, square, sign, erf,
                     maximum, minimum)
from .tensor import max, min  # noqa: A004 (paddle API shadows builtins)
# 2.0 top-level API tail (reference python/paddle/__init__.py
# DEFINE_ALIAS set): re-exports of existing lowerings + the small
# additions at the end of paddle_tpu/tensor
from .tensor import (acos, asin, atan, cosh, sinh, log1p, log2, log10,
                     mod, remainder, floor_divide, floor_mod, trace,
                     cross, cholesky, histogram, increment, is_empty,
                     empty, empty_like, chunk, stanh, shard_index,
                     unstack, strided_slice, add_n, addcmul,
                     broadcast_shape, einsum, has_inf, has_nan,
                     inverse, is_tensor, mm, multiplex, rank,
                     scatter_nd, tensordot, unbind, set_default_dtype,
                     get_default_dtype, set_printoptions,
                     get_tensor_from_selected_rows)
from .tensor import all, any, slice  # noqa: A004 (shadows builtins)
from .fluid import (CUDAPinnedPlace, LoDTensor, LoDTensorArray,
                    is_compiled_with_cuda)
from .fluid.layers import (create_global_var, create_parameter,
                           elementwise_add, elementwise_sub,
                           elementwise_mul, elementwise_div,
                           elementwise_floordiv, elementwise_mod,
                           elementwise_pow, fill_constant, reduce_max,
                           reduce_mean, reduce_min, reduce_prod,
                           reduce_sum, shape)
from .fluid.dygraph.parallel import DataParallel


def get_cuda_rng_state():
    """No CUDA generators on this build (TPU-first; RNG is stateless
    jax keys / the TPU hardware generator) — the reference returns a
    list of per-device generator states, so the TPU answer is the
    empty list."""
    return []


def set_cuda_rng_state(state_list):
    if state_list:
        raise ValueError(
            "set_cuda_rng_state: this build has no CUDA generators "
            "(TPU-first, stateless jax PRNG); only an empty state list "
            "is accepted.")
from .fluid.dygraph.base import enable_dygraph as disable_static_mode
from .fluid.dygraph import to_variable, no_grad, grad
from .fluid.dygraph.varbase import Tensor
from .fluid import (CPUPlace, CUDAPlace, TPUPlace, Executor, ParamAttr,
                    Program, Variable, append_backward, cpu_places,
                    cuda_places, default_main_program,
                    default_startup_program, global_scope, program_guard,
                    scope_guard, tpu_places, in_dygraph_mode)
from .fluid.layers.tensor import data

def enable_static():
    from .fluid.dygraph import disable_dygraph

    disable_dygraph()


def disable_static(place=None):
    from .fluid.dygraph import enable_dygraph

    enable_dygraph(place)
from . import incubate  # noqa: E402,F401
_imported("incubate")


def _record_import_phases():
    """`setup.import` and a child `setup.import/<subpackage>` for each
    marked line (a child runs from the mark before it: lines between
    two marks import what is loaded already); timer `import_ms`."""
    end = _time.perf_counter()
    start = _IMPORT_MARKS[0][1]
    for (_, t0), (name, t1) in zip(_IMPORT_MARKS, _IMPORT_MARKS[1:]):
        profiler.add_phase("setup.import/" + name, t0, t1 - t0,
                           parent="setup.import")
    profiler.add_phase("setup.import", start, end - start)
    profiler.time_add("import_ms", (end - start) * 1e3)


_record_import_phases()
