"""paddle_tpu.fluid — the Fluid-compatible static-graph front end,
re-designed TPU-native (see SURVEY.md §7 and per-module docstrings)."""

from __future__ import annotations

import numpy as np

from . import core, unique_name
from . import dataset
from .dataset import DatasetFactory, InMemoryDataset, QueueDataset
from .framework import (Program, Variable, Parameter, OpRole,
                        default_main_program, default_startup_program,
                        program_guard, in_dygraph_mode)
from .executor import (Executor, LazyFetch, Scope, global_scope,
                       scope_guard)
from .backward import append_backward, gradients
from . import initializer, regularizer, clip, io
from .param_attr import ParamAttr, WeightNormParamAttr
from . import layers
from . import optimizer
from .layers.tensor import data


class CPUPlace:
    """Host platform (place.h:26 in the reference)."""

    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    """TPU device identity — the new first-class Place the north star asks
    for (BASELINE.json).  device_id indexes jax.devices() (tpu_places(),
    mesh building); `Executor(TPUPlace(i))` reads the platform only — it
    runs on JAX's default device and raises when that is not a TPU."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"

    def jax_device(self):
        import jax

        return jax.devices()[self.device_id]


# CUDAPlace name kept as an alias so reference scripts run unchanged: on
# this framework "the accelerator" is the TPU.
CUDAPlace = TPUPlace


class CUDAPinnedPlace:
    """Pinned-host place (place.h:52).  On TPU, host staging is managed
    by the runtime (jax.device_put handles transfer layout), so this is
    an identity marker for API compatibility — feeds placed 'pinned'
    behave exactly like CPUPlace feeds."""

    def __repr__(self):
        return "CUDAPinnedPlace"


class LoDTensor:
    """Feed/fetch-side compat shim for the reference's LoDTensor
    (lod_tensor.h:114).  The TPU redesign carries dense arrays +
    explicit lengths/masks instead of LoD metadata (SURVEY.md §2.4 LoD
    N/A family); executors here feed/fetch numpy arrays directly.  This
    class keeps `t = fluid.LoDTensor(); t.set(arr, place)` scripts
    working: it wraps the array and preserves any recursive sequence
    lengths the caller attaches (for their own bookkeeping)."""

    def __init__(self):
        self._array = None
        self._lengths = []

    def set(self, array, place=None):
        self._array = np.asarray(array)

    def set_recursive_sequence_lengths(self, lengths):
        self._lengths = [list(l) for l in lengths]

    set_lod = set_recursive_sequence_lengths

    def recursive_sequence_lengths(self):
        return self._lengths

    lod = recursive_sequence_lengths

    def shape(self):
        return [] if self._array is None else list(self._array.shape)

    def __array__(self, dtype=None):
        a = self._array if self._array is not None else np.empty((0,))
        return a.astype(dtype) if dtype is not None else a


class LoDTensorArray(list):
    """Compat alias for the reference's LoDTensorArray (a vector of
    LoDTensor) — a plain list of arrays here."""


def tpu_places(device_ids=None):
    import jax

    n = len(jax.devices())
    ids = device_ids if device_ids is not None else range(n)
    return [TPUPlace(i) for i in ids]


cuda_places = tpu_places


def cpu_places(device_count=1):
    return [CPUPlace() for _ in range(device_count)]


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def device_count():
    import jax

    return len(jax.devices())


from ..parallel.compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: E402
from . import compiler  # noqa: E402
from . import contrib  # noqa: E402
from . import metrics  # noqa: E402,F401 - legacy host-side metric classes
