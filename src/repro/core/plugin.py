"""Plugin base classes + drivers (paper §III.F).

A plugin is an independent processing step.  It declares how many
in/out datasets it needs, sets up its out_datasets (shape, axis labels,
patterns) in ``setup``, and implements a pure ``process_frames`` that
maps m input frames -> m output frames.  The framework owns all data
movement; the plugin never sees more than its requested frames.

Drivers (paper §III.F.1): the CPU driver lets every process run the
plugin; the GPU driver restricts execution to a sub-communicator.  In
the mesh adaptation a driver names the mesh axes the plugin's jit may
shard over — ``MeshDriver(axes=("data",))`` is the CPU-driver analogue
(everyone participates along ``data``); a reduced driver such as
``MeshDriver(axes=("model",))`` or a sub-mesh driver reproduces the
GPU-communicator behaviour.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Sequence

import numpy as np

from .dataset import DataSet


@dataclasses.dataclass(frozen=True)
class MeshDriver:
    """Names the mesh axes a plugin distributes over."""
    axes: tuple[str, ...] = ("data",)
    #: run on a sub-mesh only (e.g. GPU-driver analogue); empty = all
    submesh: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def data_axis(self) -> str | None:
        return self.axes[0] if self.axes else None


CPU_DRIVER = MeshDriver(axes=("data",))
GPU_DRIVER = MeshDriver(axes=("data",), submesh={"model": 1})


@dataclasses.dataclass
class PluginData:
    """Per-plugin view onto a dataset (paper §III.F.4): which access
    pattern and how many frames per processing call."""
    dataset: DataSet
    pattern_name: str = ""
    n_frames: int = 1
    #: frame-padding in core dims: {axis_label: pad} (framework applies)
    padding: dict[str, int] = dataclasses.field(default_factory=dict)
    #: True when this plugin step is the dataset's FINAL consumer — the
    #: runner sets it from its liveness analysis in ``begin_step``; a
    #: donating transport may only donate an input buffer whose view has
    #: ``last_use=True`` (a branching chain reads it again otherwise).
    #: Defaults to True so direct transport use keeps eager donation.
    last_use: bool = True

    @property
    def pattern(self):
        return self.dataset.get_pattern(self.pattern_name)


class BasePlugin:
    """Base of all plugins.  Subclass one of BaseFilter/BaseRecon/
    BaseLoader/BaseSaver rather than this directly."""

    name: str = "base_plugin"
    n_in_datasets: int = 1
    n_out_datasets: int = 1
    #: pattern for out_datasets when it differs from the input pattern
    #: (e.g. recon: SINOGRAM in, VOLUME_XZ out); None = same as input.
    out_pattern_name: str | None = None
    driver: MeshDriver = CPU_DRIVER
    #: user-tunable parameters with defaults; overridden per process-list
    parameters: dict[str, Any] = {}
    #: params that select WHICH data is processed (file path, scan seed)
    #: rather than HOW — excluded from the chain signature so jobs over
    #: different datasets still count as "the same pipeline"
    data_params: tuple[str, ...] = ()
    #: *tunable* params — Savu-style parameter-tuning candidates (filter
    #: cutoff, Paganin tau, ring strength...).  Declaring a param here is
    #: the same contract as ``data_params``: its effect on
    #: ``process_frames`` flows ONLY through :meth:`jit_constants`
    #: (arrays/floats built in ``setup``), never as a static trace-time
    #: value.  Tunables are excluded from both the chain signature and
    #: the compile-cache signature, so a parameter sweep expands into
    #: variant jobs with IDENTICAL chains that gang-batch and share one
    #: compiled program (see ``repro.service.sweep``).
    tunable_params: tuple[str, ...] = ()
    #: instance attrs that must stay trace-time constants even though
    #: they are arrays/floats (e.g. a float used in python control flow
    #: inside process_frames) — excluded from jit_constants and folded
    #: into the cache key instead
    static_attrs: tuple[str, ...] = ()

    def __init__(self, **params):
        self.params = {**self.__class__.parameters}
        unknown = set(params) - set(self.params) - {"in_datasets",
                                                    "out_datasets"}
        if unknown:
            raise ValueError(
                f"plugin {self.name!r}: unknown parameters {sorted(unknown)} "
                f"(valid: {sorted(self.params)})")
        self.params.update({k: v for k, v in params.items()
                            if k not in ("in_datasets", "out_datasets")})
        #: dataset names, filled from the process list at check time
        self.in_dataset_names: list[str] = list(params.get("in_datasets", []))
        self.out_dataset_names: list[str] = list(params.get("out_datasets", []))
        #: PluginData views, attached by the framework when plugged in
        self.in_data: list[PluginData] = []
        self.out_data: list[PluginData] = []

    # -- mandatory interface ------------------------------------------
    def setup(self, in_datasets: list[DataSet]) -> list[DataSet]:
        """Describe out_datasets given in_datasets, and set the pattern +
        n_frames on every PluginData.  Default: single in -> single out of
        identical shape, same patterns, first pattern, 1 frame."""
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0])
        pat = self.default_pattern(din)
        self.chunk_frames(pat)
        return [dout]

    def process_frames(self, frames: Sequence[Any]) -> Any:
        """Pure function: list of per-in-dataset frame blocks -> per-out
        blocks.  Each block has shape (m, *core_shape).  Must be jax-
        traceable for the sharded transport."""
        raise NotImplementedError

    # -- optional hooks -------------------------------------------------
    def pre_process(self) -> None:  # once, before the frame loop
        pass

    def post_process(self) -> None:  # once, after an implicit barrier
        pass

    # -- helpers ---------------------------------------------------------
    def default_pattern(self, din: DataSet) -> str:
        if not din.patterns:
            raise ValueError(f"dataset {din.name!r} has no patterns")
        return next(iter(din.patterns))

    def chunk_frames(self, pattern_name: str, n_frames: int = 1) -> None:
        """Set pattern/nframes on all attached PluginData (in then out)."""
        for pd in self.in_data + self.out_data:
            pd.pattern_name = pattern_name
            pd.n_frames = n_frames

    def get_param(self, key: str):
        return self.params[key]

    @classmethod
    def param_spec(cls) -> dict[str, Any]:
        """Introspect this plugin class for the service layer's wire
        format (``repro.service.wire``): declared parameters with their
        defaults, which of them are ``data_params``, and the dataset
        arity.  Everything returned is JSON-serialisable so a remote
        client can discover the registry via ``GET /plugins``.

        Returns:
            dict with ``name`` (wire name), ``doc`` (first docstring
            line), ``n_in_datasets``/``n_out_datasets``, and ``params``
            mapping each parameter to ``{"default", "data_param",
            "sweepable"}`` (non-JSON defaults are shown as their
            ``repr``; ``sweepable`` marks ``tunable_params`` — the only
            ones a parameter sweep may grid over).
        """
        params = {}
        for k, v in cls.parameters.items():
            params[k] = {"default": v if _is_jsonable(v) else repr(v),
                         "data_param": k in cls.data_params,
                         "sweepable": k in cls.tunable_params}
        doc = (cls.__doc__ or "").strip().splitlines()
        return {"name": cls.name,
                "doc": doc[0] if doc else "",
                "n_in_datasets": cls.n_in_datasets,
                "n_out_datasets": cls.n_out_datasets,
                "params": params}

    # -- compile-cache support (service layer) --------------------------
    #: instance attrs that never feed process_frames
    _NON_CONST_ATTRS = frozenset({
        "params", "in_dataset_names", "out_dataset_names",
        "in_data", "out_data"})

    def jit_constants(self) -> dict[str, Any]:
        """Setup-derived values that ``process_frames`` reads off ``self``
        and that VARY with the input data (dark/flat fields, filter
        banks, angles, scalar calibrations...).  The sharded transport
        passes these as jit *arguments* rather than letting them bake in
        as trace-time constants, so one compiled function serves every
        plugin instance with the same :meth:`cache_signature` — the
        paper's "same pipeline, many datasets" case.

        Default: every instance attribute that is an array or a python
        float.  ints/strs/bools stay static (they select shapes/branches)
        and are folded into :meth:`cache_signature` instead."""
        consts: dict[str, Any] = {}
        for k, v in vars(self).items():
            if k in self._NON_CONST_ATTRS or k in self.static_attrs:
                continue
            if isinstance(v, np.ndarray) or (
                    hasattr(v, "dtype") and hasattr(v, "shape")
                    and hasattr(v, "__array__") and not isinstance(v, DataSet)):
                consts[k] = v
            elif isinstance(v, float) and not isinstance(v, bool):
                consts[k] = v
        return consts

    def cache_signature(self) -> tuple:
        """Hashable static identity of this plugin for the compile cache:
        class + jsonable params + static (int/str/bool/None) attrs.  Two
        instances with equal signatures, equal in/out dataset specs and
        structurally-equal :meth:`jit_constants` may share one compiled
        function.  ``data_params`` and ``tunable_params`` are excluded:
        declaring a param in either is a contract that its effect on
        ``process_frames`` flows ONLY through :meth:`jit_constants`
        (arrays/floats built in setup), never as a static trace-time
        value — which is what lets a parameter sweep's variants share
        one compiled program."""
        sig_params: dict[str, Any] = {}
        unsignable: list[tuple] = []
        for k, v in sorted(self.params.items()):
            if k in self.data_params or k in self.tunable_params:
                continue
            if _is_jsonable(v):
                sig_params[k] = v
            else:
                # a param we cannot fingerprint (callable, object...) —
                # pin the entry to THIS instance's value rather than
                # silently sharing a compiled program across different
                # behaviours; declare it in data_params if it is data
                unsignable.append((k, type(v).__qualname__, id(v)))
        params_j = json.dumps(sig_params, sort_keys=True)
        statics = tuple(
            (k, repr(v))
            for k, v in sorted(vars(self).items())
            if k not in self._NON_CONST_ATTRS
            and (isinstance(v, (bool, int, str, type(None)))
                 or k in self.static_attrs
                 # jsonable containers (e.g. a kernel list derived in
                 # setup) are trace-time constants too — key on them so
                 # differing values never share a program
                 or (isinstance(v, (list, tuple, dict))
                     and _is_jsonable(v))))
        return (f"{type(self).__module__}.{type(self).__qualname__}",
                params_j, tuple(unsignable), statics)

    def persistable(self) -> bool:
        """Whether another process may reuse a compiled step of this
        plugin (the persistent executable tier).  Only when its
        signature names no process-local object and its class is part
        of this package, whose source the tier's fingerprint covers."""
        return (type(self).__module__.startswith("repro.")
                and not self.cache_signature()[2])

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


def _is_jsonable(v) -> bool:
    try:
        json.dumps(v)
        return True
    except TypeError:
        return False


class BaseFilter(BasePlugin):
    """1-in 1-out, same shape — the common filter plugin type."""
    name = "base_filter"
    pattern_name: str | None = None   # subclass fixes its space
    frames: int = 1

    def setup(self, in_datasets):
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0])
        pat = self.pattern_name or self.default_pattern(din)
        self.chunk_frames(pat, self.frames)
        return [dout]


class BaseRecon(BasePlugin):
    """Sinogram-in, volume-slice-out reconstruction plugins."""
    name = "base_recon"


class BaseLoader(BasePlugin):
    """Creates DataSets lazily (paper: loader loads *information*, not
    data).  ``load`` returns fully-described datasets whose backing may be
    a thunk."""
    name = "base_loader"
    n_in_datasets = 0

    def setup(self, in_datasets):  # loaders use load() instead
        raise RuntimeError("loaders use .load()")

    def load(self) -> list[DataSet]:
        raise NotImplementedError

    def process_frames(self, frames):
        raise RuntimeError("loaders do not process frames")


class BaseSaver(BasePlugin):
    """Persists datasets; called after loaders, retains a link with the
    framework until the chain completes (paper §III.F.2)."""
    name = "base_saver"
    n_out_datasets = 0

    def setup(self, in_datasets):
        self.chunk_frames(self.default_pattern(in_datasets[0]))
        return []

    def create(self, dataset: DataSet, now, next_) -> None:
        """Allocate backing storage for an out_dataset (chunked)."""
        raise NotImplementedError

    def save(self, dataset: DataSet) -> None:
        raise NotImplementedError

    def process_frames(self, frames):
        raise RuntimeError("savers do not process frames")


# ----------------------------------------------------------------------
class LambdaFilter(BaseFilter):
    """Quick functional filter: wraps fn(block)->block (testing/examples)."""
    name = "lambda_filter"

    def __init__(self, fn: Callable, pattern: str | None = None,
                 frames: int = 1, out_dtype=None, **params):
        super().__init__(**params)
        self._fn = fn
        self.pattern_name = pattern
        self.frames = frames
        self._out_dtype = out_dtype

    def setup(self, in_datasets):
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0],
                        dtype=self._out_dtype or din.dtype)
        pat = self.pattern_name or self.default_pattern(din)
        self.chunk_frames(pat, self.frames)
        return [dout]

    def process_frames(self, frames):
        return self._fn(frames[0])

    _fn_tokens = iter(range(1, 1 << 62))

    def persistable(self) -> bool:
        return False          # the token below is local to this process

    def cache_signature(self):
        # the wrapped callable is invisible to the default signature;
        # pin the cache entry to this exact function object via a token
        # stored ON the function (id() values can be recycled after GC,
        # which would alias a dead lambda's compiled program)
        try:
            token = self._fn.__savu_cache_token__
        except AttributeError:
            token = next(LambdaFilter._fn_tokens)
            try:
                self._fn.__savu_cache_token__ = token
            except (AttributeError, TypeError):
                token = ("id", id(self._fn))   # unpinnable callable
        return super().cache_signature() + (
            ("fn", getattr(self._fn, "__qualname__", "?"), token),)
