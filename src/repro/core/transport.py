"""Transports — who moves the data (paper §III.A / §IV).

Three interchangeable backends, selected at runner construction:

* :class:`InMemoryTransport` — the paper's "serial on a PC" mode; numpy
  frame loop, no jit.  Reference semantics for every test.
* :class:`ShardedTransport` — the cluster mode, adapted to TPU: each
  plugin (or fused group of plugins) is compiled with ``jax.jit`` under a
  device mesh; patterns provide in/out ``NamedSharding``s; pattern
  transitions become XLA collectives instead of parallel-file round trips.
* :class:`ChunkedFileTransport` — the faithful out-of-core mode: every
  dataset is a chunk-addressed file (np.memmap standing in for parallel
  HDF5) with an LRU chunk cache of the paper's 1 MB default; chunk layout
  comes from the §IV.A optimiser.  Read/write statistics feed the
  chunking benchmark.
"""
from __future__ import annotations

import dataclasses
import math
import os
import shutil
import tempfile
import time
from collections import OrderedDict
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..obs.trace import traced
from .chunking import DEFAULT_CACHE_BYTES, optimise_chunks
from .dataset import DataSet
from .patterns import Pattern
from .plugin import BasePlugin


def _named(fn, name: str):
    """``fn`` renamed ``name``: jit names its program ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class Transport:
    """Interface: allocate out-dataset backing + run one plugin."""

    name = "base"

    def allocate(self, ds: DataSet, now: Pattern, next_: Pattern | None
                 ) -> None:
        raise NotImplementedError

    def run_plugin(self, plugin: BasePlugin) -> list[Any]:
        """Execute plugin.process_frames over all frames.  The plugin's
        PluginData views (in_data/out_data) define patterns + m."""
        raise NotImplementedError

    def read(self, ds: DataSet) -> np.ndarray:
        """Materialise a dataset to host numpy (tests / savers)."""
        out = ds.materialise()
        return np.asarray(out)

    def stats(self) -> dict[str, Any]:
        """Service-layer hook: transport-specific counters (IO traffic,
        compile-cache hits...).  Keys are transport-defined."""
        return {}

    def close(self) -> None:
        pass


class LocalCompileCache:
    """Minimal per-transport compiled-function cache.  The service layer
    substitutes a process-level, thread-safe
    :class:`repro.service.CompileCache` via the ``compile_cache``
    constructor argument so that many concurrent pipelines share one
    cache (same duck type: ``get_or_build`` + ``stats``)."""

    def __init__(self):
        self._entries: dict = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key, builder, serializable=False,
                     step: str | None = None):
        # ``serializable`` marks builders whose output could go to the
        # process-level cache's persistent tier; the local cache has no
        # such tier and ignores it
        try:
            fn = self._entries[key]
            self.hits += 1
            return fn
        except KeyError:
            self.misses += 1
            # an actual build (not a hit) becomes a ``compile`` span on
            # whichever job is executing on this thread
            with traced("compile", step=step or "",
                        kind=key[0] if isinstance(key, tuple) and key
                        else "plugin"):
                fn = self._entries[key] = builder()
            return fn

    def stats(self) -> dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}


# ======================================================================
class InMemoryTransport(Transport):
    """Serial PC mode — numpy loop over frames, reference semantics."""

    name = "inmemory"

    def allocate(self, ds: DataSet, now, next_) -> None:
        ds.backing = np.zeros(ds.shape, dtype=ds.dtype)

    def run_plugin(self, plugin: BasePlugin) -> list[Any]:
        ins = [pd.dataset.materialise() for pd in plugin.in_data]
        in_pats = [pd.pattern for pd in plugin.in_data]
        out_pats = [pd.pattern for pd in plugin.out_data]
        m = plugin.in_data[0].n_frames if plugin.in_data else 1

        in_frames = [np.asarray(p.to_frames(a))
                     for p, a in zip(in_pats, ins)]
        nf = in_frames[0].shape[0]
        out_accum: list[list[np.ndarray]] = [[] for _ in plugin.out_data]
        for start in range(0, nf, m):
            blocks = [f[start:start + m] for f in in_frames]
            res = _as_list(plugin.process_frames(blocks))
            for i, r in enumerate(res):
                out_accum[i].append(np.asarray(r))
        outs = []
        for pd, pieces, pat in zip(plugin.out_data, out_accum, out_pats):
            flat = np.concatenate(pieces, axis=0)
            outs.append(np.asarray(pat.from_frames(flat, pd.dataset.shape)))
        for pd, o in zip(plugin.out_data, outs):
            pd.dataset.backing = o.astype(pd.dataset.dtype, copy=False)
        return outs


# ======================================================================
class ShardedTransport(Transport):
    """Mesh mode — one jit per plugin (or fused group), shardings from
    patterns.  This is Savu's MPI layer re-expressed as SPMD compilation:
    the slice dims shard over the driver's data axis, and a pattern
    change between consecutive plugins lowers to an all-to-all instead of
    an HDF5 round-trip."""

    name = "sharded"

    def __init__(self, mesh: Mesh, donate: bool = True,
                 compile_cache=None):
        self.mesh = mesh
        self.donate = donate
        self.compile_cache = (compile_cache if compile_cache is not None
                              else LocalCompileCache())

    def allocate(self, ds: DataSet, now: Pattern, next_: Pattern | None
                 ) -> None:
        # jit outputs allocate themselves; nothing to do (lazy, like the
        # paper's loaders).
        ds.backing = None

    def _sharding(self, pat: Pattern, data_axis: str | None) -> NamedSharding:
        axes = set(self.mesh.axis_names)
        da = data_axis if data_axis in axes else None
        spec = [None] * pat.ndim
        if pat.slice_dims and da:
            spec[pat.slice_dims[0]] = da
        for d, ax in pat.shard_axes.items():
            if ax in axes:
                spec[d] = ax
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def device_put(self, ds: DataSet, pattern_name: str | None = None,
                   data_axis: str = "data"):
        """Place a host dataset onto the mesh with its pattern sharding."""
        pat = (ds.get_pattern(pattern_name) if pattern_name
               else next(iter(ds.patterns.values())))
        ds.backing = self._put(ds.materialise(),
                               self._sharding(pat, data_axis))
        return ds.backing

    @staticmethod
    def _put(a, sharding: NamedSharding) -> jax.Array:
        """``a`` placed with ``sharding``; a host array's copy is a
        ``transfer.h2d`` span with its ``bytes``."""
        if isinstance(a, jax.Array):
            return jax.device_put(a, sharding)
        a = np.asarray(a)
        with traced("transfer.h2d", bytes=a.nbytes):
            return jax.device_put(a, sharding)

    def read(self, ds: DataSet) -> np.ndarray:
        """The dataset on the host; a device array's copy is a
        ``transfer.d2h`` span with its ``bytes``."""
        out = ds.materialise()
        if not isinstance(out, jax.Array):
            return np.asarray(out)
        with traced("transfer.d2h", bytes=out.nbytes):
            return np.asarray(out)

    def _check_divisible(self, plugin: BasePlugin) -> None:
        """Refuse datasets whose slices cannot be laid evenly over the
        mesh's data axis (each device holds an equal share)."""
        da = plugin.driver.data_axis
        n = self.mesh.shape[da] if da in self.mesh.axis_names else 1
        for pd in (*plugin.in_data, *plugin.out_data):
            dims = pd.pattern.slice_dims
            if dims and pd.dataset.shape[dims[0]] % n:
                raise ValueError(
                    f"plugin {plugin.name}: dataset {pd.dataset.name!r} has "
                    f"{pd.dataset.shape[dims[0]]} slices along dim "
                    f"{dims[0]} ({pd.pattern_name}), which do not divide "
                    f"over the {n} devices of mesh axis {da!r}")

    def _plugin_fn(self, plugin: BasePlugin):
        """Traceable (consts, *arrays) -> outs, named after the plugin
        (its program is ``jit_<plugin>`` on the device) and traced under
        ``jax.named_scope(<plugin>)``.  ``consts`` is the plugin's
        :meth:`jit_constants` dict passed as jit ARGUMENTS (not
        trace-time closure constants), so a compiled function can be
        replayed for a different plugin instance — same chain, new
        dataset — without retracing."""
        in_pats = [pd.pattern for pd in plugin.in_data]
        out_pats = [pd.pattern for pd in plugin.out_data]
        out_shapes = [pd.dataset.shape for pd in plugin.out_data]
        out_dtypes = [pd.dataset.dtype for pd in plugin.out_data]
        m = plugin.in_data[0].n_frames if plugin.in_data else 1
        const_keys = tuple(sorted(plugin.jit_constants()))
        self._check_divisible(plugin)

        def per_frames(consts, *frames):
            saved = {k: getattr(plugin, k) for k in const_keys}
            for k in const_keys:
                setattr(plugin, k, consts[k])
            try:
                nf = frames[0].shape[0]
                if m == 1:
                    res = jax.vmap(
                        lambda *fs: _as_list(
                            plugin.process_frames([f[None] for f in fs])),
                    )(*frames)
                else:
                    grouped = [f.reshape((nf // m, m) + f.shape[1:])
                               for f in frames]
                    res = jax.vmap(
                        lambda *fs: _as_list(plugin.process_frames(list(fs))),
                    )(*grouped)
                return tuple(r.reshape((nf,) + r.shape[2:]) for r in res)
            finally:
                for k, v in saved.items():
                    setattr(plugin, k, v)

        da = plugin.driver.data_axis
        n_shards = (self.mesh.shape[da] if da in self.mesh.axis_names
                    else 1)

        def fn(consts, *arrays):
            with jax.named_scope(plugin.name):
                return body(consts, *arrays)

        def body(consts, *arrays):
            frames = [p.to_frames(a) for p, a in zip(in_pats, arrays)]
            nf = frames[0].shape[0]
            if nf % m:
                raise ValueError(
                    f"sharded transport requires n_frames({m}) | "
                    f"total frames({nf}) for plugin {plugin.name}")
            if n_shards == 1:
                res = per_frames(consts, *frames)
            else:
                # frames are independent: each device maps its own share,
                # so per-frame work (FFTs, Pallas kernels, which the
                # partitioner cannot split) runs unpartitioned per
                # device.  The frame axis is zero-padded to whole groups
                # on every device and the padding cropped afterwards.
                pad = -nf % (n_shards * m)
                frames = [jnp.pad(f, ((0, pad),) + ((0, 0),) * (f.ndim - 1))
                          for f in frames]
                res = jax.shard_map(
                    per_frames, mesh=self.mesh,
                    in_specs=(PartitionSpec(),)
                    + (PartitionSpec(da),) * len(frames),
                    out_specs=PartitionSpec(da),
                    check_vma=False)(consts, *frames)
                res = [r[:nf] for r in res]
            outs = []
            for r, pat, shp, dt in zip(res, out_pats, out_shapes,
                                       out_dtypes):
                outs.append(pat.from_frames(r, shp).astype(dt))
            return tuple(outs)

        return _named(fn, plugin.name)

    def _donate_mask(self, plugin: BasePlugin) -> tuple[bool, ...]:
        """Per-input donation decision: donate only at the dataset's
        FINAL use (``PluginData.last_use``, set by the runner's liveness
        analysis; defaults True for direct transport use).  Donating
        earlier deletes a buffer a later plugin in a branching chain —
        or the checkpointer — still needs."""
        return tuple(self.donate and pd.last_use for pd in plugin.in_data)

    # -- compile-cache keys --------------------------------------------
    def _mesh_key(self) -> tuple:
        return (tuple(self.mesh.axis_names), tuple(self.mesh.devices.shape),
                tuple(int(d.id) for d in self.mesh.devices.flat))

    def _plugin_key(self, plugin: BasePlugin,
                    consts: dict | None = None) -> tuple:
        """Cache key: (plugin static identity, in/out dataset specs,
        consts structure, driver, mesh, donation).  Everything that
        selects a DIFFERENT compiled program must appear here."""
        def pd_meta(pd):
            return (pd.dataset.shape, str(np.dtype(pd.dataset.dtype)),
                    pd.pattern_name, pd.n_frames)
        if consts is None:
            consts = plugin.jit_constants()
        cmeta = tuple(
            (k, tuple(np.shape(v)), str(np.result_type(v)))
            for k, v in sorted(consts.items()))
        return ("plugin", plugin.cache_signature(),
                tuple(pd_meta(pd) for pd in plugin.in_data),
                tuple(pd_meta(pd) for pd in plugin.out_data),
                cmeta, plugin.driver.axes,
                tuple(sorted(plugin.driver.submesh.items())),
                self._mesh_key(), self._donate_mask(plugin))

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def compile_plugin(self, plugin: BasePlugin, lower_only: bool = False,
                       consts: dict | None = None):
        """Compile one plugin step.  With ``consts`` given, compiles
        **ahead-of-time** (``jit(...).lower(...).compile()``) — the
        resulting executable is callable exactly like the jit wrapper
        AND serializable via ``jax.experimental.serialize_executable``
        for the persistent cache tier.  Consts are lowered as concrete
        values (not ShapeDtypeStructs) so python-float constants keep
        their weak types and call-time avals match."""
        da = plugin.driver.data_axis
        in_sh = tuple(self._sharding(pd.pattern, da) for pd in plugin.in_data)
        out_sh = tuple(self._sharding(pd.pattern, da)
                       for pd in plugin.out_data)
        fn = self._plugin_fn(plugin)
        mask = self._donate_mask(plugin)
        if lower_only:
            lconsts = plugin.jit_constants()
            jfn = jax.jit(_named(lambda *arrays: fn(lconsts, *arrays),
                                 plugin.name),
                          in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=tuple(
                              i for i, m in enumerate(mask) if m))
            specs = [jax.ShapeDtypeStruct(pd.dataset.shape,
                                          pd.dataset.dtype, sharding=s)
                     for pd, s in zip(plugin.in_data, in_sh)]
            return jfn.lower(*specs)
        jfn = jax.jit(fn, in_shardings=(self._replicated(), *in_sh),
                      out_shardings=out_sh,
                      donate_argnums=tuple(
                          i + 1 for i, m in enumerate(mask) if m))
        if consts is None:
            return jfn
        specs = [jax.ShapeDtypeStruct(pd.dataset.shape, pd.dataset.dtype,
                                      sharding=s)
                 for pd, s in zip(plugin.in_data, in_sh)]
        return jfn.lower(consts, *specs).compile()

    def _device_in(self, plugin: BasePlugin) -> list[Any]:
        self._check_divisible(plugin)
        da = plugin.driver.data_axis
        # unconditional: AOT-compiled executables (persistent cache
        # tier) are stricter than jit about input placement, so even
        # jax.Arrays are re-committed to the pattern sharding (a no-op
        # when already there)
        return [self._put(pd.dataset.materialise(),
                          self._sharding(pd.pattern, da))
                for pd in plugin.in_data]

    def run_plugin(self, plugin: BasePlugin) -> list[Any]:
        arrays = self._device_in(plugin)
        consts = plugin.jit_constants()
        with self.mesh:
            jfn = self.compile_cache.get_or_build(
                self._plugin_key(plugin, consts),
                lambda: self.compile_plugin(plugin, consts=consts),
                serializable=plugin.persistable(), step=plugin.name)
            outs = list(jfn(consts, *arrays))
        for pd, o in zip(plugin.out_data, outs):
            pd.dataset.backing = o
        return outs

    # -- fusion (beyond-paper): compile a run of plugins as ONE jit ----
    def run_fused(self, plugins: Sequence[BasePlugin]) -> list[Any]:
        """Fuse consecutive plugins into one compilation so XLA overlaps
        the pattern-transition collectives with compute.  Requires the
        chain to be linear (each plugin consumes the previous output)."""
        first, last = plugins[0], plugins[-1]
        in_sh = tuple(self._sharding(pd.pattern, first.driver.data_axis)
                      for pd in first.in_data)
        out_sh = tuple(self._sharding(pd.pattern, last.driver.data_axis)
                       for pd in last.out_data)
        label = "+".join(p.name for p in plugins)

        def builder():
            fns = [self._plugin_fn(p) for p in plugins]
            mid_sh = [tuple(self._sharding(pd.pattern, p.driver.data_axis)
                            for pd in p.out_data) for p in plugins]

            def chain(all_consts, *arrays):
                cur = arrays
                for f, consts, shs in zip(fns, all_consts, mid_sh):
                    cur = f(consts, *cur)
                    cur = tuple(jax.lax.with_sharding_constraint(c, s)
                                for c, s in zip(cur, shs))
                return cur

            return jax.jit(_named(chain, label),
                           in_shardings=(self._replicated(), *in_sh),
                           out_shardings=out_sh)

        arrays = self._device_in(first)
        key = ("fused", tuple(self._plugin_key(p) for p in plugins))
        with self.mesh:
            jfn = self.compile_cache.get_or_build(key, builder, step=label)
            outs = list(jfn(tuple(p.jit_constants() for p in plugins),
                            *arrays))
        for pd, o in zip(last.out_data, outs):
            pd.dataset.backing = o
        return outs

    # -- gang execution (service layer): N jobs, ONE compiled call -----
    def run_plugin_batch(self, plugins: Sequence[BasePlugin]) -> None:
        """Execute the SAME plugin step from several concurrent jobs as a
        single compiled call: inputs are stacked along a new leading job
        axis and the plugin function is vmapped over it — setup-derived
        constants (dark/flat fields, filter banks...) ride along as
        stacked arguments, so jobs with different calibration data still
        share the one program.  All plugins must agree on
        :meth:`_plugin_key` (identical chain step + shapes)."""
        p0 = plugins[0]
        k0 = self._plugin_key(p0)
        for p in plugins[1:]:
            if self._plugin_key(p) != k0:
                raise ValueError(
                    f"run_plugin_batch: plugin {p.name} does not match "
                    f"the batch signature of {p0.name}")
        n = len(plugins)
        da = p0.driver.data_axis

        def batched(sh: NamedSharding) -> NamedSharding:
            return NamedSharding(self.mesh, PartitionSpec(None, *sh.spec))

        in_sh = tuple(batched(self._sharding(pd.pattern, da))
                      for pd in p0.in_data)
        out_sh = tuple(batched(self._sharding(pd.pattern, da))
                       for pd in p0.out_data)

        def builder():
            fn = self._plugin_fn(p0)
            return jax.jit(
                _named(lambda consts, *arrays: jax.vmap(fn)(consts, *arrays),
                       p0.name),
                in_shardings=(self._replicated(), *in_sh),
                out_shardings=out_sh)

        arrays = []
        for i in range(len(p0.in_data)):
            ins = [p.in_data[i].dataset.materialise() for p in plugins]
            if all(isinstance(a, jax.Array) for a in ins):
                stack = jnp.stack(ins)          # stays on device
            else:
                stack = np.stack([np.asarray(a) for a in ins])
            arrays.append(self._put(stack, in_sh[i]))
        consts = [p.jit_constants() for p in plugins]
        stacked_consts = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *consts)
        with self.mesh:
            jfn = self.compile_cache.get_or_build(("batch", n, k0), builder,
                                                  step=p0.name)
            outs = list(jfn(stacked_consts, *arrays))
        for j, p in enumerate(plugins):
            for pd, o in zip(p.out_data, outs):
                pd.dataset.backing = o[j]

    def stats(self) -> dict[str, Any]:
        return {"compile_cache": self.compile_cache.stats()}


# ======================================================================
@dataclasses.dataclass
class IOStats:
    chunk_reads: int = 0          # cache-missing chunk fetches
    chunk_writes: int = 0
    cache_hits: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    wall: float = 0.0

    def merge(self, o: "IOStats") -> "IOStats":
        return IOStats(self.chunk_reads + o.chunk_reads,
                       self.chunk_writes + o.chunk_writes,
                       self.cache_hits + o.cache_hits,
                       self.bytes_read + o.bytes_read,
                       self.bytes_written + o.bytes_written,
                       self.wall + o.wall)


class ChunkedFile:
    """A chunk-addressed on-disk array: np.memmap standing in for a
    parallel-HDF5 dataset.  Chunks are stored contiguously in row-major
    chunk-grid order; an LRU cache of ``cache_bytes`` emulates the HDF5
    raw-chunk cache, and all traffic is counted in :class:`IOStats`."""

    def __init__(self, path: str, shape: Sequence[int], dtype,
                 chunks: Sequence[int],
                 cache_bytes: int = DEFAULT_CACHE_BYTES,
                 mode: str = "w+"):
        self.path = path
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.chunks = tuple(int(min(c, s))
                            for c, s in zip(chunks, self.shape))
        self.grid = tuple(-(-s // c) for s, c in zip(self.shape, self.chunks))
        self.chunk_items = int(np.prod(self.chunks))
        self.chunk_nbytes = self.chunk_items * self.dtype.itemsize
        self._n_items = int(np.prod(self.grid)) * self.chunk_items
        self._readonly = mode == "r"
        self._mm = np.memmap(path, dtype=self.dtype, mode=mode,
                             shape=(self._n_items,))
        self.stats = IOStats()
        self._cache: OrderedDict[int, np.ndarray] = OrderedDict()
        self._cache_slots = max(1, cache_bytes // max(1, self.chunk_nbytes))
        #: flat chunk ids whose contents changed since last mark_clean()
        #: — the incremental-checkpoint increment
        self.dirty: set[int] = set()

    def mark_clean(self) -> None:
        """Reset dirty-chunk tracking (after a checkpoint captured the
        current contents)."""
        self.dirty = set()

    # -- chunk addressing ------------------------------------------------
    def _flat(self, cidx: tuple[int, ...]) -> int:
        f = 0
        for i, g in zip(cidx, self.grid):
            f = f * g + i
        return f

    def _get_chunk(self, cidx: tuple[int, ...]) -> np.ndarray:
        f = self._flat(cidx)
        if f in self._cache:
            self.stats.cache_hits += 1
            self._cache.move_to_end(f)
            return self._cache[f]
        t0 = time.perf_counter()
        raw = np.array(self._mm[f * self.chunk_items:
                                (f + 1) * self.chunk_items])
        self.stats.wall += time.perf_counter() - t0
        self.stats.chunk_reads += 1
        self.stats.bytes_read += self.chunk_nbytes
        chunk = raw.reshape(self.chunks)
        self._put_cache(f, chunk)
        return chunk

    def _put_cache(self, f: int, chunk: np.ndarray) -> None:
        self._cache[f] = chunk
        self._cache.move_to_end(f)
        while len(self._cache) > self._cache_slots:
            ef, ec = self._cache.popitem(last=False)
            self._flush_chunk(ef, ec)

    def _flush_chunk(self, f: int, chunk: np.ndarray) -> None:
        if self._readonly:
            return                        # reads never dirty a chunk
        t0 = time.perf_counter()
        self._mm[f * self.chunk_items:(f + 1) * self.chunk_items] = \
            chunk.reshape(-1)
        self.stats.wall += time.perf_counter() - t0
        self.stats.chunk_writes += 1
        self.stats.bytes_written += self.chunk_nbytes

    def flush(self) -> None:
        for f, c in list(self._cache.items()):
            self._flush_chunk(f, c)
        self._cache.clear()
        self._mm.flush()

    # -- region IO --------------------------------------------------------
    def _touched(self, region: tuple[slice, ...]):
        ranges = []
        for d, sl in enumerate(region):
            start = sl.start or 0
            stop = self.shape[d] if sl.stop is None else min(sl.stop,
                                                             self.shape[d])
            ranges.append(range(start // self.chunks[d],
                                (stop - 1) // self.chunks[d] + 1))
        return ranges

    def read(self, region: tuple[slice, ...]) -> np.ndarray:
        region = tuple(region)
        starts = [sl.start or 0 for sl in region]
        stops = [self.shape[d] if sl.stop is None else sl.stop
                 for d, sl in enumerate(region)]
        out = np.empty([b - a for a, b in zip(starts, stops)],
                       dtype=self.dtype)
        ranges = self._touched(region)
        for cidx in np.ndindex(*[len(r) for r in ranges]):
            c = tuple(ranges[d][cidx[d]] for d in range(len(cidx)))
            chunk = self._get_chunk(c)
            # intersection of chunk extent and region, in both coords
            src, dst = [], []
            for d in range(len(c)):
                c0 = c[d] * self.chunks[d]
                lo = max(starts[d], c0)
                hi = min(stops[d], c0 + self.chunks[d], self.shape[d])
                src.append(slice(lo - c0, hi - c0))
                dst.append(slice(lo - starts[d], hi - starts[d]))
            out[tuple(dst)] = chunk[tuple(src)]
        return out

    def write(self, region: tuple[slice, ...], values: np.ndarray) -> None:
        if self._readonly:
            raise OSError(f"{self.path} is open read-only")
        region = tuple(region)
        starts = [sl.start or 0 for sl in region]
        stops = [self.shape[d] if sl.stop is None else sl.stop
                 for d, sl in enumerate(region)]
        values = np.asarray(values, dtype=self.dtype).reshape(
            [b - a for a, b in zip(starts, stops)])
        ranges = self._touched(region)
        for cidx in np.ndindex(*[len(r) for r in ranges]):
            c = tuple(ranges[d][cidx[d]] for d in range(len(cidx)))
            src, dst = [], []
            full = True
            for d in range(len(c)):
                c0 = c[d] * self.chunks[d]
                lo = max(starts[d], c0)
                hi = min(stops[d], c0 + self.chunks[d], self.shape[d])
                if lo > c0 or hi < min(c0 + self.chunks[d], self.shape[d]):
                    full = False
                dst.append(slice(lo - c0, hi - c0))
                src.append(slice(lo - starts[d], hi - starts[d]))
            f = self._flat(c)
            if full and f not in self._cache:
                # whole-chunk write: no read-modify-write round trip
                chunk = np.zeros(self.chunks, dtype=self.dtype)
                self._put_cache(f, chunk)
            else:
                chunk = self._get_chunk(c)
            chunk[tuple(dst)] = values[tuple(src)]
            self.dirty.add(f)
        # cached chunks are flushed on eviction/flush (write-back cache)

    def read_all(self) -> np.ndarray:
        return self.read(tuple(slice(0, s) for s in self.shape))

    def write_all(self, values: np.ndarray) -> None:
        self.write(tuple(slice(0, s) for s in self.shape), values)
        self.flush()

    def load_from(self, path: str) -> None:
        """Replace this file's contents with another chunk file of the
        SAME shape/layout via an OS-level file copy — restores a
        checkpointed volume without round-tripping it through RAM
        (O(frames), not O(dataset), memory)."""
        if self._readonly:
            raise OSError(f"{self.path} is open read-only")
        if os.path.getsize(path) < self._n_items * self.dtype.itemsize:
            raise ValueError(f"{path} too small for layout {self.chunks} "
                             f"over {self.shape}")
        self._cache.clear()
        self._mm = None                   # release before overwriting
        shutil.copyfile(path, self.path)
        self._mm = np.memmap(self.path, dtype=self.dtype, mode="r+",
                             shape=(self._n_items,))
        self.dirty = set(range(int(np.prod(self.grid))))


class ChunkedFileTransport(Transport):
    """Out-of-core mode: every dataset is a ChunkedFile; chunk layouts
    come from the paper's optimiser given (now, next) patterns; plugins
    see m frames at a time read straight off file — RAM use is O(frames),
    never O(dataset) (paper §III.A)."""

    name = "chunked_file"

    def __init__(self, directory: str | None = None,
                 cache_bytes: int = DEFAULT_CACHE_BYTES,
                 optimise: bool = True, frames_hint: int = 8):
        self.dir = directory or tempfile.mkdtemp(prefix="savu_jax_")
        os.makedirs(self.dir, exist_ok=True)
        self.cache_bytes = cache_bytes
        self.optimise = optimise
        self.frames_hint = frames_hint
        self.files: dict[str, ChunkedFile] = {}
        self._counter = 0

    def _new_path(self, name: str) -> str:
        self._counter += 1
        return os.path.join(self.dir, f"{self._counter:03d}_{name}.dat")

    def chunk_for(self, ds: DataSet, now: Pattern, next_: Pattern | None
                  ) -> tuple[int, ...]:
        if not self.optimise:
            from .chunking import naive_chunks
            return naive_chunks(ds.shape, np.dtype(ds.dtype).itemsize,
                                self.cache_bytes)
        return optimise_chunks(
            ds.shape, now, next_, itemsize=np.dtype(ds.dtype).itemsize,
            frames=self.frames_hint, cache_bytes=self.cache_bytes)

    def allocate(self, ds: DataSet, now: Pattern, next_: Pattern | None
                 ) -> None:
        chunks = self.chunk_for(ds, now, next_)
        cf = ChunkedFile(self._new_path(ds.name), ds.shape, ds.dtype,
                         chunks, self.cache_bytes)
        self.files[ds.name] = cf
        ds.backing = cf
        ds.metadata["chunks"] = chunks

    def ingest(self, ds: DataSet, now: Pattern,
               next_: Pattern | None = None) -> None:
        """Copy a materialised dataset into a chunked file (loader side)."""
        data = np.asarray(ds.materialise())
        self.allocate(ds, now, next_)
        ds.backing.write_all(data)

    def run_plugin(self, plugin: BasePlugin) -> list[Any]:
        in_pds = plugin.in_data
        out_pds = plugin.out_data
        m = in_pds[0].n_frames
        in_pats = [pd.pattern for pd in in_pds]
        out_pats = [pd.pattern for pd in out_pds]
        shape0 = in_pds[0].dataset.shape
        slices_iters = [pd.pattern.frame_slices(pd.dataset.shape, m)
                        for pd in in_pds]
        out_iters = [pd.pattern.frame_slices(pd.dataset.shape, m)
                     for pd in out_pds]
        n_calls = 0
        for idx_tuple in zip(*slices_iters):
            blocks = []
            for pd, pat, idx in zip(in_pds, in_pats, idx_tuple):
                backing = pd.dataset.backing
                if isinstance(backing, ChunkedFile):
                    raw = backing.read(idx)
                else:
                    raw = np.asarray(pd.dataset.materialise())[idx]
                blocks.append(pat.to_frames(
                    raw, shape=[s.stop - (s.start or 0)
                                if isinstance(s, slice) else 1
                                for s in _norm_idx(idx, pd.dataset.shape)]))
            res = _as_list(plugin.process_frames(blocks))
            for pd, pat, r, it in zip(out_pds, out_pats, res, out_iters):
                oidx = next(it)
                oshape = [s.stop - (s.start or 0)
                          for s in _norm_idx(oidx, pd.dataset.shape)]
                val = pat.from_frames(np.asarray(r), oshape)
                pd.dataset.backing.write(_norm_idx(oidx, pd.dataset.shape),
                                         val)
            n_calls += 1
        for pd in out_pds:
            pd.dataset.backing.flush()
        return [pd.dataset.backing for pd in out_pds]

    def read(self, ds: DataSet) -> np.ndarray:
        b = ds.materialise()
        if isinstance(b, ChunkedFile):
            return b.read_all()
        return np.asarray(b)

    def total_stats(self) -> IOStats:
        s = IOStats()
        for cf in self.files.values():
            s = s.merge(cf.stats)
        return s

    def stats(self) -> dict[str, Any]:
        return {"io": dataclasses.asdict(self.total_stats())}

    def close(self) -> None:
        for cf in self.files.values():
            cf.flush()


def _norm_idx(idx: tuple, shape: Sequence[int]) -> tuple[slice, ...]:
    out = []
    for d, s in enumerate(idx):
        if isinstance(s, slice):
            out.append(slice(s.start or 0,
                             shape[d] if s.stop is None else s.stop))
        else:
            out.append(slice(int(s), int(s) + 1))
    return tuple(out)
