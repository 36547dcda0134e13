"""Process-level compiled-plugin cache, with a persistent disk tier.

The paper's headline workload is "the same pipeline over many datasets":
at a facility, hundreds of scans a day run one tuned process list.  On
the jax substrate the expensive part of a repeat submission is the
``jax.jit`` retrace+compile of every plugin, so the service keeps ONE
cache for the whole process, shared by every job's
:class:`~repro.core.transport.ShardedTransport`.

Keys come from ``ShardedTransport._plugin_key``: (plugin static identity,
in/out dataset shapes/dtypes/patterns, constants structure, driver, mesh,
donation).  Values are compiled callables whose setup-derived constants
(dark/flat fields, filter banks...) are jit *arguments*, so a hit is
valid across jobs even when calibration data differs.

Beyond the in-memory tier (valid for one process), entries whose builder
produces an AOT-compiled executable can be **persisted**: serialized via
``jax.experimental.serialize_executable`` into an :class:`ExecutableStore`
keyed by :func:`executable_signature` — a digest of the cache key PLUS
the jax/jaxlib version and backend/device fingerprint, so an entry built
under a different toolchain can never be silently loaded (it simply has a
different signature, and its header is re-verified on load anyway).  A
fresh worker process pointed at the same store — or prefetching from the
broker's spool (``GET /executables/{sig}``) — deserializes hot programs
in milliseconds instead of recompiling them: the "kill the retrace tax"
warm pool (docs/worker-protocol.md).

Thread-safety: one build per key even under concurrent misses — losers
of the build race block on the winner's per-key event rather than
compiling twice.  :meth:`CompileCache.clear` bumps a generation counter
so a build that was already in flight when the clear happened cannot
re-insert its (now unwanted) entry afterwards.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Callable

from ..obs.trace import current_trace, traced

#: on-disk payload framing: magic + one JSON header line + pickle body
_MAGIC = b"SAVUEXE1\n"

_HEX = frozenset("0123456789abcdef")


class StaleExecutable(Exception):
    """A persisted executable payload cannot be loaded into THIS process:
    corrupted/truncated bytes, a header written by a different jax/jaxlib
    version or backend, or a signature mismatch.  Always recoverable —
    the caller falls back to a fresh compile."""


#: root of the checkout this package runs from (src/repro/service/..)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def cache_root() -> str:
    """Where this program keeps compiled code: ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else ``.jax_cache`` at the root of the checkout.  The
    path is fixed, so what one run caches the next one finds."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def default_executables_dir() -> str:
    """Default :class:`ExecutableStore` directory, under :func:`cache_root`."""
    return os.path.join(cache_root(), "executables")


def setup_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache at :func:`cache_root`
    (entry points call this before their first compile).  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set no directory
    is set here.  Returns the directory in use."""
    root = cache_root()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    return root


def _source_digest() -> str:
    """sha256 over this package's source files: a persisted executable
    is only valid for the code that compiled it."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, pkg).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
    return h.hexdigest()


_fingerprint_cache: dict[str, Any] | None = None


def env_fingerprint() -> dict[str, Any]:
    """The toolchain+hardware identity a serialized executable is only
    valid under: jax/jaxlib versions, backend, device kinds/count, and
    the package source it was compiled from.
    Baked into every payload header AND into
    :func:`executable_signature`, so stale entries are rejected twice
    over (different signature, and a header mismatch on load) rather
    than ever being silently loaded."""
    global _fingerprint_cache
    if _fingerprint_cache is None:
        import jax
        try:
            import jaxlib
            jaxlib_ver = getattr(jaxlib, "__version__", "unknown")
        except ImportError:              # pragma: no cover
            jaxlib_ver = "none"
        devs = jax.devices()
        _fingerprint_cache = {
            "fmt": 1,
            "jax": jax.__version__,
            "jaxlib": jaxlib_ver,
            "backend": jax.default_backend(),
            "devices": sorted({d.device_kind for d in devs}),
            "n_devices": len(devs),
            "source": _source_digest(),
        }
    return _fingerprint_cache


def executable_signature(key: Any) -> str:
    """Stable hex digest naming one compiled program across processes:
    sha256 over the cache key's repr (plugin identity, shapes, mesh,
    donation — all stable-repr tuples) salted with
    :func:`env_fingerprint`.  This is the ``{sig}`` in
    ``GET/PUT /executables/{sig}``."""
    fp = json.dumps(env_fingerprint(), sort_keys=True)
    return hashlib.sha256(f"{fp}|{key!r}".encode()).hexdigest()


def serialize_payload(compiled: Any, sig: str) -> bytes:
    """Frame an AOT-compiled executable for disk/wire: magic + JSON
    header (signature + env fingerprint) + pickled
    ``jax.experimental.serialize_executable`` triple.  Raises whatever
    ``serialize`` raises for executables jax cannot serialize."""
    from jax.experimental import serialize_executable as se
    ser, in_tree, out_tree = se.serialize(compiled)
    header = json.dumps({"sig": sig, "fingerprint": env_fingerprint()},
                        sort_keys=True).encode()
    return _MAGIC + header + b"\n" + pickle.dumps((ser, in_tree, out_tree))


def deserialize_payload(payload: bytes, sig: str | None = None) -> Any:
    """Load a framed payload back into a runnable executable.

    Every failure mode — bad magic, truncated bytes, unparseable
    header, a fingerprint from another jax version/backend, a signature
    mismatch, an undeserializable body — raises
    :class:`StaleExecutable`; nothing is ever silently loaded wrong.
    """
    if not payload.startswith(_MAGIC):
        raise StaleExecutable("bad magic (not a serialized executable)")
    try:
        nl = payload.index(b"\n", len(_MAGIC))
        header = json.loads(payload[len(_MAGIC):nl])
    except (ValueError, UnicodeDecodeError) as e:
        raise StaleExecutable(f"unparseable header: {e}") from None
    if not isinstance(header, dict):
        raise StaleExecutable("header is not an object")
    if header.get("fingerprint") != env_fingerprint():
        raise StaleExecutable(
            f"toolchain mismatch: payload built under "
            f"{header.get('fingerprint')!r}, this process is "
            f"{env_fingerprint()!r}")
    if sig is not None and header.get("sig") != sig:
        raise StaleExecutable(
            f"signature mismatch: header says {header.get('sig')!r}")
    try:
        from jax.experimental import serialize_executable as se
        ser, in_tree, out_tree = pickle.loads(payload[nl + 1:])
        return se.deserialize_and_load(ser, in_tree, out_tree)
    except StaleExecutable:
        raise
    except Exception as e:               # noqa: BLE001 — any decode fault
        raise StaleExecutable(
            f"undeserializable body: {type(e).__name__}: {e}") from None


def _safe_sig(sig: str) -> str:
    """A signature that may become a filename: lowercase hex only."""
    if not (isinstance(sig, str) and 8 <= len(sig) <= 128
            and set(sig) <= _HEX):
        raise ValueError(f"not a hex executable signature: {sig!r}")
    return sig


class ExecutableStore:
    """Disk spool of serialized executables keyed by signature.

    Used on both ends of the warm-pool protocol: a worker's local disk
    tier (payloads it built or prefetched) and the broker's spool
    (payloads uploaded by workers, served to newly registered ones).
    Raw payload bytes only — the broker never deserializes.

    Retention is LRU by total bytes (``max_bytes``); use counts feed
    :meth:`hot` — the "prefetch these first" list a registration reply
    carries.  All writes are atomic (tmp + rename), so a reader never
    sees a torn payload.
    """

    def __init__(self, directory: str, max_bytes: int = 512 << 20):
        self.dir = directory
        os.makedirs(self.dir, exist_ok=True)
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        #: per-signature use count (puts + gets) — the heat signal
        self._uses: dict[str, int] = {}
        #: insertion/use order for LRU eviction
        self._order: list[str] = []
        self.puts = 0
        self.evictions = 0
        for name in sorted(os.listdir(self.dir)):   # adopt prior spool
            if name.endswith(".exe"):
                sig = name[:-4]
                self._uses.setdefault(sig, 0)
                self._order.append(sig)

    def _path(self, sig: str) -> str:
        return os.path.join(self.dir, f"{_safe_sig(sig)}.exe")

    def _touch_locked(self, sig: str) -> None:
        self._uses[sig] = self._uses.get(sig, 0) + 1
        if sig in self._order:
            self._order.remove(sig)
        self._order.append(sig)

    def has(self, sig: str) -> bool:
        try:
            return os.path.exists(self._path(sig))
        except ValueError:
            return False

    def get_bytes(self, sig: str) -> bytes | None:
        """The raw payload for ``sig`` (None if absent).  Counts a use
        — repeated fetches mark the signature hot."""
        try:
            path = self._path(sig)
        except ValueError:
            return None
        try:
            with open(path, "rb") as fh:
                payload = fh.read()
        except OSError:
            return None
        with self._lock:
            self._touch_locked(sig)
        return payload

    def put_bytes(self, sig: str, payload: bytes) -> bool:
        """Store one payload (idempotent: re-putting an existing
        signature just marks it hot).  Only framed payloads are
        accepted — arbitrary bytes can't enter the spool.  Evicts LRU
        entries beyond ``max_bytes``.  Returns True if stored/present.
        """
        try:
            path = self._path(sig)
        except ValueError:
            return False
        if not payload.startswith(_MAGIC):
            return False
        with self._lock:
            if not os.path.exists(path):
                tmp = f"{path}.{os.getpid()}.tmp"
                try:
                    with open(tmp, "wb") as fh:
                        fh.write(payload)
                    os.replace(tmp, path)
                except OSError:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    return False
                self.puts += 1
            self._touch_locked(sig)
            self._evict_locked()
        return True

    def discard(self, sig: str) -> None:
        """Drop one entry (e.g. a payload that failed to deserialize —
        no point re-parsing it on every miss)."""
        try:
            path = self._path(sig)
        except ValueError:
            return
        with self._lock:
            try:
                os.unlink(path)
            except OSError:
                pass
            self._uses.pop(sig, None)
            if sig in self._order:
                self._order.remove(sig)

    def _evict_locked(self) -> None:
        while self.total_bytes() > self.max_bytes and len(self._order) > 1:
            victim = self._order.pop(0)
            self._uses.pop(victim, None)
            try:
                os.unlink(os.path.join(self.dir, f"{victim}.exe"))
            except OSError:
                pass
            self.evictions += 1

    def total_bytes(self) -> int:
        total = 0
        try:
            for name in os.listdir(self.dir):
                if name.endswith(".exe"):
                    try:
                        total += os.path.getsize(
                            os.path.join(self.dir, name))
                    except OSError:
                        pass
        except OSError:
            pass
        return total

    def signatures(self) -> list[str]:
        with self._lock:
            return list(self._order)

    def hot(self, n: int = 8) -> list[str]:
        """The ``n`` most-used signatures, hottest first — what a
        registration reply tells a fresh worker to prefetch."""
        with self._lock:
            ranked = sorted(self._uses.items(),
                            key=lambda kv: (-kv[1],
                                            -self._order.index(kv[0])
                                            if kv[0] in self._order
                                            else 0))
        return [sig for sig, _ in ranked[:n] if self.has(sig)]

    def clear(self) -> None:
        """Drop every entry (a cache invalidation must reach disk too —
        otherwise a cleared program would come straight back on the
        next miss)."""
        with self._lock:
            for sig in list(self._order):
                try:
                    os.unlink(os.path.join(self.dir, f"{sig}.exe"))
                except OSError:
                    pass
            self._order.clear()
            self._uses.clear()

    def stats(self) -> dict[str, Any]:
        with self._lock:
            n = len(self._order)
        return {"entries": n, "bytes": self.total_bytes(),
                "puts": self.puts, "evictions": self.evictions}


class CompileCache:
    """Process-level compiled-plugin cache (paper §I: "the same
    pipeline, many datasets" — resubmission must not retrace), with an
    optional persistent tier that survives the process."""

    def __init__(self, max_entries: int | None = None,
                 store: ExecutableStore | str | None = None,
                 fetch: Callable[[str], bytes | None] | None = None,
                 publish: Callable[[str, bytes], Any] | None = None):
        """Args:
            max_entries: FIFO-evict beyond this many compiled programs
                (None = unbounded).
            store: disk tier — an :class:`ExecutableStore` or a
                directory path (None = in-memory only).  Only entries
                built with ``serializable=True`` use it.
            fetch: optional ``sig -> payload bytes | None`` callback
                consulted on a disk miss BEFORE compiling (the worker
                wires ``GET /executables/{sig}`` here).  Failures fall
                back to a fresh compile.
            publish: optional ``(sig, payload) -> None`` callback run
                after a fresh serializable build (the worker wires
                ``PUT /executables/{sig}`` here).  Best-effort.

        Note: an EMPTY cache is falsy (``__len__``) — test ``is None``,
        never truthiness, when defaulting."""
        self.max_entries = max_entries
        self.store = (ExecutableStore(store) if isinstance(store, str)
                      else store)
        self.fetch = fetch
        self.publish = publish
        self._entries: dict[Any, Any] = {}
        self._building: dict[Any, threading.Event] = {}
        self._lock = threading.Lock()
        self._generation = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.build_s = 0.0               # total wall spent compiling
        self.disk_hits = 0               # deserialized instead of compiled
        self.disk_misses = 0             # persisted tier had nothing usable
        self.disk_rejects = 0            # stale/corrupt payloads refused
        self.uploads = 0                 # payloads handed to ``publish``

    def get_or_build(self, key, builder: Callable[[], Any],
                     serializable: bool = False, step: str | None = None):
        """Return the cached value for ``key``, building it (once) on a
        miss.

        Args:
            key: hashable identity (see
                ``ShardedTransport._plugin_key`` / ARCHITECTURE.md).
            builder: zero-arg callable producing the compiled program;
                invoked at most once per key even under concurrent
                misses — losers of the build race block on the winner.
            serializable: the builder produces an AOT-compiled
                executable (``jit(...).lower(...).compile()``) — on a
                memory miss the persistent tier is consulted first
                (disk, then the ``fetch`` callback), and a fresh build
                is serialized back out (disk + ``publish``).
            step: the plugin step (or fused/batched group) the program
                runs, named on the ``compile`` and
                ``executable.deserialize`` spans of a miss.

        Returns: the cached/built value.  A ``builder`` that raises
        propagates to its caller; waiting losers retry (and one of them
        becomes the next builder).
        """
        while True:
            with self._lock:
                if key in self._entries:
                    self.hits += 1
                    return self._entries[key]
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = threading.Event()
                    self.misses += 1
                    # snapshot the generation BEFORE building: a clear()
                    # issued mid-build bumps it, and the late winner
                    # below must then be dropped, not re-inserted
                    gen = self._generation
                    break
            ev.wait()                    # someone else is compiling this key
        try:
            fn = None
            sig = None
            if serializable and self.store is not None:
                sig = executable_signature(key)
                fn = self._load_persisted(sig, step or "")
            if fn is None:
                t0 = time.perf_counter()
                # actual builds (never hits) show up as ``compile`` spans
                # on whichever job triggered them
                with traced("compile", step=step or "",
                            kind=key[0] if isinstance(key, tuple) and key
                            else "plugin"):
                    fn = builder()
                dt = time.perf_counter() - t0
                with self._lock:
                    self.build_s += dt
                if sig is not None:
                    self._persist(sig, fn)
            with self._lock:
                if self._generation != gen:
                    # cleared while we were building: this program was
                    # invalidated before it existed — hand it to the
                    # caller (it is still correct for THIS call) but
                    # never cache it
                    return fn
                self._entries[key] = fn
                if (self.max_entries is not None
                        and len(self._entries) > self.max_entries):
                    # FIFO eviction — plugin programs are all roughly the
                    # same size; recency tracking is not worth the locking
                    oldest = next(iter(self._entries))
                    del self._entries[oldest]
                    self.evictions += 1
            return fn
        finally:
            with self._lock:
                self._building.pop(key).set()

    # -- persistent tier ------------------------------------------------
    def _load_persisted(self, sig: str, step: str):
        """A runnable executable for ``sig`` from the persistent tier —
        local disk first, then the broker ``fetch`` callback — or None
        (count a disk miss; the caller compiles).  Loads record
        ``executable.fetch`` + ``executable.deserialize`` spans (the
        latter live, naming ``step``) on the current trace, as real
        builds record ``compile``."""
        tr = current_trace()
        t0 = time.time()
        payload = self.store.get_bytes(sig)
        source = "disk"
        if payload is None and self.fetch is not None:
            try:
                payload = self.fetch(sig)
            except Exception:            # noqa: BLE001 — network is advisory
                payload = None
            source = "broker"
            if payload is not None:
                self.store.put_bytes(sig, payload)
        if payload is None:
            with self._lock:
                self.disk_misses += 1
            return None
        if tr is not None:
            tr.record("executable.fetch", t0, time.time(),
                      attrs={"sig": sig[:16], "source": source,
                             "bytes": len(payload)})
        try:
            with traced("executable.deserialize", sig=sig[:16], step=step):
                fn = deserialize_payload(payload, sig)
        except StaleExecutable:
            # never silently loaded: corrupt/version-mismatched payloads
            # are dropped from disk and the caller compiles fresh
            with self._lock:
                self.disk_rejects += 1
                self.disk_misses += 1
            self.store.discard(sig)
            return None
        with self._lock:
            self.disk_hits += 1
        return fn

    def _persist(self, sig: str, fn: Any) -> None:
        """Serialize a fresh build into the store and hand it to
        ``publish``.  Best-effort on both counts: an executable jax
        cannot serialize (or a broker that refuses the upload) must
        never fail the job that compiled it."""
        try:
            payload = serialize_payload(fn, sig)
        except Exception:                # noqa: BLE001 — not serializable
            return
        self.store.put_bytes(sig, payload)
        if self.publish is not None:
            try:
                self.publish(sig, payload)
                with self._lock:
                    self.uploads += 1
            except Exception:            # noqa: BLE001 — upload is advisory
                pass

    def prefetch(self, sigs: list[str]) -> int:
        """Warm-pool fill: fetch every signature not already on disk
        via the ``fetch`` callback (the broker's hottest list, carried
        on the registration reply).  Returns how many payloads landed.
        Purely additive — failures are skipped."""
        if self.store is None or self.fetch is None:
            return 0
        n = 0
        for sig in sigs or ():
            if not isinstance(sig, str) or self.store.has(sig):
                continue
            try:
                payload = self.fetch(sig)
            except Exception:            # noqa: BLE001
                continue
            if payload and self.store.put_bytes(sig, payload):
                n += 1
        return n

    def items(self) -> list[tuple[Any, Any]]:
        """Snapshot of the (key, compiled program) pairs held in memory."""
        with self._lock:
            return list(self._entries.items())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        """Drop every cached program (counters are kept) — including
        the persistent tier, and including builds currently in flight:
        the generation bump makes a pre-clear builder's late insert a
        no-op."""
        with self._lock:
            self._generation += 1
            self._entries.clear()
        if self.store is not None:
            self.store.clear()

    def stats(self) -> dict[str, Any]:
        """Counters for ``GET /stats``: ``hits``, ``misses``,
        ``entries``, ``evictions``, total compile ``build_s``, and —
        when a persistent tier is configured — a ``disk`` block with
        its hit/miss/reject/upload counters and store occupancy."""
        with self._lock:
            out: dict[str, Any] = {
                "hits": self.hits, "misses": self.misses,
                "entries": len(self._entries),
                "evictions": self.evictions,
                "build_s": round(self.build_s, 4),
                "generation": self._generation}
            disk = {"hits": self.disk_hits, "misses": self.disk_misses,
                    "rejects": self.disk_rejects, "uploads": self.uploads}
        if self.store is not None:
            out["disk"] = {**disk, **self.store.stats()}
        return out
