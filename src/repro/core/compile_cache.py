"""Persistent content-addressed compile cache (paper Section 3.3, extended).

The paper's hierarchical-codegen speedup comes from compiling each task
*definition* once and stitching instances.  The seed reproduction kept only
the in-process half of that: definitions were keyed on ``id(fn)``, so every
new process, every re-created closure, and every QoR-tuning edit recompiled
the world.  This module supplies the missing halves:

1.  **Structural definition hash** — a stable digest of a Python function's
    bytecode, constants, referenced globals, closure cell *values*, and
    defaults (plus the jax version, backend, and cache schema).  Two
    separately-created lambdas with the same body hash equal; an edited
    constant or closure weight hashes different.  The digest survives
    process restarts, which ``id(fn)`` never could.

2.  **Two-level content-addressed store** — an in-memory dict in front of an
    on-disk store (``<root>/v1/ex/<hh>/<digest>.exe``) holding serialized
    XLA executables (:mod:`jax.experimental.serialize_executable`).  Disk
    entries are LRU-evicted against a size bound, corrupt entries are
    deleted and recompiled, and a schema bump invalidates the whole layout.

3.  **Result memo store** — small JSON payloads keyed by the same digests
    (``<root>/v1/memo/<hh>/<digest>.json``), used by the QoR-tuning loop in
    ``benchmarks/perf_iter.py`` to skip re-measuring unchanged variants.

4.  **Lowering memo** — in memory only: the per-phase I/O counts that
    ``CompiledEngine`` traces a task graph for, keyed by the graph's
    structural hash, so re-invoking an unchanged graph traces nothing
    (``docs/synthesis.md``).

The cache is what makes the paper's edit-compile-measure cycle fast across
*runs*: edit one of gaussian's definitions and only that definition pays an
XLA compile — everything else is a digest lookup.  See ``docs/codegen.md``.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import re
import threading
import types
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

SCHEMA = "v1"
_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")

# how deep to chase functions referenced from globals/closures before
# falling back to their qualified name (keeps the hash off library innards)
_MAX_FN_DEPTH = 4


# ---------------------------------------------------------------------------
# structural hashing
# ---------------------------------------------------------------------------

def _stable_repr(v: Any) -> str:
    """``repr`` with memory addresses stripped (stable across processes)."""
    return _ADDR_RE.sub("", repr(v))


def _obj_state(v: Any) -> Optional[dict]:
    """Instance attributes of an object (``__dict__`` or ``__slots__``),
    or None when it carries no inspectable state."""
    d = getattr(v, "__dict__", None)
    if d:
        return dict(d)
    slots = getattr(type(v), "__slots__", None)
    if slots:
        return {s: getattr(v, s, None) for s in slots
                if isinstance(s, str)}
    return None


def _enc_code(h, code: types.CodeType, depth: int, seen: set) -> None:
    h.update(b"code")
    h.update(code.co_code)
    h.update(_stable_repr(code.co_names).encode())
    h.update(_stable_repr(code.co_freevars).encode())
    h.update(str(code.co_argcount).encode())
    for c in code.co_consts:
        _enc(h, c, depth, seen)


def _code_names(code: types.CodeType, acc: set) -> None:
    """All names referenced by ``code`` and every nested code object —
    a constant read inside a nested lambda is still baked into the traced
    program, so its global must be value-hashed too."""
    acc.update(code.co_names)
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            _code_names(c, acc)


def _enc_fn(h, fn: Callable, depth: int, seen: set) -> None:
    if id(fn) in seen or depth > _MAX_FN_DEPTH:
        h.update(getattr(fn, "__qualname__", repr(type(fn))).encode())
        return
    seen.add(id(fn))
    if isinstance(fn, partial):
        h.update(b"partial")
        _enc_fn(h, fn.func, depth, seen)
        _enc(h, fn.args, depth, seen)
        _enc(h, fn.keywords, depth, seen)
        return
    code = getattr(fn, "__code__", None)
    if code is None:
        # no Python code object: unwrap before giving up — jit wrappers
        # expose __wrapped__, bound methods __func__ (+ the state their
        # behaviour depends on, __self__)
        wrapped = getattr(fn, "__wrapped__", None)
        if wrapped is not None and wrapped is not fn:
            h.update(b"wrapped")
            _enc_fn(h, wrapped, depth, seen)
            return
        inner = getattr(fn, "__func__", None)
        state = _obj_state(fn)
        if inner is not None and inner is not fn:
            _enc_fn(h, inner, depth + 1, seen)
            _enc(h, getattr(fn, "__self__", None), depth + 1, seen)
        elif state is not None:
            # callable object instance: behaviour = class __call__ code +
            # instance attributes (Scale(2.0) must never collide with
            # Scale(3.0))
            h.update(f"callable-obj:{type(fn).__qualname__}".encode())
            _enc(h, state, depth + 1, seen)
            call = getattr(type(fn), "__call__", None)
            if getattr(call, "__code__", None) is not None:
                _enc_fn(h, call, depth + 1, seen)
        elif depth == 0:
            # opaque top-level callable: a content digest is impossible,
            # so salt with the object identity — unstable keys cost a
            # recompile, shared keys would silently reuse the wrong
            # executable
            h.update(f"opaque:{type(fn).__qualname__}:{id(fn)}".encode())
        else:
            h.update(getattr(fn, "__qualname__",
                             _stable_repr(fn)).encode())
        return
    _enc_code(h, code, depth + 1, seen)
    _enc(h, getattr(fn, "__defaults__", None), depth + 1, seen)
    _enc(h, getattr(fn, "__kwdefaults__", None), depth + 1, seen)
    if getattr(fn, "__self__", None) is not None:     # bound with state
        _enc(h, fn.__self__, depth + 1, seen)
    # closure cell *values*: a re-created closure over the same data hashes
    # equal; an edited weight/constant hashes different
    closure = getattr(fn, "__closure__", None) or ()
    for name, cell in zip(code.co_freevars, closure):
        h.update(name.encode())
        try:
            _enc(h, cell.cell_contents, depth + 1, seen)
        except ValueError:          # empty cell (still being defined)
            h.update(b"<empty-cell>")
    # referenced module-level globals — including ones only nested code
    # objects touch: data is hashed by content, functions structurally,
    # modules by name (stage fns bake these into the program)
    gl = getattr(fn, "__globals__", {})
    names: set = set()
    _code_names(code, names)
    for name in sorted(names):
        if name in gl:
            v = gl[name]
            if isinstance(v, types.ModuleType):
                h.update(f"mod:{v.__name__}".encode())
            else:
                h.update(name.encode())
                _enc(h, v, depth + 1, seen)


def _enc(h, v: Any, depth: int = 0, seen: Optional[set] = None) -> None:
    seen = seen if seen is not None else set()
    iface = None if isinstance(v, type) else getattr(v, "iface_kind", None)
    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        h.update(f"lit:{v!r}".encode())
    elif iface in ("mmap", "async_mmap"):
        # the typed-interface contract (paper S3.1.2): an mmap argument is
        # a *runtime* device buffer, so only its aval reaches the hash —
        # two instances differing in array values share one definition.
        # Async ports fold in latency/depth: they size the lowered queue.
        h.update(f"{iface}:{v.dtype}:{tuple(v.shape)}".encode())
        if iface == "async_mmap":
            h.update(f":lat{v.latency}:d{v.depth}".encode())
    elif iface == "scalar":
        h.update(b"scalar")
        _enc(h, v.value, depth, seen)
    elif isinstance(v, types.ModuleType):
        h.update(f"mod:{v.__name__}".encode())
    elif isinstance(v, types.CodeType):
        _enc_code(h, v, depth, seen)
    elif isinstance(v, (types.FunctionType, types.MethodType, partial)) \
            or callable(v) and not isinstance(v, type):
        _enc_fn(h, v, depth, seen)
    elif isinstance(v, type):
        # classes hash by qualified name — never by their descriptor
        # attributes (a class with shape/dtype __slots__ is not an array)
        h.update(f"cls:{v.__module__}.{v.__qualname__}".encode())
    elif isinstance(v, np.ndarray):
        h.update(f"nd:{v.dtype}:{v.shape}".encode())
        h.update(np.ascontiguousarray(v).tobytes())
    elif hasattr(v, "shape") and hasattr(v, "dtype"):
        # jax arrays (hash content: constants get baked into programs) and
        # ShapeDtypeStructs (shape/dtype only — they carry no data)
        h.update(f"arr:{v.dtype}:{tuple(v.shape)}".encode())
        try:
            h.update(np.asarray(v).tobytes())
        except (TypeError, ValueError):
            pass
    elif isinstance(v, (tuple, list)):
        h.update(f"seq:{len(v)}".encode())
        for x in v:
            _enc(h, x, depth, seen)
    elif isinstance(v, dict):
        h.update(f"map:{len(v)}".encode())
        for k in sorted(v, key=_stable_repr):
            _enc(h, k, depth, seen)
            _enc(h, v[k], depth, seen)
    elif isinstance(v, (set, frozenset)):
        h.update(b"set")
        for x in sorted(v, key=_stable_repr):
            _enc(h, x, depth, seen)
    else:
        h.update(f"obj:{type(v).__qualname__}".encode())
        # default reprs are address-only: hash instance state instead (a
        # bound method's behaviour depends on __self__'s attributes)
        state = _obj_state(v)
        if state and id(v) not in seen and depth <= _MAX_FN_DEPTH:
            seen.add(id(v))
            _enc(h, state, depth + 1, seen)
        else:
            h.update(_stable_repr(v).encode())


def structural_digest(fn: Callable) -> str:
    """Stable digest of a task *definition* (no input signature).

    Contract: equal digests mean "tracing this function produces the same
    computation for the same input avals".  Covered: bytecode, constants,
    defaults, closure cell values, bound-method receiver state, referenced
    module-level globals including those read from nested functions (data
    by content, functions structurally, modules by name).  NOT covered:
    attribute chains deeper than the recursion cap and impure reads (time,
    rng, I/O) — functions doing those must bypass the cache
    (docs/codegen.md).  Deliberately NOT memoized per function object: the
    QoR loop mutates captured arrays in place, and a memo would return the
    pre-edit digest.
    """
    h = hashlib.sha256()
    _enc_fn(h, fn, 0, set())
    return h.hexdigest()


def aval_signature(args: tuple, kwargs: dict) -> tuple:
    """Shape/dtype signature of array-like args (ShapeDtypeStruct and
    interface aware: mmap/async_mmap sign by aval, scalars by value)."""
    def one(x):
        k = getattr(x, "iface_kind", None)
        if k in ("mmap", "async_mmap"):
            return (k, tuple(x.shape), str(x.dtype))
        if k == "scalar":
            return ("lit", repr(x.value))
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return ("arr", tuple(x.shape), str(x.dtype))
        if isinstance(x, (list, tuple)):
            return ("seq", tuple(one(v) for v in x))
        if isinstance(x, dict):
            return ("map", tuple(sorted((k, one(v)) for k, v in x.items())))
        return ("lit", repr(x))
    return (tuple(one(a) for a in args),
            tuple(sorted((k, one(v)) for k, v in kwargs.items())))


_aval_signature = aval_signature        # pre-rename alias


def lower_spec(v: Any) -> Any:
    """Replace interface arguments with what the XLA lowering should see:
    mmap/async_mmap become :class:`jax.ShapeDtypeStruct` placeholders (the
    buffer is a runtime input, not a baked constant) and scalars unwrap to
    their value.  Containers are converted recursively."""
    k = getattr(v, "iface_kind", None)
    if k in ("mmap", "async_mmap"):
        import jax
        return jax.ShapeDtypeStruct(v.shape, np.dtype(v.dtype))
    if k == "scalar":
        return v.value
    if isinstance(v, (list, tuple)):
        return type(v)(lower_spec(x) for x in v)
    if isinstance(v, dict):
        return {key: lower_spec(x) for key, x in v.items()}
    return v


def runtime_value(v: Any) -> Any:
    """Replace interface arguments with their runtime payload: the mmap's
    device buffer / the scalar's value — what a compiled executable is
    actually fed (mirrors :func:`lower_spec`)."""
    k = getattr(v, "iface_kind", None)
    if k in ("mmap", "async_mmap"):
        return v.data
    if k == "scalar":
        return v.value
    if isinstance(v, (list, tuple)):
        return type(v)(runtime_value(x) for x in v)
    if isinstance(v, dict):
        return {key: runtime_value(x) for key, x in v.items()}
    return v


def toolchain_tag() -> str:
    """Backend plus device kind (``tpu/TPU v5 lite``): what an executable
    was compiled for, folded into every compile key."""
    import jax
    return f"{jax.default_backend()}/{jax.devices()[0].device_kind}"


def instance_key(fn: Callable, args: tuple = (), kwargs: Optional[dict] = None,
                 *, extra: Any = None, digest: Optional[str] = None) -> str:
    """Full cache key: definition digest + aval signature + toolchain.

    Executables are only valid for (definition, input avals, jax version,
    device kind); all four are folded into the key so a toolchain upgrade,
    a backend switch or another TPU generation is a clean miss, never a
    wrong hit.  ``digest``: a
    precomputed ``structural_digest(fn)`` — callers keying many instances
    of one definition pass it to skip the redundant content hash.
    """
    import jax
    h = hashlib.sha256()
    h.update((digest or structural_digest(fn)).encode())
    h.update(_stable_repr(aval_signature(args, kwargs or {})).encode())
    h.update(f"jax:{jax.__version__}:{toolchain_tag()}:{SCHEMA}".encode())
    if extra is not None:
        _enc(h, extra)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

@dataclass
class CacheStats:
    mem_hits: int = 0
    disk_hits: int = 0
    misses: int = 0
    puts: int = 0
    evictions: int = 0
    corrupt: int = 0
    serialize_failures: int = 0
    disk_writes: int = 0            # executables that reached disk
    memo_hits: int = 0

    def as_dict(self) -> dict:
        return dict(self.__dict__)


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed in-checkout default (listed in .gitignore): the path is part of
# what makes a cache hit, so it never depends on the process or the time
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".cache" / "jax"


def cache_root() -> Path:
    """Directory of JAX's persistent compilation cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.cache/jax`` in the
    checkout.  This repo's executable store lives in its ``repro/``
    subdirectory, so one variable places both caches."""
    env = os.environ.get(CACHE_ENV)
    return Path(env) if env else _CHECKOUT_CACHE


def enable_persistent_cache() -> Path:
    """Turn on JAX's persistent compilation cache at :func:`cache_root`.

    With ``$JAX_COMPILATION_CACHE_DIR`` set JAX already reads it, and
    nothing is changed.  Process entry points (the ``launch`` CLIs,
    ``chip_smoke.py``) call this before their first compile, so a warm
    process skips XLA for jitted steps that do not go through
    :class:`CompileCache` (the training step); library functions leave
    JAX's global configuration alone."""
    import jax
    root = cache_root()
    if os.environ.get(CACHE_ENV) is None and \
            jax.config.jax_compilation_cache_dir != str(root):
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", str(root))
        compilation_cache.reset_cache()
    return root


def _devices(ids: list) -> list:
    """Visible devices by id, in the given order (KeyError when one is
    missing: the entry was built for devices this process cannot see)."""
    import jax
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in ids]


# Framed executable entries: magic + sha256(blob) + blob.  The digest makes
# any bit-level corruption (not just unpicklable truncation) detectable at
# read time, feeding the existing delete+recompile path.  Legacy unframed
# entries (pre-digest trees) still load.
_MAGIC = b"RCC1"
_DIGEST_LEN = 32


def _frame(blob: bytes) -> bytes:
    return _MAGIC + hashlib.sha256(blob).digest() + blob


def _unframe(data: bytes) -> bytes:
    if not data.startswith(_MAGIC):
        return data                     # legacy unframed entry
    digest = data[len(_MAGIC):len(_MAGIC) + _DIGEST_LEN]
    blob = data[len(_MAGIC) + _DIGEST_LEN:]
    if hashlib.sha256(blob).digest() != digest:
        raise ValueError("cache entry digest mismatch")
    return blob


class CompileCache:
    """Two-level (memory, disk) content-addressed executable store.

    Layout (versioned; a SCHEMA bump orphans old trees wholesale)::

        <root>/v1/ex/<digest[:2]>/<digest>.exe     pickled serialized exe
        <root>/v1/memo/<digest[:2]>/<digest>.json  memoized JSON results

    Disk entries carry their last-use time in mtime (bumped on every hit);
    eviction drops least-recently-used entries until the tree fits
    ``max_bytes``.  Any unreadable/undeserializable entry is deleted and
    counted in ``stats.corrupt`` — a corrupt cache costs a recompile, never
    an error.
    """

    def __init__(self, root: Optional[os.PathLike] = None,
                 max_bytes: int = 512 << 20, disk: bool = True,
                 faults: Any = None):
        self.root = Path(root) if root is not None \
            else cache_root() / "repro"
        self.max_bytes = max_bytes
        self.disk = disk
        # chaos harness (repro.core.faults): injected transient write
        # failures and post-write corruption; None in normal operation
        if faults is not None and not hasattr(faults, "io_error"):
            faults = faults.injector()
        self.faults = faults
        self.stats = CacheStats()
        self._mem: dict[str, Any] = {}
        # counted phase plans of lowered task graphs (``synth._lower``),
        # keyed by structure; memory only, like a process's traces
        self._lowered: dict[str, Any] = {}
        self._lock = threading.RLock()
        # running estimate of on-disk bytes; None until the first full
        # walk.  Keeps the per-put cost O(1): the tree is only re-walked
        # when the estimate crosses max_bytes.
        self._approx_bytes: Optional[int] = None

    # -- paths ---------------------------------------------------------------

    def _path(self, key: str, kind: str = "ex") -> Path:
        ext = "exe" if kind == "ex" else "json"
        return self.root / SCHEMA / kind / key[:2] / f"{key}.{ext}"

    def _entries(self) -> list:
        base = self.root / SCHEMA
        if not base.exists():
            return []
        out = []
        for p in base.rglob("*"):
            if p.is_file():
                try:
                    st = p.stat()
                    out.append((st.st_mtime, st.st_size, p))
                except OSError:
                    continue
        return out

    def disk_bytes(self) -> int:
        return sum(size for _, size, _ in self._entries())

    # -- executables ---------------------------------------------------------

    def get(self, key: str) -> Optional[Any]:
        exe, _ = self.get_with_source(key)
        return exe

    def get_with_source(self, key: str):
        """Return ``(executable, source)``; source in memory/disk/None."""
        with self._lock:
            exe = self._mem.get(key)
            if exe is not None:
                self.stats.mem_hits += 1
                return exe, "memory"
        if self.disk:
            p = self._path(key)
            if p.exists():
                try:
                    from jax.experimental import serialize_executable as se
                    entry = pickle.loads(_unframe(p.read_bytes()))
                    if entry.get("schema") != SCHEMA:
                        raise ValueError("schema mismatch")
                    payload, in_tree, out_tree = entry["payload"]
                    exe = se.deserialize_and_load(
                        payload, in_tree, out_tree,
                        execution_devices=_devices(entry["devices"]))
                    os.utime(p)                       # LRU bump
                    with self._lock:
                        self._mem[key] = exe
                        self.stats.disk_hits += 1
                    return exe, "disk"
                except Exception:
                    # corrupt / truncated / stale entry: delete + recompile
                    with self._lock:
                        self.stats.corrupt += 1
                    try:
                        p.unlink()
                    except OSError:
                        pass
        with self._lock:
            self.stats.misses += 1
        return None, None

    def put(self, key: str, executable: Any, meta: Optional[dict] = None
            ) -> None:
        with self._lock:
            self._mem[key] = executable
            self.stats.puts += 1
        if not self.disk:
            return
        try:
            from jax.experimental import serialize_executable as se
            payload = se.serialize(executable)
            # the devices it runs on, in order: deserializing without them
            # would spread a one-device program over every visible device
            devices = [d.id for d in
                       executable.runtime_executable().local_devices()]
            buf = io.BytesIO()
            pickle.dump({"schema": SCHEMA, "key": key, "meta": meta or {},
                         "devices": devices, "payload": payload}, buf)
        except Exception:
            # not every executable serializes (callbacks, exotic custom
            # calls); stay memory-only rather than fail the compile
            with self._lock:
                self.stats.serialize_failures += 1
            return
        path = self._path(key)
        if self._write_atomic(path, _frame(buf.getvalue()), verify=True):
            with self._lock:
                self.stats.disk_writes += 1
            if self.faults is not None and self.faults.corrupt_cache():
                self._corrupt_entry(path)   # chaos: prove delete+recompile
        self._maybe_evict()

    def compile_cached(self, fn: Callable, args: tuple = (),
                       kwargs: Optional[dict] = None, *,
                       key: Optional[str] = None, extra: Any = None,
                       hash_fn: Optional[Callable] = None,
                       jit_fn: Optional[Callable] = None,
                       jit_kwargs: Optional[dict] = None):
        """``jit(fn).lower(*args).compile()`` through the cache.

        ``hash_fn`` keys the entry on a different function than is compiled
        (e.g. hash the user's stage body, compile its shard_map wrapper
        whose internals would make a noisy hash); ``jit_fn`` overrides the
        callable handed to ``jax.jit``; ``jit_kwargs`` are forwarded to
        ``jax.jit`` (e.g. ``donate_argnums`` — input/output aliasing is
        part of the compiled HLO, so it survives (de)serialization and is
        folded into the key).  Returns ``(executable, source)``.
        """
        import jax
        kwargs = kwargs or {}
        if jit_kwargs:
            extra = (extra, sorted(jit_kwargs.items()))
        key = key or instance_key(hash_fn or fn, args, kwargs, extra=extra)
        exe, source = self.get_with_source(key)
        if exe is None:
            largs = tuple(lower_spec(a) for a in args)
            lkw = {k: lower_spec(v) for k, v in kwargs.items()}
            exe = jax.jit(jit_fn or fn, **(jit_kwargs or {})) \
                .lower(*largs, **lkw).compile()
            self.put(key, exe)
            source = "compiled"
        return exe, source

    # -- counted phase plans (CompiledEngine lowering) ------------------------

    def lowering_get(self, key: str) -> Optional[Any]:
        with self._lock:
            return self._lowered.get(key)

    def lowering_put(self, key: str, counts: Any) -> None:
        with self._lock:
            self._lowered[key] = counts

    # -- memoized JSON results (QoR-tuning measurements) ---------------------

    def memo_get(self, key: str) -> Optional[Any]:
        if not self.disk:
            return None
        p = self._path(key, "memo")
        if not p.exists():
            return None
        try:
            out = json.loads(p.read_text())
            os.utime(p)
            with self._lock:
                self.stats.memo_hits += 1
            return out
        except Exception:
            with self._lock:
                self.stats.corrupt += 1
            try:
                p.unlink()
            except OSError:
                pass
            return None

    def memo_put(self, key: str, value: Any) -> None:
        if not self.disk:
            return
        self._write_atomic(self._path(key, "memo"),
                           json.dumps(value).encode())
        self._maybe_evict()

    # -- maintenance ---------------------------------------------------------

    def _write_atomic(self, path: Path, data: bytes,
                      verify: bool = False) -> bool:
        """Write-rename a disk entry; one retry on a transient ``OSError``.

        With ``verify=True`` the published entry is read back and compared
        to what was written (verify-after-write), so a torn or silently
        failed write is caught while the original data is still in hand.
        Returns False when both attempts failed (read-only FS etc.): the
        store degrades to memory-only, never errors.
        """
        for attempt in (0, 1):
            try:
                if self.faults is not None and self.faults.io_error("cache"):
                    raise OSError("injected transient cache IO failure")
                path.parent.mkdir(parents=True, exist_ok=True)
                tmp = path.with_suffix(path.suffix + f".tmp{os.getpid()}")
                tmp.write_bytes(data)
                os.replace(tmp, path)   # readers never see partial entries
                if verify and path.read_bytes() != data:
                    raise OSError(f"verify-after-write mismatch for {path}")
                with self._lock:
                    if self._approx_bytes is not None:
                        self._approx_bytes += len(data)
                return True
            except OSError:
                if attempt:
                    return False        # read-only FS: memory level only
        return False

    def _corrupt_entry(self, path: Path) -> None:
        """Chaos-only: flip one byte mid-entry (inside the framed blob for
        any realistically-sized executable), making the published entry
        fail its digest check on the next read."""
        try:
            data = bytearray(path.read_bytes())
            if data:
                data[len(data) // 2] ^= 0xFF
                path.write_bytes(bytes(data))
        except OSError:
            pass

    def _maybe_evict(self) -> None:
        """Full-tree eviction only when the running estimate says the
        bound may be exceeded (a put is O(1) otherwise)."""
        with self._lock:
            approx = self._approx_bytes
        if approx is None or approx > self.max_bytes:
            self.evict_to_fit()

    def evict_to_fit(self) -> int:
        """Drop least-recently-used disk entries until under ``max_bytes``."""
        entries = self._entries()
        total = sum(size for _, size, _ in entries)
        dropped = 0
        for _, size, p in sorted(entries):          # oldest mtime first
            if total <= self.max_bytes:
                break
            try:
                p.unlink()
                total -= size
                dropped += 1
            except OSError:
                continue
        with self._lock:
            self.stats.evictions += dropped
            self._approx_bytes = total
        return dropped

    def clear_memory(self) -> None:
        """Drop the first level and the lowering memo (what a process
        restart does for free)."""
        with self._lock:
            self._mem.clear()
            self._lowered.clear()

    def clear(self) -> None:
        self.clear_memory()
        for _, _, p in self._entries():
            try:
                p.unlink()
            except OSError:
                pass
        with self._lock:
            self._approx_bytes = 0


# ---------------------------------------------------------------------------
# process-default cache
# ---------------------------------------------------------------------------

_default: Optional[CompileCache] = None
_default_lock = threading.Lock()


def default_cache() -> CompileCache:
    """Process-wide cache under ``cache_root() / "repro"``, bound from
    ``$REPRO_COMPILE_CACHE_MAX_MB`` (default 512)."""
    global _default
    with _default_lock:
        if _default is None:
            mb = int(os.environ.get("REPRO_COMPILE_CACHE_MAX_MB", "512"))
            _default = CompileCache(max_bytes=mb << 20)
        return _default


def set_default_cache(cache: Optional[CompileCache]) -> None:
    global _default
    with _default_lock:
        _default = cache
