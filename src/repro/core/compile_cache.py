"""Persistent compiled-executable cache (DESIGN.md §14).

Two stacked layers take a restarted process from 45–425 ms cold
dispatch to ~the steady path:

  export layer   this module: ``jax.export`` artifacts (serialized
                 StableHLO, shape-specialized) content-addressed by
                 ``plan.cache_fingerprint`` under
                 ``<root>/entries/<fp>.bin``. A hit skips the Python
                 trace + lowering for that (program, arg-signature).
  XLA layer      jax's own persistent compilation cache with the
                 size/time thresholds zeroed (the default thresholds
                 skip CPU programs entirely). It lives where
                 ``JAX_COMPILATION_CACHE_DIR`` says when that is set —
                 no code then sets another directory — and at
                 ``<root>/xla`` otherwise. A hit skips the StableHLO ->
                 machine-code compile — for both export replays AND
                 fresh traces.

Both layers are write-through and crash-safe: entries land via
tmp-file + ``os.replace`` so readers never observe a torn write, and
ANY failure (corrupt blob, version drift, unwritable dir) degrades to
a fresh in-process compile — the cache is an accelerator, never a
correctness dependency. ``jax.export`` round-trips are bitwise: a
deserialized executable computes exactly what the fresh-compiled one
does (tests/test_compile_cache.py proves it cross-process against the
golden fixture).

Layout under ``EngineConfig.compile_cache_dir``:

    entries/<sha256>.bin   serialized exported program
    manifest.json          informational: fp -> {key, args_sig, size,
                           created} (best-effort; losing it costs
                           nothing — the entries are content-addressed)
    prewarm.json           optional Executor.manifest() snapshot for
                           manifest-driven prewarm across restarts
    xla/                   jax's persistent compilation cache (unless
                           JAX_COMPILATION_CACHE_DIR places it)

``default_cache_root()`` is the root programs use when none is
configured: the fixed ``lilis/`` subdirectory of
``JAX_COMPILATION_CACHE_DIR`` when that is set, else ``.jax_cache/``
at the top of the checkout (git-ignored). The path is part of what a
later run looks up, so it never depends on the time or the process.

Eviction is size-capped LRU by mtime: ``load`` bumps the entry's
mtime, ``store`` triggers a sweep deleting oldest-first until the
entries/ tree fits ``EngineConfig.compile_cache_bytes``.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional, Tuple

import jax

from repro.core.plan import CACHE_SCHEMA, cache_fingerprint

__all__ = ["CompileCache", "process_context", "enable_xla_cache",
           "default_cache_root", "save_manifest", "load_manifest"]

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def default_cache_root() -> str:
    """Fixed cache root: ``$JAX_COMPILATION_CACHE_DIR/lilis`` when the
    variable is set, else ``.jax_cache/`` in the checkout."""
    env = os.environ.get(_ENV_DIR)
    return str(Path(env) / "lilis") if env else str(CHECKOUT_CACHE)


def code_digest(root=Path(__file__).resolve().parents[1]) -> str:
    """sha256 over the package's Python sources (paths and bytes). An
    exported program is the code that traced it: without this, a cache
    kept across a code change replays the old program (a fixed
    mutation kernel kept returning the old, wrong answers on the
    chip until its stale artifact stopped matching)."""
    h = hashlib.sha256()
    for f in sorted(Path(root).rglob("*.py")):
        h.update(str(f.relative_to(root)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()


def process_context() -> dict:
    """Process-level invariants folded into every cache fingerprint:
    an artifact is only reachable from an environment that would have
    produced the identical program."""
    import jaxlib
    dev = jax.devices()[0]
    return {
        "schema": CACHE_SCHEMA,
        "code": code_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


_XLA_LOCK = threading.Lock()
_XLA_DIR: Optional[str] = None


def enable_xla_cache(path) -> bool:
    """Turn on jax's persistent compilation cache (process-global;
    first caller wins): in ``JAX_COMPILATION_CACHE_DIR`` when that is
    set — jax reads it itself, and ``path`` is ignored — else at
    ``path``. Zeroes the entry-size / compile-time thresholds — their
    defaults silently skip every CPU program."""
    global _XLA_DIR
    env = os.environ.get(_ENV_DIR)
    path = str(env or path)
    with _XLA_LOCK:
        if _XLA_DIR is not None:
            return _XLA_DIR == path
        try:
            os.makedirs(path, exist_ok=True)
            if not env:
                jax.config.update("jax_compilation_cache_dir", path)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            # the dir only takes effect at cache-singleton init: when
            # the backend already ran a computation (it always has, by
            # Executor-construction time) the singleton must be reset
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc)
            _cc.reset_cache()
        except Exception:
            return False
        _XLA_DIR = path
        return True


def save_manifest(path, manifest: dict) -> None:
    """Atomic JSON write of an Executor.manifest() snapshot."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        os.replace(tmp, str(path))
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_manifest(path) -> Optional[dict]:
    """Read a prewarm manifest; None if missing or unreadable."""
    try:
        with open(path) as f:
            m = json.load(f)
        return m if isinstance(m, dict) else None
    except (OSError, ValueError):
        return None


class CompileCache:
    """Content-addressed blob store for serialized exported programs.

    Thread-safe for the executor's use: stores happen from the
    dispatch path and from the async precompile worker concurrently;
    the atomic-replace protocol makes racing stores of the same
    fingerprint idempotent (both write identical bytes).
    """

    def __init__(self, root, max_bytes: int = 1 << 30,
                 context: Optional[dict] = None):
        self.root = Path(root)
        self.entries = self.root / "entries"
        self.entries.mkdir(parents=True, exist_ok=True)
        self.max_bytes = int(max_bytes)
        self.context = dict(context or {})
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        enable_xla_cache(self.root / "xla")

    # -- addressing ---------------------------------------------------

    def fingerprint(self, key: Tuple, args_sig: Tuple) -> str:
        return cache_fingerprint(self.context, key, args_sig)

    def _path(self, fp: str) -> Path:
        return self.entries / f"{fp}.bin"

    # -- blob I/O -----------------------------------------------------

    def load(self, fp: str) -> Optional[bytes]:
        """The serialized artifact, or None (miss). Bumps mtime (LRU)."""
        p = self._path(fp)
        try:
            data = p.read_bytes()
        except OSError:
            with self._lock:
                self.misses += 1
            return None
        try:
            os.utime(p)
        except OSError:
            pass
        with self._lock:
            self.hits += 1
        return data

    def store(self, fp: str, data: bytes, meta: Optional[dict] = None
              ) -> bool:
        """Atomically persist one artifact; best-effort (False on any
        I/O failure — the in-memory executable is unaffected)."""
        p = self._path(fp)
        try:
            fd, tmp = tempfile.mkstemp(dir=str(self.entries),
                                       suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, str(p))
        except OSError:
            try:
                os.unlink(tmp)
            except (OSError, UnboundLocalError):
                pass
            return False
        if meta is not None:
            self._note(fp, dict(meta, size=len(data)))
        self.evict()
        return True

    def invalidate(self, fp: str) -> None:
        """Drop a corrupt/undeserializable entry so the next process
        recompiles fresh instead of tripping on it again."""
        try:
            os.unlink(self._path(fp))
        except OSError:
            pass

    # -- hygiene ------------------------------------------------------

    def evict(self, max_bytes: Optional[int] = None) -> int:
        """Size-capped LRU-by-mtime sweep of entries/. Returns the
        number of entries deleted."""
        cap = self.max_bytes if max_bytes is None else int(max_bytes)
        try:
            ents = [(p.stat().st_mtime, p.stat().st_size, p)
                    for p in self.entries.glob("*.bin")]
        except OSError:
            return 0
        total = sum(s for _, s, _ in ents)
        if total <= cap:
            return 0
        dropped = 0
        for _, size, p in sorted(ents):        # oldest mtime first
            if total <= cap:
                break
            try:
                p.unlink()
            except OSError:
                continue
            total -= size
            dropped += 1
        return dropped

    def size_bytes(self) -> int:
        try:
            return sum(p.stat().st_size
                       for p in self.entries.glob("*.bin"))
        except OSError:
            return 0

    def __len__(self) -> int:
        try:
            return sum(1 for _ in self.entries.glob("*.bin"))
        except OSError:
            return 0

    # -- informational manifest ---------------------------------------

    def _note(self, fp: str, meta: dict) -> None:
        """Best-effort manifest.json update (atomic replace). Purely
        informational — the store is content-addressed."""
        mpath = self.root / "manifest.json"
        with self._lock:
            try:
                try:
                    with open(mpath) as f:
                        man = json.load(f)
                    if not isinstance(man, dict):
                        man = {}
                except (OSError, ValueError):
                    man = {}
                man[fp] = dict(meta, created=round(time.time(), 3))
                fd, tmp = tempfile.mkstemp(dir=str(self.root),
                                           suffix=".tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump(man, f, indent=1, sort_keys=True)
                os.replace(tmp, str(mpath))
            except OSError:
                pass
