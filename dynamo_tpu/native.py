"""ctypes loader for libdynamo_native (native/ — the C++ hot-path core).

Follows the environment's binding constraints (no pybind11): a plain C ABI
loaded with ctypes. Set DYNTPU_NO_NATIVE=1 to force the pure-Python
fallbacks everywhere.

Build discipline:
- `ensure_built()` — blocking compile+load; call it once from process entry
  points (CLI/worker startup) before serving.
- `lib()` — never blocks the caller on a compile: returns the loaded CDLL,
  or None while a background build (started on first miss) is running.
  Callers must keep a Python fallback path (tokens/blocks.py,
  kv_router/indexer.py do).
- The library is keyed on a hash of the tracked sources (native/*.cpp,
  xxh3.h, Makefile): it lives at native/build/libdynamo_native-<hash>.so,
  so a library built from other sources is never loaded — file times say
  nothing once a tree has been copied.
- Builds are cross-process safe: compiled under an flock to a temp name in
  native/build/, then os.replace'd into place so a concurrent loader never
  dlopens a half-written ELF.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"


def lib_path() -> Path:
    """native/build/libdynamo_native-<hash of the tracked sources>.so"""
    h = hashlib.sha256()
    for src in (
        *sorted(_NATIVE_DIR.glob("*.cpp")),
        _NATIVE_DIR / "xxh3.h",
        _NATIVE_DIR / "Makefile",
    ):
        h.update(src.name.encode() + b"\0")
        h.update(src.read_bytes())
    return _NATIVE_DIR / "build" / f"libdynamo_native-{h.hexdigest()[:16]}.so"


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_thread: Optional[threading.Thread] = None
_build_failed = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, u32, sz = ctypes.c_uint64, ctypes.c_uint32, ctypes.c_size_t
    p = ctypes.c_void_p
    lib.dyn_xxh3_64.restype = u64
    lib.dyn_xxh3_64.argtypes = [ctypes.c_char_p, sz, u64]
    lib.dyn_hash_token_blocks.restype = sz
    lib.dyn_hash_token_blocks.argtypes = [p, sz, sz, u64, u64, p, p]
    lib.dyn_radix_new.restype = p
    lib.dyn_radix_free.argtypes = [p]
    lib.dyn_radix_intern.restype = u32
    lib.dyn_radix_intern.argtypes = [p, ctypes.c_char_p]
    lib.dyn_radix_apply.argtypes = [p, u32, ctypes.c_int, p, sz]
    lib.dyn_radix_remove_worker.restype = sz
    lib.dyn_radix_remove_worker.argtypes = [p, u32]
    lib.dyn_radix_take_worker.restype = sz
    lib.dyn_radix_take_worker.argtypes = [p, u32, p, sz]
    lib.dyn_radix_digest.restype = sz
    lib.dyn_radix_digest.argtypes = [p, u32, u64, p]
    lib.dyn_radix_clear.argtypes = [p]
    lib.dyn_radix_find.restype = sz
    lib.dyn_radix_find.argtypes = [p, p, sz, p, p, sz, p]
    lib.dyn_radix_num_blocks.restype = sz
    lib.dyn_radix_num_blocks.argtypes = [p]
    lib.dyn_radix_blocks_for.restype = sz
    lib.dyn_radix_blocks_for.argtypes = [p, u32]
    lib.dyn_radix_events_applied.restype = u64
    lib.dyn_radix_events_applied.argtypes = [p]
    # pool.cpp — device page pool
    i64 = ctypes.c_int64
    lib.dyn_pool_new.restype = p
    lib.dyn_pool_new.argtypes = [u32]
    lib.dyn_pool_delete.argtypes = [p]
    lib.dyn_pool_num_free.restype = sz
    lib.dyn_pool_num_free.argtypes = [p]
    lib.dyn_pool_free_list_len.restype = sz
    lib.dyn_pool_free_list_len.argtypes = [p]
    lib.dyn_pool_peek_reclaimable.restype = sz
    lib.dyn_pool_peek_reclaimable.argtypes = [p, p, sz]
    lib.dyn_pool_allocate.restype = ctypes.c_int
    lib.dyn_pool_allocate.argtypes = [p, sz, p]
    lib.dyn_pool_release.restype = i64
    lib.dyn_pool_release.argtypes = [p, p, sz]
    lib.dyn_pool_register.restype = ctypes.c_int
    lib.dyn_pool_register.argtypes = [p, u32, u64]
    lib.dyn_pool_lookup.restype = sz
    lib.dyn_pool_lookup.argtypes = [p, p, sz, p]
    lib.dyn_pool_match_length.restype = sz
    lib.dyn_pool_match_length.argtypes = [p, p, sz]
    lib.dyn_pool_clear_cache.restype = sz
    lib.dyn_pool_clear_cache.argtypes = [p]
    lib.dyn_pool_evicted_pending.restype = sz
    lib.dyn_pool_evicted_pending.argtypes = [p]
    lib.dyn_pool_drain_evicted.restype = sz
    lib.dyn_pool_drain_evicted.argtypes = [p, p, p, sz]
    # host_tier.cpp — KVBM G2 slab store
    lib.dyn_host_new.restype = p
    lib.dyn_host_new.argtypes = [u64, u64, ctypes.c_int]
    lib.dyn_host_delete.argtypes = [p]
    lib.dyn_host_len.restype = sz
    lib.dyn_host_len.argtypes = [p]
    lib.dyn_host_used_bytes.restype = u64
    lib.dyn_host_used_bytes.argtypes = [p]
    lib.dyn_host_capacity_slots.restype = u64
    lib.dyn_host_capacity_slots.argtypes = [p]
    lib.dyn_host_contains.restype = ctypes.c_int
    lib.dyn_host_contains.argtypes = [p, u64]
    lib.dyn_host_peek_lru.restype = u64
    lib.dyn_host_peek_lru.argtypes = [p, p]
    lib.dyn_host_reserve.restype = p
    lib.dyn_host_reserve.argtypes = [p, u64]
    lib.dyn_host_get.restype = p
    lib.dyn_host_get.argtypes = [p, u64]
    lib.dyn_host_pop.restype = ctypes.c_int
    lib.dyn_host_pop.argtypes = [p, u64]
    lib.dyn_host_clear.argtypes = [p]
    # codec.cpp — two-part frame codec
    lib.dyn_frame_prefix.argtypes = [p, sz, p, sz, p]
    lib.dyn_frame_parse_prefix.restype = ctypes.c_int
    lib.dyn_frame_parse_prefix.argtypes = [p, p, p]
    lib.dyn_frame_check.restype = ctypes.c_int
    lib.dyn_frame_check.argtypes = [p, p, sz, p, sz]
    # kv_events.cpp — external-engine KV-event publisher
    lib.dyn_kv_pub_connect.restype = p
    lib.dyn_kv_pub_connect.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
    ]
    lib.dyn_kv_pub_publish.restype = ctypes.c_int
    lib.dyn_kv_pub_publish.argtypes = [p, ctypes.c_int, p, sz, i64]
    lib.dyn_kv_pub_last_error.restype = ctypes.c_char_p
    lib.dyn_kv_pub_last_error.argtypes = [p]
    lib.dyn_kv_pub_close.argtypes = [p]
    return lib


def _build() -> bool:
    """Compile under an inter-process lock; atomic rename into place."""
    build_dir = _NATIVE_DIR / "build"
    tmp = build_dir / f".tmp.{os.getpid()}.so"
    try:
        target = lib_path()
        build_dir.mkdir(parents=True, exist_ok=True)
        lock_path = build_dir / ".build.lock"
        with open(lock_path, "w") as lock_f:
            import fcntl

            fcntl.flock(lock_f, fcntl.LOCK_EX)
            try:
                if target.exists():  # another process built it meanwhile
                    return True
                proc = subprocess.run(
                    ["make", "-s", "-C", str(_NATIVE_DIR),
                     f"LIB=build/{tmp.name}"],
                    capture_output=True, text=True, timeout=180,
                )
                if proc.returncode != 0:
                    logger.warning("native build failed:\n%s", proc.stderr[-2000:])
                    return False
                os.replace(tmp, target)
                # libraries of other source revisions are dead weight
                for old in build_dir.glob("libdynamo_native*.so"):
                    if old != target:
                        old.unlink(missing_ok=True)
                return True
            finally:
                tmp.unlink(missing_ok=True)
                fcntl.flock(lock_f, fcntl.LOCK_UN)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build unavailable: %s", e)
        tmp.unlink(missing_ok=True)
        return False


def _load() -> Optional[ctypes.CDLL]:
    """Must be called with _lock held. Latches failure: a present-but-
    unloadable .so (corrupt/ABI mismatch) must not be retried per request."""
    global _lib, _build_failed
    path = lib_path()
    try:
        _lib = _configure(ctypes.CDLL(str(path)))
    except OSError as e:
        logger.warning("could not load %s: %s", path, e)
        _lib = None
        _build_failed = True
    return _lib


def ensure_built(timeout_s: float = 180.0) -> Optional[ctypes.CDLL]:
    """Blocking build+load. Call from process entry points before serving."""
    global _build_failed
    if os.environ.get("DYNTPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        t = _build_thread
    if t is not None:
        t.join(timeout=timeout_s)
    # Compile OUTSIDE _lock: concurrent lib() callers must stay non-blocking
    # (they fall back to Python while this thread builds). _build() itself is
    # flock-serialized, so parallel ensure_built calls don't race the .so.
    built = lib_path().exists() or _build()
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        if not built:
            _build_failed = True
            return None
        return _load()


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None. Never compiles on the caller's
    thread: a stale/missing .so kicks off one background build and this
    returns None until it lands (pure-Python fallbacks cover the gap)."""
    global _build_thread, _build_failed
    if os.environ.get("DYNTPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if lib_path().exists():
            return _load()
        if _build_thread is None or not _build_thread.is_alive():

            def _bg():
                global _build_failed
                ok = _build()
                with _lock:
                    if ok:
                        _load()  # latches _build_failed itself on error
                    else:
                        _build_failed = True

            _build_thread = threading.Thread(
                target=_bg, name="dynamo-native-build", daemon=True
            )
            _build_thread.start()
        return None
