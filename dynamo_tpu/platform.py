"""Process-level device hygiene: which platform a run may land on, where
the persistent compile cache lives, and the per-generation chip peaks.

The platform comes from JAX_PLATFORMS in the environment and nothing in
the package rewrites it. Left unset, jax falls back to the CPU when it
finds no accelerator; entry points that measure or prove something on
the chip call `require_platform()` so that fallback is an error.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import dynamo_tpu

#: compile cache used when JAX_COMPILATION_CACHE_DIR is unset: one fixed
#: directory inside the checkout (the path is part of the cache key, so
#: a directory that moves never hits)
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parent.parent / ".jax_cache"
)


def require_platform() -> str:
    """The platform this process runs on: "cpu" only when the operator
    set JAX_PLATFORMS=cpu, otherwise "tpu" — or RuntimeError when no TPU
    can be had (left alone, jax falls back to the CPU without being
    asked). A chip belongs to one process at a time, so this is also
    where a second engine process on one host fails."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return "cpu"
    import jax

    hint = (
        "A TPU chip belongs to one process at a time: if another process "
        "on this host holds it (a second worker, a parent that touched "
        "jax), this one cannot have it — serve several engines from one "
        "process, or give each child its own device. Set "
        "JAX_PLATFORMS=cpu to run on the CPU on purpose."
    )
    try:
        found = jax.devices()[0].platform
    except RuntimeError as e:
        raise RuntimeError(f"no TPU could be opened ({e}). {hint}") from e
    if found != "tpu":
        raise RuntimeError(
            f"no TPU attached (jax found {found!r}). {hint}"
        )
    return "tpu"


def process_age_s() -> float:
    """Seconds since this process started, as the OS has it: on Linux
    `/proc/self/stat`'s starttime (clock ticks after boot) against
    CLOCK_BOOTTIME. Where that cannot be read, or reads younger than the
    package's own import, seconds since `dynamo_tpu` was imported."""
    imported_s = time.perf_counter() - dynamo_tpu.IMPORTED_PERF_S
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's name, which may hold spaces
            fields = f.read().rsplit(")", 1)[1].split()
        started_s = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age_s = time.clock_gettime(time.CLOCK_BOOTTIME) - started_s
    except (OSError, ValueError, IndexError, AttributeError):
        return imported_s
    return max(age_s, imported_s)


def enable_persistent_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache so worker restarts (and
    repeated runs on one machine) skip recompiles, and return the
    directory in force. Where JAX_COMPILATION_CACHE_DIR is set jax reads
    it itself and no directory is set in code; otherwise the cache is
    DEFAULT_COMPILE_CACHE_DIR. Process entry points call this once,
    before the first compile."""
    import jax

    # default min-compile-time gate (1 s) would skip most decode buckets;
    # cache everything non-trivial
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    return jax.config.jax_compilation_cache_dir


#: HBM capacity bytes per chip by TPU generation (public numbers):
#: device_kind substring tag -> capacity. The chip's peaks (FLOP/s,
#: bytes/s) live with the benchmark, in chipbench/peaks.json. Order
#: matters: longer/more-specific tags first ("v5e" before "v5lite"
#: would both miss "v5 lite" after the space strip — keep both).
_TPU_HBM_BYTES = (
    ("v6e", 32e9),
    ("v6", 32e9),
    ("v5p", 95e9),
    ("v5e", 16e9),
    ("v5lite", 16e9),
    ("v4", 32e9),
)


def device_hbm_bytes() -> float:
    """Per-chip HBM capacity — the `free` denominator of the HBM
    accounting plane (engine.memory_report / GET /v1/debug/memory) when
    the backend exposes no memory_stats (the documented CPU fallback).
    TPU generations resolve to their public capacities (a kind the
    table does not know is an error); off-TPU the fallback comes from
    DYNTPU_HBM_BYTES (else a nominal 16e9, the v5e capacity, so
    free/peak stay plausible on CPU dev boxes)."""
    import jax

    if jax.default_backend() == "tpu":
        kind = jax.devices()[0].device_kind
        tag_of = kind.lower().replace(" ", "")
        for tag, capacity in _TPU_HBM_BYTES:
            if tag in tag_of:
                return capacity
        raise ValueError(
            f"TPU device_kind {kind!r} matches no row of "
            "dynamo_tpu.platform._TPU_HBM_BYTES; add its public capacity"
        )
    try:
        env = float(os.environ.get("DYNTPU_HBM_BYTES", "") or 0.0)
        if env > 0:
            return env
    except ValueError:
        pass
    return 16e9
