"""Computation-environment presets: platform, XLA flags, host devices.

Benchmark runs (``benchmarks/run.py``) call :func:`apply_bench_preset`
first so numbers from different boxes are produced under one declared
environment instead of whatever flags the shell happened to carry.  All
helpers only take full effect *before* the JAX backend initializes —
call them at process start.

Unlike the usual one-shot recipes, every ``XLA_FLAGS`` edit here is a
**merge**: existing flags survive, and a flag already set by the user
wins over the preset — overwriting the whole variable (the common bug)
silently drops e.g. a mesh-smoke job's ``device_count`` flag.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count
from pathlib import Path

import jax

# the GPU preset from JAX's gpu_performance_tips page: fusion + async
# collectives + latency-hiding scheduling — the flags every serious GPU
# deployment sets, declared once instead of per shell
GPU_XLA_PRESET = {
    "--xla_gpu_enable_triton_softmax_fusion": "true",
    "--xla_gpu_triton_gemm_any": "True",
    "--xla_gpu_enable_async_collectives": "true",
    "--xla_gpu_enable_latency_hiding_scheduler": "true",
    "--xla_gpu_enable_highest_priority_async_stream": "true",
}


def merge_xla_flags(flags: dict[str, str], *, override: bool = False) -> str:
    """Merge ``{--flag: value}`` into ``XLA_FLAGS``, preserving existing.

    Existing flags win unless ``override``.  Returns the new value (also
    written to ``os.environ``)."""
    current: dict[str, str] = {}
    order: list[str] = []
    for tok in os.environ.get("XLA_FLAGS", "").split():
        key, _, val = tok.partition("=")
        if key not in current:
            order.append(key)
        current[key] = val
    for key, val in flags.items():
        if key not in current:
            order.append(key)
            current[key] = val
        elif override:
            current[key] = val
    merged = " ".join(
        k if current[k] == "" else f"{k}={current[k]}" for k in order)
    os.environ["XLA_FLAGS"] = merged
    return merged


def set_platform(platform: str = "cpu") -> None:
    """Select the JAX platform ('cpu' | 'gpu' | 'tpu') + its flag preset.

    Only effective before backend initialization.  On 'gpu' the
    :data:`GPU_XLA_PRESET` flags merge into ``XLA_FLAGS``.
    """
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        merge_xla_flags(GPU_XLA_PRESET)


def set_host_device_count(n: int) -> None:
    """Expose ``n`` host CPU devices (the mesh-smoke / fig6 mechanism).

    Clamps to the physical core count with a warning; only effective
    before backend initialization."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(
            f"only {total} CPUs available; exposing {total} devices",
            stacklevel=2)
        n = total
    merge_xla_flags(
        {"--xla_force_host_platform_device_count": str(n)}, override=True)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other path is set here.  Otherwise the cache goes to ``.jax_cache``
    at the repository root: a fixed path, because the path is part of
    the cache key and a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_debug_nan(flag: bool) -> None:
    """Raise on NaN production (jax_debug_nans) — debugging aid."""
    jax.config.update("jax_debug_nans", bool(flag))


def apply_bench_preset() -> None:
    """The benchmark harness's reproducible-environment preset.

    Pins the platform to the detected default backend (making the run's
    environment explicit in one place) and applies that platform's flag
    preset.  Safe to call after backend init — it only re-applies flags
    that already match the live backend."""
    backend = jax.default_backend()
    if backend == "gpu":
        merge_xla_flags(GPU_XLA_PRESET)
    # no platform switch here: the bench measures the environment it is
    # launched in; the preset's job is flag hygiene, not redirection
