"""spark_rapids_tpu — a TPU-native SQL/columnar execution engine.

A from-scratch framework with the capabilities of the RAPIDS Accelerator for
Apache Spark (reference: /root/reference, NVIDIA spark-rapids): a plan-rewrite
engine that converts SQL physical plans into columnar operators executing on
TPUs via JAX/XLA, with per-operator CPU fallback,
bit-for-bit Spark-compatible semantics, an HBM buffer catalog with host/disk
spill and OOM split-and-retry, TPU-aware shuffle (host path + ICI collectives),
and accelerated Parquet/ORC/CSV/JSON/Avro IO.

Architecture mirrors the reference's proven shape (see SURVEY.md):
  plan -> meta/tag/convert (overrides/) -> columnar execs (execs/)
       -> runtime (semaphore, spill catalog, retry) -> shuffle (parallel/)
but the substrate is XLA: expression trees are fused into single jitted
computations over statically-bucketed device columns, strings ride an
order-preserving dictionary encoding so the device only touches fixed-width
data, and distributed exchange uses jax.sharding collectives over ICI/DCN.
"""

import os as _os

import jax

# Spark semantics are 64-bit (LongType, TimestampType micros, DoubleType).
# Bit-for-bit parity requires x64 mode; TPU emulates i64/f64 (slower but
# exact), and opt-in 32-bit fast paths can be layered on later.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: TPU backend compiles dominate a cold
# process; caching them on disk amortizes across processes (the
# reference's CUDA kernels are precompiled — this is the XLA counterpart,
# SURVEY.md §7 "XLA compile-time amortization").
#: the cache directory when the environment names none: a FIXED path in
#: the checkout (listed in .gitignore). The directory is part of what
#: makes a later process hit, so it carries no host, pid or time part.
DEFAULT_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def _configured_platform() -> str:
    """The PRIMARY jax platform from explicit config ('' when the host
    relies on JAX auto-detection); only the first entry of a
    comma-separated list counts."""
    cfg = getattr(jax.config, "jax_platforms", None) or \
        _os.environ.get("JAX_PLATFORMS", "")
    return cfg.split(",")[0].strip().lower()


_compile_cache_enabled = False


def _enable_persistent_cache() -> None:
    """Place the cache. A JAX_COMPILATION_CACHE_DIR set from outside
    wins — jax reads the variable itself, so nothing is updated here;
    otherwise the directory is exactly DEFAULT_CACHE_DIR. (The minimum
    compile time worth caching stays jax's own default, or its own
    environment variable.)"""
    global _compile_cache_enabled
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    _compile_cache_enabled = True


def ensure_compile_cache() -> bool:
    """Enable the persistent compile cache once the effective backend is
    known to be non-CPU. Import time only trusts an EXPLICIT platform
    config; hosts relying on JAX auto-detection (unset JAX_PLATFORMS on
    a stock TPU VM) get the cache here, called on runtime init, via
    jax.default_backend() — which initializes the backend, so it cannot
    run at import. CPU stays uncached: XLA:CPU compiles are fast AND
    this jax's CPU AOT (de)serialization can abort/segfault on some
    programs and on feature-mismatched hosts — both observed in this
    repo's test runs. Returns whether the cache is enabled."""
    if _compile_cache_enabled:
        return True
    if _configured_platform() == "cpu" or jax.default_backend() == "cpu":
        return False
    _enable_persistent_cache()
    return _compile_cache_enabled


if _configured_platform() not in ("", "cpu"):
    # explicit non-cpu primary: safe to enable before backend init
    _enable_persistent_cache()

__version__ = "0.1.0"

from spark_rapids_tpu.conf import RapidsConf  # noqa: E402,F401
from spark_rapids_tpu import types  # noqa: E402,F401


def __getattr__(name):
    # lazy heavy imports so `import spark_rapids_tpu` stays light
    import importlib
    if name == "TpuSession":
        return importlib.import_module("spark_rapids_tpu.session").TpuSession
    if name == "functions":
        return importlib.import_module("spark_rapids_tpu.functions")
    raise AttributeError(name)
