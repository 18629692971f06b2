"""Test fixtures. Runs JAX on a virtual 8-device CPU mesh so multi-chip
sharding logic is exercised without TPU hardware (the driver dry-runs the
real multi-chip path separately)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# the config update holds even if jax was imported (and read the
# environment) before this file ran
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# run the static plan verifier in ERROR mode for every session the test
# suite creates (the spark-rapids `spark.rapids.sql.test.enabled`
# assert-on-fallback pattern): any structural invariant a converted plan
# violates fails the test that built it. Injected per-session rather
# than flipped in the conf REGISTRY so generated docs (CONFIGS.md drift
# tests) still show the production default.
from spark_rapids_tpu.session import TpuSession  # noqa: E402

_ORIG_SESSION_INIT = TpuSession.__init__


def _verifying_init(self, conf=None):
    conf = dict(conf or {})
    conf.setdefault("spark.rapids.sql.planVerify.mode", "error")
    _ORIG_SESSION_INIT(self, conf)


TpuSession.__init__ = _verifying_init

_TESTS_RUN = {"n": 0}


@pytest.fixture(autouse=True)
def _periodic_jax_cache_clear():
    """Free jitted XLA:CPU executables every few hundred tests. One
    full-suite process otherwise accumulates ~1k compiled programs;
    on some hosts XLA's CPU compiler segfaults once that much JIT
    state has piled up (observed at ~95% of the suite, always inside
    backend_compile). Recompiles cost a little time; crashes cost
    the whole run."""
    yield
    _TESTS_RUN["n"] += 1
    if _TESTS_RUN["n"] % 250 == 0:
        jax.clear_caches()


@pytest.fixture(scope="session")
def session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession()


@pytest.fixture(scope="session")
def cpu_session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.sql.enabled": "false"})
