"""Bring-up contracts (ISSUE 21): a compile cache the driver can place,
no quiet CPU on the chip path.

Everything here runs on the CPU backend; what only a chip can show is
chip_smoke.py's job."""

import os
import shutil
import subprocess
import sys
import time

import pytest

import jax

import spark_rapids_tpu as st
from spark_rapids_tpu.conf import RapidsConf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- a compile cache that can be placed from outside -------------------------

@pytest.fixture
def cache_updates(monkeypatch):
    """Spy on jax.config.update; cache keys are recorded, not applied."""
    monkeypatch.setattr(st, "_compile_cache_enabled", False)
    seen = []
    real_update = jax.config.update

    def spy(key, value):
        if "cache" in key:
            seen.append((key, value))
            return None
        return real_update(key, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return seen


def test_cache_dir_from_environment_is_left_alone(monkeypatch, cache_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(st, "_configured_platform", lambda: "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert st.ensure_compile_cache() is True
    assert cache_updates == []  # jax reads the variable itself


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch,
                                                    cache_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(st, "_configured_platform", lambda: "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for _ in range(2):  # two successive enables: the identical path
        monkeypatch.setattr(st, "_compile_cache_enabled", False)
        assert st.ensure_compile_cache() is True
    want = ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))
    assert cache_updates == [want, want]
    assert "SPARK_RAPIDS_TPU_CACHE" not in open(st.__file__).read()


def test_cpu_backend_stays_uncached(monkeypatch, cache_updates):
    # explicit cpu config: never enables, never probes the backend
    monkeypatch.setattr(st, "_configured_platform", lambda: "cpu")
    assert st.ensure_compile_cache() is False
    # auto-detection that resolved to cpu: uncached too
    monkeypatch.setattr(st, "_configured_platform", lambda: "")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert st.ensure_compile_cache() is False
    assert cache_updates == []


# -- no quiet CPU on the chip path -------------------------------------------

def test_chip_smoke_refuses_the_cpu_backend():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line
    assert "backend is 'cpu'" in proc.stderr
    assert "data {" not in proc.stderr  # stopped before generating data
    assert time.monotonic() - t0 < 60


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout == ""


def test_chip_smoke_last_line_is_exactly_ok_and_device(monkeypatch, capsys):
    """The driver reads the LAST stdout line and wants exactly these keys;
    the readings travel on the line before it."""
    import json

    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    child = {"device": device, "data": {}, "hbm": {}, "phases": ["device"],
             "seconds": {}, "child_wall_s": 1.0,
             "demotions": {}, "f64_on_device": {}, "native_available": True,
             "persistent_cache": {"dir": "d", "hits": 1, "misses": 0}}
    monkeypatch.setattr(cs, "run_child", lambda *a: dict(child))
    cs.parent(1000)
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    report = json.loads(lines[-2])["report"]
    assert report["mesh"] == "skipped: 1 device(s)"
    assert report["persistent_cache"]["B"]["hits"] == 1


class _FakeDevice:
    def __init__(self, platform, kind="TPU v5 lite", stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform", ["cpu", "gpu", "some-plugin"])
def test_require_tpu_backend_rejects_other_platforms(monkeypatch, platform,
                                                     capsys):
    from spark_rapids_tpu.tools import require_tpu_backend
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(platform)])
    with pytest.raises(SystemExit) as exc:
        require_tpu_backend()
    assert exc.value.code == 2
    assert repr(platform) in capsys.readouterr().out


def test_require_tpu_backend_returns_platform_and_kind(monkeypatch):
    from spark_rapids_tpu.tools import require_tpu_backend
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("tpu")])
    assert require_tpu_backend() == ("tpu", "TPU v5 lite")


def test_executor_subprocess_is_pinned_to_the_cpu(monkeypatch):
    """Executors decode on the host by design; an inherited
    JAX_PLATFORMS must not send them after the driver's chip."""
    from spark_rapids_tpu.runtime import cluster
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    seen = {}

    def fake_popen(cmd, env=None, **_kw):
        seen["env"] = env
        return object()

    monkeypatch.setattr(cluster.subprocess, "Popen", fake_popen)
    cluster.spawn_executor(("127.0.0.1", 1), "h0")
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_hbm_stand_in_is_the_cpu_backends_only():
    from spark_rapids_tpu.errors import ColumnarProcessingError
    from spark_rapids_tpu.runtime.device_manager import reported_hbm_bytes
    assert reported_hbm_bytes(_FakeDevice("cpu")) == 16 << 30
    assert reported_hbm_bytes(
        _FakeDevice("tpu", stats={"bytes_limit": 123})) == 123
    with pytest.raises(ColumnarProcessingError, match="no memory limit"):
        reported_hbm_bytes(_FakeDevice("tpu", stats={}))


def test_arbiter_budget_comes_from_the_device_without_a_manager(monkeypatch):
    from spark_rapids_tpu.runtime import device_manager as dm
    from spark_rapids_tpu.runtime.memory import MemoryArbiter
    monkeypatch.setattr(dm.TpuDeviceManager, "_instance", None)
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _FakeDevice("tpu", stats={"bytes_limit": 10 << 30})])
    conf = RapidsConf({"spark.rapids.memory.gpu.allocFraction": 0.5,
                       "spark.rapids.memory.gpu.reserve": 1 << 30})
    assert MemoryArbiter._backend_budget(conf) == (5 << 30) - (1 << 30)


def test_device_manager_discovery_and_selection():
    """Resource discovery + device selection (GpuDeviceManager analog):
    topology facts recorded, explicit ordinal honored, bad ordinal
    rejected with a clear error."""
    from spark_rapids_tpu.errors import ColumnarProcessingError
    from spark_rapids_tpu.runtime.device_manager import TpuDeviceManager
    m = TpuDeviceManager(RapidsConf())
    m.initialize()
    topo = m.topology()
    assert topo["local_devices"] >= 1
    assert 0 <= topo["device_ordinal"] < topo["local_devices"]
    assert topo["hbm_limit_bytes"] > 0
    assert topo["num_processes"] >= 1

    m2 = TpuDeviceManager(RapidsConf(
        {"spark.rapids.tpu.deviceOrdinal": topo["local_devices"] - 1}))
    m2.initialize()
    assert m2.topology()["device_ordinal"] == topo["local_devices"] - 1

    bad = TpuDeviceManager(RapidsConf(
        {"spark.rapids.tpu.deviceOrdinal": 4096}))
    with pytest.raises(ColumnarProcessingError):
        bad.initialize()
