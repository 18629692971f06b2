"""Fatal-error capture + debug dumps.

Reference (SURVEY.md §5): ``GpuCoreDumpHandler.scala`` — on a fatal CUDA
error the executor captures a GPU core dump via a named-pipe monitor and
streams it out, then ``RapidsExecutorPlugin.onTaskFailed`` exits the
process with code 20 so Spark reschedules on another node;
``DumpUtils.scala`` dumps cudf tables to parquet for debugging.

TPU mapping: fatal XLA/PJRT errors (non-OOM XlaRuntimeError: INTERNAL,
device halted, PJRT connection lost) trigger a crash-report capture — device
memory stats, buffer-catalog state, the failing plan, the exception, and
a faulthandler-style thread dump — written to the configured dump dir.
``FATAL_EXIT_CODE`` and ``exit_on_fatal`` implement the
reschedule-elsewhere protocol for executor deployments."""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
from typing import Optional

from spark_rapids_tpu.conf import RapidsConf, bool_conf, str_conf

FATAL_EXIT_CODE = 20  # reference: RapidsExecutorPlugin exits 20

CRASH_DUMP_DIR = str_conf(
    "spark.rapids.memory.crashDump.dir", "/tmp/rapids_tpu_crash",
    "Directory for fatal-device-error crash reports (GpuCoreDumpHandler "
    "analog).")

EXIT_ON_FATAL = bool_conf(
    "spark.rapids.fatalError.exit", False,
    "Exit the process with code 20 on a fatal device error so the "
    "scheduler replaces this executor (reference Plugin.scala:669-694).")


def is_fatal_device_error(exc: BaseException) -> bool:
    """Fatal = device/runtime failure that is NOT a recoverable OOM.
    Distinct from the per-op KernelCrashError class the circuit breaker
    owns: a fatal error means the DEVICE (or its PJRT client) is gone,
    so recovery is backend reinitialization (runtime/health.py), not
    operator demotion."""
    from spark_rapids_tpu.errors import DeviceLostError
    from spark_rapids_tpu.runtime.retry import is_device_oom
    if isinstance(exc, DeviceLostError):
        return True  # already classified (typed injection / re-raise)
    if is_device_oom(exc):
        return False
    name = type(exc).__name__
    msg = str(exc)
    return "XlaRuntimeError" in name and any(
        k in msg for k in ("INTERNAL", "UNAVAILABLE", "ABORTED",
                           "device halted", "DEADLINE_EXCEEDED"))


def write_crash_report(exc: BaseException, conf: RapidsConf,
                       plan_description: str = "") -> Optional[str]:
    """Capture a crash report; returns the report path (best effort — a
    crash handler must never raise)."""
    try:
        dump_dir = str(conf.get_entry(CRASH_DUMP_DIR))
        os.makedirs(dump_dir, exist_ok=True)
        path = os.path.join(dump_dir, f"crash_{int(time.time() * 1000)}.json")
        report = {
            "timestamp": time.time(),
            "exception_type": type(exc).__name__,
            "exception": str(exc),
            "traceback": traceback.format_exc(),
            "plan": plan_description,
        }
        try:
            import jax
            dev = jax.devices()[0]
            report["device"] = {"platform": dev.platform,
                                "kind": getattr(dev, "device_kind", "")}
            try:
                report["memory_stats"] = {
                    k: int(v) for k, v in dev.memory_stats().items()}
            except Exception:
                pass
        except Exception:
            pass
        try:
            from spark_rapids_tpu.runtime.spill import BufferCatalog
            cat = BufferCatalog.get()
            report["buffer_catalog"] = {
                "device_bytes": cat.device_bytes(),
                "host_bytes": cat.host_bytes(),
                "spill_device_count": cat.spill_device_count,
                "spill_disk_count": cat.spill_disk_count,
            }
        except Exception:
            pass
        try:
            import threading
            names = {t.ident: t.name for t in threading.enumerate()}
            dump = []
            for tid, frame in sys._current_frames().items():
                dump.append(f"Thread {names.get(tid, tid)}:\n"
                            + "".join(traceback.format_stack(frame)))
            report["thread_dump"] = "\n".join(dump)
        except Exception:
            pass
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        return path
    except Exception:
        return None


def handle_fatal(exc: BaseException, conf: RapidsConf,
                 plan_description: str = "") -> None:
    """Executor fatal-error protocol: capture a report, optionally exit 20
    (the caller re-raises when we return)."""
    path = write_crash_report(exc, conf, plan_description)
    if path:
        print(f"[spark-rapids-tpu] fatal device error; crash report at "
              f"{path}", file=sys.stderr)
    if bool(conf.get_entry(EXIT_ON_FATAL)):
        # os._exit skips atexit handlers, so the disk-tier spill files
        # must be swept HERE — the crash-exit path is exactly where
        # they used to leak (the catalog's shutdown() never ran)
        try:
            from spark_rapids_tpu.runtime.spill import _atexit_spill_sweep
            _atexit_spill_sweep()
        except Exception:
            pass
        # likewise the transactional writer's staging trees: a write
        # job in flight when the device dies must not leave
        # _temporary/ debris for the rescheduled executor's scans
        # (the committed destination is untouched — the replayed job
        # re-stages and re-promotes the same deterministic names)
        try:
            from spark_rapids_tpu.io.committer import sweep_active_jobs
            sweep_active_jobs()
        except Exception:
            pass
        sys.stderr.flush()
        os._exit(FATAL_EXIT_CODE)


def dump_table(table, path: str) -> str:
    """Dump a Host/Device table to parquet for debugging
    (DumpUtils.scala analog; LORE uses the same shape)."""
    from spark_rapids_tpu.io.arrow_convert import host_table_to_arrow
    import pyarrow.parquet as pq
    host = table.to_host() if hasattr(table, "to_host") else table
    pq.write_table(host_table_to_arrow(host), path)
    return path
