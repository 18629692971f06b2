"""Offline profiling / qualification tools over query event logs.

The spark-rapids-tools analog: ``python -m spark_rapids_tpu.tools
profile <eventlog>`` turns the JSONL event logs the engine writes
(``spark.rapids.sql.eventLog.enabled`` — obs/events.py) into a
machine-readable profiling report (top operators by self time, compute
vs transfer vs shuffle breakdown, per-exchange skew, spill/retry
summary, fallback inventory, span attribution), and ``... compare A B``
diffs two runs per-query/per-operator — the tool perf PRs cite instead
of hand-timing.

Operates purely on the JSON records — no session/runtime machinery is
touched, so the CLI runs anywhere the logs land (it shares only the
event-schema constant with obs/events.py).
"""

from spark_rapids_tpu.tools.report import (  # noqa: F401
    build_profile,
    load_events,
    render_profile,
)
from spark_rapids_tpu.tools.compare import (  # noqa: F401
    build_compare,
    render_compare,
)


def require_tpu_backend():
    """THE require-a-TPU gate shared by chip_smoke.py and scale_test.py:
    resolve the JAX backend (initializes it — call only
    after any virtual-device/mesh environment setup) and exit 2 with a
    machine-readable error unless the platform is literally 'tpu' — any
    other name, not only 'cpu', is a run that meant to hit the chip and
    did not. Returns (platform, device_kind). One error contract, not
    hand-synced copies: a CPU-backend number was once committed as a
    chip reading because nothing failed loudly."""
    import json
    import sys

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({
            "error": f"backend is {dev.platform!r} but a TPU was "
                     "required (no 'tpu' platform resolved)",
            "backend": dev.platform}))
        sys.exit(2)
    return dev.platform, dev.device_kind
