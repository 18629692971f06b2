"""IO / scan (io/, execs/basic.py `TpuFileScanExec`): the median over the
window of the host seconds a query spent inside its file scan execs —
waiting for the reader's decoded batches, encoding strings to
dictionaries, staging and uploading (`opTime` of the plan's scan nodes) —
in milliseconds."""

from benchmarks.layer_metrics.scan_common import median_of


def read(run):
    return median_of(run, lambda scan: scan["opTime"], 1e3)
