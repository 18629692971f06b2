"""IO / scan (execs/basic.py `TpuFileScanExec`): the median over the
window of the rows a query pulled through its file scan execs
(`scanRows`): the table's rows while nothing prunes row groups or
files."""

from benchmarks.layer_metrics.scan_common import median_of


def read(run):
    return median_of(run, lambda scan: scan["scanRows"])
