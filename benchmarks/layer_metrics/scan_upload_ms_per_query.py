"""IO / scan (execs/basic.py `TpuFileScanExec`, columnar/ `stage_upload`):
the median over the window of the host seconds a query's file scan execs
spent landing decoded batches on the device (`scanUploadTime`: dictionary
encoding of string columns, staging and the host-to-device copies), in
milliseconds."""

from benchmarks.layer_metrics.scan_common import median_of


def read(run):
    return median_of(run, lambda scan: scan["scanUploadTime"], 1e3)
