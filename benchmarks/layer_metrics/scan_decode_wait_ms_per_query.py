"""IO / scan (io/ readers): the median over the window of the host
seconds a query's file scan execs spent outside their uploads
(`opTime - scanUploadTime`): waiting for the reader to hand over a decoded
batch, which is what the prefetch did not hide — in milliseconds."""

from benchmarks.layer_metrics.scan_common import median_of


def read(run):
    return median_of(
        run, lambda scan: scan["opTime"] - scan["scanUploadTime"], 1e3)
