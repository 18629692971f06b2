"""What the four `scan_*` readers share: the window's queries' file scan
metrics (`record["scan"]`: the plan's `TpuFileScanExec` nodes' `opTime`,
`scanUploadTime`, `scanBatches`, `scanRows`, summed by
`sut.slim_record`), reduced to a median over the queries. A window whose
records carry no file scan gives nothing."""

import statistics


def median_of(run, value_of, scale=1.0):
    """The median over the window's queries of `value_of(scan)`."""
    values = [value_of(q["record"]["scan"]) for q in run["queries"]
              if q.get("record") and q["record"].get("scan")]
    return statistics.median(values) * scale if values else None
