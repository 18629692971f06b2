"""Mesh (execs/exchange.py `TpuShuffleExchangeExec`): the median over the
window of the host seconds a query spent in the ICI exchanges of a
shuffled join — the key projection and the enqueue of one
`mesh_exchange` program an input batch, the range `srt.mesh.exchange`
(`phasesS.exchangeS` of the event record) — in milliseconds. 0 where no
exchange ran. A program that records no such phase gives nothing."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("exchangeS")
              for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
