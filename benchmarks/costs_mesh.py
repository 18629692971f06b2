"""What the mesh cell's kernel readers measure against, from the shapes
and the trace alone: the least seconds the MESH could take to read what
a statement reads, and the device time of collective operations. The
same whatever implements the aggregate or the exchange.

The bytes are `costs.scan_bytes` (rows x landed width of the columns the
text names), as for `scan_roofline`; the bandwidth is every chip's: a
table sharded over `chips` chips is read by all of them at once."""

from __future__ import annotations

#: HLO opcodes that move data between chips, with their asynchronous
#: `-start` / `-done` halves
COLLECTIVE_OPCODES = frozenset(
    base + suffix
    for base in ("all-gather", "all-reduce", "all-to-all",
                 "collective-permute", "reduce-scatter",
                 "collective-broadcast")
    for suffix in ("", "-start", "-done"))


def mesh_least_seconds(run: dict, query_ids) -> float:
    """The least seconds the traced chips could take to read what the
    statements `query_ids` read: their bytes over chips x one chip's
    peak HBM bandwidth. `run["trace"]["chips"]` is the number of device
    planes the trace held."""
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    needed = sum(run["scan_bytes_per_query"][q] for q in query_ids)
    chips = int(run["trace"].get("chips", 1))
    return needed / (chips * run["peaks"][kind]["hbm_bytes_per_s"])


def opcode_of(op_name: str) -> str:
    """The HLO opcode in one of `trace_reduce`'s operation names,
    `<program> <result> <opcode> <shape>` (the shape may be missing)."""
    parts = op_name.split(" ")
    return parts[2] if len(parts) >= 3 else ""


def collective_seconds(trace: dict) -> float:
    """Self seconds, per chip, of the device operations of a reduced
    trace whose opcode is a collective."""
    return sum(seconds for name, seconds in trace["device_ops"]
               if opcode_of(name) in COLLECTIVE_OPCODES)
