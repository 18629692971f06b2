"""The one general traffic generator: a mix is a data file of parameters
under `benchmarks/traffic/`, and this turns it into the statements a
window sends.

A mix names its loop (`closed`: a client sends its next statement when the
last one's result is in its hands), its clients, and its queries in the
order they are sent, round after round, each with the values of the spec's
substitution parameters. The parameters are fixed in the mix: a changed
literal recompiles in the engine (PERF.md, Open questions), so parameters
drawn per execution arrive with the cell that can be warmed for them.
"""

from __future__ import annotations

import functools
import itertools
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError(
            f"traffic {name!r}: this generator drives a closed loop with "
            f"one client; got loop={mix.get('loop')!r} "
            f"clients={mix.get('clients')!r}")
    if not mix.get("queries"):
        raise ValueError(f"traffic {name!r} lists no query")
    return mix


@functools.lru_cache(maxsize=None)
def _template(query_id: str) -> str:
    with open(os.path.join(HERE, "queries", f"{query_id}.sql")) as f:
        return f.read().strip()


def statement(query_id: str, params: dict) -> str:
    """The query's text with its `[NAME]` substitution parameters filled."""
    text = _template(query_id)
    for key, value in params.items():
        text = text.replace(f"[{key}]", str(value))
    if "[" in text:
        raise ValueError(f"query {query_id}: unfilled parameter in {text!r}")
    return text


def distinct_statements(mix: dict) -> list:
    """Every (query id, parameters) the mix sends: what warm-up runs."""
    out = []
    for q in mix["queries"]:
        entry = (q["id"], dict(q.get("params", {})))
        if entry not in out:
            out.append(entry)
    return out


def stream(mix: dict):
    """Endless (query id, parameters) in the order the mix sends them."""
    for q in itertools.cycle(mix["queries"]):
        yield q["id"], dict(q.get("params", {}))
