"""The one traffic generator: turns a mix's data file and a seed into
requests.

A mix (``chipbench/traffic/<name>.json``) names a ``driver`` (how the load is
offered, see :mod:`chipbench.drivers`) and the parameters below.  The same
seed always gives the same requests, and every seed gives the same kinds
and sizes of request in the same order: a seed only changes the searches'
own seeds.

Closed loop (``service_closed``): client ``c``'s ``k``-th
request is ``methods[c % len(methods)]`` on dataflow
``dataflows[i % len(dataflows)]`` and objective
``objectives[(i // len(dataflows)) % len(objectives)]``, with
``i = c + clients * k``, and a search seed drawn from ``(seed, c, k)``.
"""
from __future__ import annotations

import numpy as np

SEED_BOUND = 2 ** 31 - 1


def search_seed(seed: int, *path: int) -> int:
    return int(np.random.default_rng([int(seed), *map(int, path)]).integers(
        0, SEED_BOUND))


def closed_request(mix: dict, config: dict, seed: int, client: int,
                   k: int) -> dict:
    """Request spec of client ``client``'s ``k``-th search."""
    i = client + mix["clients"] * k
    m = mix["methods"][client % len(mix["methods"])]
    dfs = mix.get("dataflows", config["dataflows"])
    objs = mix.get("objectives", config["objectives"])
    return {"method": m["method"], "eps": m.get("eps", config["eps"]),
            "options": dict(m.get("options", {})),
            "platform": mix.get("platform", config["platform"]),
            "dataflow": dfs[i % len(dfs)],
            "objective": objs[(i // len(dfs)) % len(objs)],
            "seed": search_seed(seed, client, k)}
