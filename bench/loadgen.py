"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
into each client's sequence of query templates.

A mix file holds data only:

    loop         the loop driver, ``loops/<loop>.py`` ("closed": a client
                 sends its next query only after its last answer is ready)
    clients      number of concurrent clients (tenants)
    templates    template names of ``queries.TEMPLATES``
    order        "rounds": every template once per round, in an order
                 drawn afresh each round; or "zipf": templates drawn with
                 probability ~ 1/rank**zipf_s, ranks mapped to templates
                 through each client's own permutation
    zipf_s       skew of the zipf order
    churn        optional: a base table reloaded during the window, as
                 {"table": "page_views", "every_s": 5, "data_sets": 3}:
                 every ``every_s`` seconds of the window's clock (a whole
                 number), the harness registers the whole of ``table``
                 anew in the service's catalog with its next data set
                 (``datagen.DATA_SETS``), cycling through ``data_sets``
                 of them.  The streams do not depend on it.

Every stream is drawn from the run's seed and the client's index, so the
same seed gives the same sequences.  The zipf order follows the
recurrence model of ``repro.workloads.stream`` (Chen et al.,
arXiv:1208.4174) with one stream per client.
"""
from __future__ import annotations

import json
import os

import numpy as np

LOOPS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "loops")

ORDERS = ("rounds", "zipf")
CHURN_KEYS = {"table", "every_s", "data_sets"}


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not os.path.exists(os.path.join(LOOPS, f"{mix.get('loop')}.py")):
        raise ValueError(f"{path}: no loop driver loops/"
                         f"{mix.get('loop')}.py")
    if mix.get("order") not in ORDERS:
        raise ValueError(f"{path}: order {mix.get('order')!r} not in "
                         f"{ORDERS}")
    if int(mix.get("clients", 0)) < 1 or not mix.get("templates"):
        raise ValueError(f"{path}: needs clients >= 1 and templates")
    if "churn" in mix:
        _check_churn(path, mix["churn"])
    return mix


def _check_churn(path: str, churn) -> None:
    from datagen import DATA_SETS
    if not isinstance(churn, dict) or set(churn) != CHURN_KEYS:
        raise ValueError(f"{path}: churn needs exactly the keys "
                         f"{sorted(CHURN_KEYS)}")
    if churn["table"] not in DATA_SETS:
        raise ValueError(f"{path}: churn table {churn['table']!r} has no "
                         f"data sets; one of {sorted(DATA_SETS)}")
    for key, least in (("every_s", 1), ("data_sets", 2)):
        v = churn[key]
        if isinstance(v, bool) or not isinstance(v, int) or v < least:
            raise ValueError(f"{path}: churn {key} must be a whole "
                             f"number >= {least}, not {v!r}")


def client_stream(mix: dict, seed: int, client: int):
    """Endless template names for ``client``."""
    names = list(mix["templates"])
    rng = np.random.default_rng([seed, client])
    if mix["order"] == "rounds":
        while True:
            for i in rng.permutation(len(names)):
                yield names[i]
    n = len(names)
    p = 1.0 / np.arange(1, n + 1) ** float(mix["zipf_s"])
    p /= p.sum()
    perm = np.random.default_rng([seed, client, 101]).permutation(n)
    while True:
        for rank in rng.choice(n, size=256, p=p):
            yield names[perm[rank]]
