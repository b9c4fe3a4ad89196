"""PigMix tables generated from a seed, as host numpy columns.

The shapes follow the column spec of PigMix's data generator
(``DataGenerator``, Apache Pig ``test/perf/pigmix``): every column of
``page_views``, ``users`` and ``power_users`` at its spec width, user
names and query terms drawn from zipf distributions over the spec's
cardinalities, ``users`` the distinct users of ``page_views`` and
``power_users`` 500 of them.  The program has no map, bag or 64-bit
type: maps and bags are opaque byte columns of the spec's size, longs
and doubles are 32-bit (see each configuration's ``assumed``).

Every run seed sees the same sizes.  The rank table (which user and
term rank each row has, and its action, time, hour and revenue) is
drawn once from ``BASE_SEED``; the run seed draws every string (names,
terms, addresses, the opaque columns) and the order of the rows.  So
group, join and filter sizes, and the bytes the store holds, do not
change with the seed, while the bytes the program hashes, sorts and
matches do.

A mix that churns a base table (``loadgen``'s ``churn``) re-registers it
with the table's further data sets.  Data set 0 of ``page_views`` is the
one ``tables`` gives; data set ``d`` >= 1 keeps the user and query-term
rank columns, the names and terms and the power set, so ``users``,
``power_users``, group counts and join sizes stay as they are, and draws
every other column and the order of the rows afresh from ``[seed, 1, d]``.
"""
from __future__ import annotations

import numpy as np

BASE_SEED = 1203_0061
LETTERS = (97, 123)


def zipf_counts(n_rows: int, cardinality: int, s: float,
                rng) -> np.ndarray:
    """Rows per value of a zipf(``s``) column over ``cardinality`` values,
    for the values that occur, most frequent first."""
    p = 1.0 / np.arange(1, cardinality + 1, dtype=np.float64) ** s
    counts = rng.multinomial(n_rows, p / p.sum())
    counts = counts[counts > 0]
    return np.sort(counts)[::-1]


def random_strings(rng, n: int, width: int, unique: bool = False):
    """``n`` strings of ``width`` lowercase letters, (n, width) uint8;
    all different where ``unique``."""
    while True:
        out = rng.integers(*LETTERS, (n, width), dtype=np.uint8)
        if not unique or len(np.unique(
                out.view(np.dtype((np.void, width))).ravel())) == n:
            return out


def _ranks(data: dict) -> dict:
    """The rank table: the same for every run seed."""
    rng = np.random.default_rng(BASE_SEED)
    n = data["page_views_rows"]
    key = data["keys"]
    uc = zipf_counts(n, key["user_cardinality"], key["zipf_s"], rng)
    tc = zipf_counts(n, key["query_term_cardinality"], key["zipf_s"], rng)
    return {
        "user": rng.permutation(np.repeat(np.arange(len(uc)), uc)),
        "query_term": rng.permutation(np.repeat(np.arange(len(tc)), tc)),
        "n_users": len(uc), "n_terms": len(tc),
        "power": np.sort(rng.choice(len(uc), data["power_users_rows"],
                                    replace=False)),
        **_values(rng, n),
    }


def _values(rng, n: int) -> dict:
    """Each row's action, time spent, hour and revenue."""
    return {
        "action": rng.integers(1, 3, n).astype(np.int32),
        "timespent": rng.integers(0, 100, n).astype(np.int32),
        "timestamp": rng.integers(0, 24, n).astype(np.int32),
        "estimated_revenue": rng.uniform(0, 100, n).astype(np.float32),
    }


def _people(rng, names: np.ndarray, w: dict) -> dict:
    n = len(names)
    return {
        "name": names,
        "phone": random_strings(rng, n, w["phone"]),
        "address": random_strings(rng, n, w["address"]),
        "city": random_strings(rng, n, w["city"]),
        "state": random_strings(rng, n, w["state"]),
        "zip": rng.integers(10**4, 10**5, n).astype(np.int32),
    }


def _keys(data: dict, r: dict, seed: int):
    """The run seed's generator, after it drew the user names and the
    query terms, and those."""
    w = data["widths"]
    rng = np.random.default_rng([seed, 1])
    names = random_strings(rng, r["n_users"], w["user"], unique=True)
    terms = random_strings(rng, r["n_terms"], w["query_term"], unique=True)
    return rng, names, terms


def _page_views(data: dict, r: dict, names, terms, rng, values: dict):
    w = data["widths"]
    n = data["page_views_rows"]
    order = rng.permutation(n)
    return {
        "user": names[r["user"][order]],
        "action": values["action"][order],
        "timespent": values["timespent"][order],
        "query_term": terms[r["query_term"][order]],
        "ip_addr": rng.integers(0, 256, (n, w["ip_addr"]), dtype=np.uint8),
        "timestamp": values["timestamp"][order],
        "estimated_revenue": values["estimated_revenue"][order],
        "page_info": random_strings(rng, n, w["page_info"]),
        "page_links": random_strings(rng, n, w["page_links"]),
    }


def tables(data: dict, seed: int) -> dict:
    """The three PigMix tables of a configuration, as host columns."""
    r = _ranks(data)
    w = data["widths"]
    rng, names, terms = _keys(data, r, seed)
    pv = _page_views(data, r, names, terms, rng, r)
    users = _people(rng, names, w)
    power = _people(rng, names[r["power"]], w)
    return {"page_views": pv, "users": users, "power_users": power}


def page_views(data: dict, seed: int, d: int) -> dict:
    """Data set ``d`` of ``page_views``: the same users and terms with
    the same row counts each, all other columns and the row order drawn
    from ``[seed, 1, d]``; data set 0 is that of ``tables``."""
    if d == 0:
        return tables(data, seed)["page_views"]
    r = _ranks(data)
    _, names, terms = _keys(data, r, seed)
    rng = np.random.default_rng([seed, 1, d])
    return _page_views(data, r, names, terms, rng,
                       _values(rng, data["page_views_rows"]))


# the base tables a mix may churn, each with its data sets
DATA_SETS = {"page_views": page_views}


def row_bytes(cols: dict) -> int:
    """Bytes of one row, with the one-byte validity flag of the program's
    table layout."""
    return 1 + sum(a.dtype.itemsize * (a.shape[1] if a.ndim == 2 else 1)
                   for a in cols.values())
