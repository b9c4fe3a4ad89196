"""Plain numpy answers of the query templates, and the comparison that
decides ``correct``.

Independent of the program: no hashing, sorting network, kernel or
table type, only numpy over the generated host columns.  The reference
sums in float64.  ``Reference(..., inputs="bfloat16")`` is the control:
the same reference with every float input rounded to bfloat16, the
precision a single-pass MXU product (JAX's default matmul precision on a
TPU) gives the float32 values the configuration states.
"""
from __future__ import annotations

import numpy as np

# a relative error is taken against max(|reference|, REL_FLOOR), so that
# an aggregate near zero does not turn rounding into a large ratio
REL_FLOOR = 1.0


def _ids(*arrays):
    """One shared id space over the rows of same-width uint8 string
    arrays; returns one id array each."""
    width = arrays[0].shape[1]
    void = [np.ascontiguousarray(a).view(np.dtype((np.void, width))).ravel()
            for a in arrays]
    _, inv = np.unique(np.concatenate(void), return_inverse=True)
    out, at = [], 0
    for v in void:
        out.append(inv[at:at + len(v)])
        at += len(v)
    return out


def _bf16(a):
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


class Reference:
    def __init__(self, tables: dict, inputs: str = "float32"):
        pv = dict(tables["page_views"])
        if inputs == "bfloat16":
            pv["estimated_revenue"] = _bf16(pv["estimated_revenue"])
        elif inputs != "float32":
            raise ValueError(f"inputs {inputs!r}")
        self.pv = pv
        self.users, self.power = tables["users"], tables["power_users"]
        pu, uu, wu = _ids(pv["user"], self.users["name"],
                          self.power["name"])
        self.pu, self.uu, self.wu = pu, uu, wu
        self.n_ids = int(max(pu.max(), uu.max(), wu.max())) + 1
        self.name_of = np.zeros((self.n_ids, pv["user"].shape[1]), np.uint8)
        self.name_of[pu] = pv["user"]
        self.name_of[uu] = self.users["name"]
        self.name_of[wu] = self.power["name"]

    def answer(self, template: str) -> dict:
        return getattr(self, template)()

    def _per_user(self, weights=None, keep=None):
        ids = self.pu if keep is None else self.pu[keep]
        if weights is not None and keep is not None:
            weights = weights[keep]
        return np.bincount(ids, weights=weights, minlength=self.n_ids)

    def _mask(self, ids):
        m = np.zeros(self.n_ids, bool)
        m[ids] = True
        return m

    def _joined(self):
        """Ids of users that are in page_views and in users."""
        return np.flatnonzero((self._per_user() > 0) & self._mask(self.uu))

    def L2(self):
        keep = self._mask(self.wu)[self.pu]
        return {"user": self.pv["user"][keep],
                "estimated_revenue": self.pv["estimated_revenue"][keep],
                "name": self.pv["user"][keep]}

    def L3_sum(self):
        ids = self._joined()
        rev = self._per_user(self.pv["estimated_revenue"])
        return {"user": self.name_of[ids], "total": rev[ids]}

    def L3_mean(self):
        ids = self._joined()
        rev = self._per_user(self.pv["estimated_revenue"])
        return {"user": self.name_of[ids],
                "total": rev[ids] / self._per_user()[ids]}

    def L3F(self):
        ids = self._joined()
        rev = self._per_user(self.pv["estimated_revenue"])[ids]
        cnt = self._per_user()[ids]
        return {"user": self.name_of[ids], "avg_rev": rev / cnt}

    def L4(self):
        pairs = np.unique(self.pu * 4 + self.pv["action"])
        n = np.bincount(pairs // 4, minlength=self.n_ids)
        ids = np.flatnonzero(n)
        return {"user": self.name_of[ids], "n_actions": n[ids]}

    def L5(self):
        keep = self._mask(self.uu)[self.pu]
        row_of = np.full(self.n_ids, -1)
        row_of[self.uu] = np.arange(len(self.uu))
        r = row_of[self.pu[keep]]
        return {"user": self.pv["user"][keep],
                "timespent": self.pv["timespent"][keep],
                "name": self.users["name"][r],
                "phone": self.users["phone"][r],
                "zip": self.users["zip"][r]}

    def L6(self):
        (tid,) = _ids(self.pv["query_term"])
        n_t = int(tid.max()) + 1
        ks, inv = np.unique(self.pu.astype(np.int64) * n_t + tid,
                            return_inverse=True)
        tot = np.bincount(inv, weights=self.pv["timespent"])
        term_of = np.zeros((n_t, self.pv["query_term"].shape[1]), np.uint8)
        term_of[tid] = self.pv["query_term"]
        return {"user": self.name_of[ks // n_t],
                "query_term": term_of[ks % n_t], "total_time": tot}

    def L7(self):
        ts, hour = self.pv["timespent"], self.pv["timestamp"]
        ids = np.flatnonzero(self._per_user() > 0)
        m = self._per_user(np.where(hour < 12, ts, 0))[ids]
        a = self._per_user(np.where(hour >= 12, ts, 0))[ids]
        return {"user": self.name_of[ids], "m": m, "a": a}

    def L8(self):
        return {"all": np.array([1]),
                "t": np.array([self.pv["timespent"].sum(dtype=np.float64)]),
                "r": np.array([self.pv["estimated_revenue"]
                               .mean(dtype=np.float64)])}

    def L11(self):
        return {"user": self.name_of[np.union1d(np.unique(self.pu),
                                                self.wu)]}

    def hi_rev(self):
        keep = self.pv["estimated_revenue"] > np.float32(50.0)
        n = self._per_user(keep=keep)
        ids = np.flatnonzero(n)
        return {"user": self.name_of[ids], "hi": n[ids]}

    def busy_users(self):
        keep = self.pv["timespent"] > 50
        t = self._per_user(self.pv["timespent"], keep=keep)
        ids = np.flatnonzero(self._per_user(keep=keep))
        return {"user": self.name_of[ids], "t": t[ids]}


def _canonical(cols: dict, order_by) -> dict:
    """Rows sorted by the ``order_by`` columns (strings by their bytes)."""
    keys = []
    for c in reversed(order_by):
        a = np.asarray(cols[c])
        if a.ndim == 2:
            # bytes in big-endian 8-byte words sort as the bytes do
            pad = -a.shape[1] % 8
            words = np.ascontiguousarray(
                np.pad(a, ((0, 0), (0, pad)))).view(">u8")
            keys.extend(words[:, j] for j in reversed(range(words.shape[1])))
        else:
            keys.append(a)
    idx = np.lexsort(keys)
    return {c: np.asarray(v)[idx] for c, v in cols.items()}


def _row_hash(cols: dict, names) -> np.ndarray:
    """A 64-bit hash of each row's bytes over the ``names`` columns."""
    h = np.zeros(len(next(iter(cols.values()))), np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for c in names:
            a = np.ascontiguousarray(np.asarray(cols[c]))
            a = a.reshape(len(a), -1).view(np.uint8)
            pad = -a.shape[1] % 8
            words = np.pad(a, ((0, 0), (0, pad))).view(np.uint64)
            for j in range(words.shape[1]):
                h = (h ^ words[:, j]) * prime
    return h


def _by_hash(cols: dict, order_by) -> dict:
    """Rows sorted by a hash of the ``order_by`` columns: equal rows land
    together, so two row multisets that are equal sort alike unless two
    different rows collide, which the full sort then settles."""
    idx = np.argsort(_row_hash(cols, order_by), kind="stable")
    return {c: np.asarray(v)[idx] for c, v in cols.items()}


def _as_exact(a):
    return a.astype(np.float64) if a.dtype.kind in "fiub" and a.ndim == 1 \
        else a


def compare(got: dict, want: dict, floats=()):
    """``(reason, float_err)``: ``reason`` is None when every column not
    in ``floats`` equals the reference exactly and the row sets agree,
    else why not; ``float_err`` is the widest relative error of the
    ``floats`` columns (0.0 where there are none), or None where the rows
    cannot be lined up."""
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}", None
    n_got = len(next(iter(got.values())))
    n_want = len(next(iter(want.values())))
    if n_got != n_want:
        return f"{n_got} rows != {n_want}", None
    order_by = [c for c in sorted(got) if c not in floats] or sorted(got)
    if not floats:
        g, w = _by_hash(got, order_by), _by_hash(want, order_by)
        if all(np.array_equal(_as_exact(np.asarray(g[c])),
                              _as_exact(np.asarray(w[c]))) for c in got):
            return None, 0.0
    g, w = _canonical(got, order_by), _canonical(want, order_by)
    err = 0.0
    reason = None
    for c in sorted(got):
        a, b = np.asarray(g[c]), np.asarray(w[c])
        if c in floats:
            a64, b64 = a.astype(np.float64), b.astype(np.float64)
            if a64.shape != b64.shape:
                return f"column {c}: shape {a.shape} != {b.shape}", None
            if a64.size:
                rel = np.abs(a64 - b64) / np.maximum(np.abs(b64), REL_FLOOR)
                e = float(np.max(rel))
                err = max(err, e if np.isfinite(e) else float("inf"))
        elif a.shape != b.shape or not np.array_equal(_as_exact(a),
                                                      _as_exact(b)):
            reason = reason or f"column {c} differs"
    return reason, err
