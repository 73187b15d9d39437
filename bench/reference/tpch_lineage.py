"""Plain row-level lineage of eight TPC-H queries, in numpy alone.

This is the benchmark's reference for PredTrace.  It imports nothing of the
program: it executes each query directly over the source columns while
carrying a row-id column per source table, groups, and reads off each
output group's source rows.  The lineage semantics are the paper's
(Definitions 3.1/3.2): a group contributes every member row; an inner join
contributes both sides; a semi-join also contributes the inner rows that
matched; an anti-join contributes no inner rows; an uncorrelated scalar
subquery contributes every row it aggregated.

Input is ``{table: (cols, dicts)}``: numpy columns by name (row ``i`` of a
source table has row id ``i``) and the string vocabularies of
dictionary-coded columns, so string constants are looked up by value.

``build(data, query)`` returns a :class:`RefQuery`: the output groups that
survive the query's HAVING/LIMIT, keyed by the tuple of the output's group
columns, and each group's lineage as sorted unique row ids per table.
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

Rel = Dict[str, np.ndarray]  # column name -> values; "@t" = row ids of t

# output columns that identify a group, per query, as the pipeline names them
GROUP_KEYS: Dict[str, Tuple[str, ...]] = {
    "q3": ("l_orderkey", "o_orderdate", "o_shippriority"),
    "q5": ("n_name",),
    "q9": ("n_name", "o_year"),
    "q10": ("c_custkey", "c_name", "c_acctbal", "n_name"),
    "q11": ("ps_partkey",),
    "q16": ("p_brand", "p_type", "p_size"),
    "q18": ("c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"),
    "q21": ("s_name",),
}


class RefQuery:
    """Output groups of one query and their lineage (CSR per table)."""

    def __init__(self, keys: List[Tuple], lineage: Dict[str, List[np.ndarray]]):
        self.index = {k: i for i, k in enumerate(keys)}
        self.lineage = lineage

    def __contains__(self, key: Tuple) -> bool:
        return key in self.index

    def __len__(self) -> int:
        return len(self.index)

    def rows(self, key: Tuple) -> Dict[str, np.ndarray]:
        g = self.index[key]
        return {t: per_group[g] for t, per_group in self.lineage.items()}


# --------------------------------------------------------------------------- #
# relational helpers
# --------------------------------------------------------------------------- #


def source(data, table: str) -> Rel:
    cols, _ = data[table]
    rel = dict(cols)
    n = len(next(iter(cols.values())))
    rel["@" + table] = np.arange(n, dtype=np.int64)
    return rel


def code(data, table: str, col: str, value: str) -> int:
    vocab = data[table][1][col]
    return vocab.index(value) if value in vocab else -1


def like_codes(data, table: str, col: str, pattern: str) -> np.ndarray:
    rx = re.compile("^" + re.escape(pattern).replace("%", ".*").replace("_", ".") + "$")
    return np.array([i for i, s in enumerate(data[table][1][col]) if rx.match(s)],
                    dtype=np.int64)


def take(rel: Rel, idx) -> Rel:
    return {k: v[idx] for k, v in rel.items()}


def composite(cols_l: Sequence[np.ndarray], cols_r: Sequence[np.ndarray]):
    """One int64 key per row from equi-join columns (all non-negative)."""
    kl = np.zeros(len(cols_l[0]), np.int64)
    kr = np.zeros(len(cols_r[0]), np.int64)
    for a, b in zip(cols_l, cols_r):
        a = a.astype(np.int64)
        b = b.astype(np.int64)
        m = int(max(a.max(initial=0), b.max(initial=0))) + 1
        kl = kl * m + a
        kr = kr * m + b
    return kl, kr


def match(kl: np.ndarray, kr: np.ndarray):
    """All pairs ``(i, j)`` with ``kl[i] == kr[j]``."""
    order = np.argsort(kr, kind="stable")
    sr = kr[order]
    lo = np.searchsorted(sr, kl, "left")
    cnt = np.searchsorted(sr, kl, "right") - lo
    li = np.repeat(np.arange(len(kl)), cnt)
    start = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
    ri = order[start + np.arange(len(li))]
    return li, ri


def join(left: Rel, right: Rel, on: Sequence[Tuple[str, str]]) -> Rel:
    kl, kr = composite([left[a] for a, _ in on], [right[b] for _, b in on])
    li, ri = match(kl, kr)
    out = take(left, li)
    for k, v in right.items():
        if k not in out:
            out[k] = v[ri]
    return out


def groups(rel: Rel, keys: Sequence[str]):
    """Group id per row, and one key tuple per group (sorted order)."""
    n = len(rel[keys[0]])
    rec = np.rec.fromarrays([rel[k] for k in keys], names=list(keys))
    uniq, gid = np.unique(rec, return_inverse=True)
    return gid.reshape(n), [tuple(row) for row in uniq.tolist()]


def group_lineage(gid: np.ndarray, n_groups: int, rel: Rel,
                  extra: Dict[str, Tuple[np.ndarray, np.ndarray]] = None
                  ) -> Dict[str, List[np.ndarray]]:
    """Sorted unique row ids per group for every ``@table`` column of
    ``rel``, plus ``extra[t] = (group ids, row ids)`` pairs to merge in."""
    pairs: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {}
    for k, v in rel.items():
        if k.startswith("@"):
            pairs.setdefault(k[1:], []).append((gid, v))
    for t, pr in (extra or {}).items():
        pairs.setdefault(t, []).append(pr)
    out: Dict[str, List[np.ndarray]] = {}
    for t, prs in pairs.items():
        g = np.concatenate([p[0] for p in prs]).astype(np.int64)
        r = np.concatenate([p[1] for p in prs]).astype(np.int64)
        both = np.unique(g * (int(r.max(initial=0)) + 1) + r)
        span = int(r.max(initial=0)) + 1
        gg, rr = both // span, both % span
        cuts = np.searchsorted(gg, np.arange(n_groups + 1))
        out[t] = [rr[cuts[i]:cuts[i + 1]] for i in range(n_groups)]
    return out


def group_sum(gid: np.ndarray, n: int, vals: np.ndarray) -> np.ndarray:
    return np.bincount(gid, weights=vals.astype(np.float64), minlength=n)


def top(order_keys: List[np.ndarray], limit=None) -> np.ndarray:
    """Group indices in sort order (first key most significant)."""
    idx = np.lexsort(list(reversed(order_keys)))
    return idx if limit is None else idx[:limit]


def finish(kept: np.ndarray, keys: List[Tuple],
           lineage: Dict[str, List[np.ndarray]]) -> RefQuery:
    return RefQuery([keys[g] for g in kept],
                    {t: [per[g] for g in kept] for t, per in lineage.items()})


def revenue(rel: Rel) -> np.ndarray:
    return rel["l_extendedprice"] * (1 - rel["l_discount"])


def mask(rel: Rel, m) -> Rel:
    return take(rel, np.nonzero(m)[0])


# --------------------------------------------------------------------------- #
# the eight queries
# --------------------------------------------------------------------------- #


def q3(data) -> RefQuery:
    c = source(data, "customer")
    c = mask(c, c["c_mktsegment"] == code(data, "customer", "c_mktsegment", "BUILDING"))
    o = source(data, "orders")
    o = mask(o, o["o_orderdate"] < 19950315)
    li = source(data, "lineitem")
    li = mask(li, li["l_shipdate"] > 19950315)
    j = join(join(c, o, [("c_custkey", "o_custkey")]), li, [("o_orderkey", "l_orderkey")])
    gid, keys = groups(j, GROUP_KEYS["q3"])
    rev = group_sum(gid, len(keys), revenue(j))
    odate = np.array([k[1] for k in keys])
    kept = top([-rev, odate], 10)
    return finish(kept, keys, group_lineage(gid, len(keys), j))


def q5(data) -> RefQuery:
    o = source(data, "orders")
    o = mask(o, (o["o_orderdate"] >= 19940101) & (o["o_orderdate"] < 19950101))
    r = source(data, "region")
    r = mask(r, r["r_name"] == code(data, "region", "r_name", "ASIA"))
    j = join(source(data, "customer"), o, [("c_custkey", "o_custkey")])
    j = join(j, source(data, "lineitem"), [("o_orderkey", "l_orderkey")])
    j = join(j, source(data, "supplier"),
             [("l_suppkey", "s_suppkey"), ("c_nationkey", "s_nationkey")])
    j = join(j, source(data, "nation"), [("s_nationkey", "n_nationkey")])
    j = join(j, r, [("n_regionkey", "r_regionkey")])
    gid, keys = groups(j, GROUP_KEYS["q5"])
    return finish(np.arange(len(keys)), keys, group_lineage(gid, len(keys), j))


def q9(data) -> RefQuery:
    p = source(data, "part")
    p = mask(p, np.isin(p["p_name"], like_codes(data, "part", "p_name", "%green%")))
    j = join(p, source(data, "lineitem"), [("p_partkey", "l_partkey")])
    j = join(j, source(data, "supplier"), [("l_suppkey", "s_suppkey")])
    j = join(j, source(data, "partsupp"),
             [("l_suppkey", "ps_suppkey"), ("l_partkey", "ps_partkey")])
    j = join(j, source(data, "orders"), [("l_orderkey", "o_orderkey")])
    j = join(j, source(data, "nation"), [("s_nationkey", "n_nationkey")])
    j["o_year"] = j["o_orderdate"] // 10000
    gid, keys = groups(j, GROUP_KEYS["q9"])
    return finish(np.arange(len(keys)), keys, group_lineage(gid, len(keys), j))


def q10(data) -> RefQuery:
    o = source(data, "orders")
    o = mask(o, (o["o_orderdate"] >= 19931001) & (o["o_orderdate"] < 19940101))
    li = source(data, "lineitem")
    li = mask(li, li["l_returnflag"] == code(data, "lineitem", "l_returnflag", "R"))
    j = join(source(data, "customer"), o, [("c_custkey", "o_custkey")])
    j = join(j, li, [("o_orderkey", "l_orderkey")])
    j = join(j, source(data, "nation"), [("c_nationkey", "n_nationkey")])
    gid, keys = groups(j, GROUP_KEYS["q10"])
    rev = group_sum(gid, len(keys), revenue(j))
    kept = top([-rev], 20)
    return finish(kept, keys, group_lineage(gid, len(keys), j))


def _q11_join(data) -> Rel:
    n = source(data, "nation")
    n = mask(n, n["n_name"] == code(data, "nation", "n_name", "GERMANY"))
    j = join(source(data, "partsupp"), source(data, "supplier"),
             [("ps_suppkey", "s_suppkey")])
    return join(j, n, [("s_nationkey", "n_nationkey")])


def q11(data) -> RefQuery:
    j = _q11_join(data)
    gid, keys = groups(j, GROUP_KEYS["q11"])
    amount = j["ps_supplycost"] * j["ps_availqty"]
    value = group_sum(gid, len(keys), amount)
    kept = np.nonzero(value > amount.sum() * 0.0001)[0]
    # the uncorrelated subquery aggregates the whole join: every kept group
    # derives from all of its rows
    everything = {t[1:]: np.unique(v) for t, v in j.items() if t.startswith("@")}
    lineage = {t: [rows] * len(keys) for t, rows in everything.items()}
    return finish(kept, keys, lineage)


def q16(data) -> RefQuery:
    p = source(data, "part")
    p = mask(p, (p["p_brand"] != code(data, "part", "p_brand", "Brand#45"))
             & ~np.isin(p["p_type"], like_codes(data, "part", "p_type", "MEDIUM POLISHED%"))
             & np.isin(p["p_size"], (49, 14, 23, 45, 19, 3, 36, 9)))
    j = join(source(data, "partsupp"), p, [("ps_partkey", "p_partkey")])
    s = source(data, "supplier")
    bad = s["s_suppkey"][np.isin(s["s_comment"],
                                 like_codes(data, "supplier", "s_comment",
                                            "%Customer%Complaints%"))]
    # anti-join: the complaining suppliers remove rows and add no lineage
    j = mask(j, ~np.isin(j["ps_suppkey"], bad))
    gid, keys = groups(j, GROUP_KEYS["q16"])
    return finish(np.arange(len(keys)), keys, group_lineage(gid, len(keys), j))


def q18(data) -> RefQuery:
    li = source(data, "lineitem")
    okeys, inv = np.unique(li["l_orderkey"], return_inverse=True)
    qty = np.bincount(inv, weights=li["l_quantity"].astype(np.float64))
    big = okeys[qty > 250]
    o = source(data, "orders")
    o = mask(o, np.isin(o["o_orderkey"], big))
    j = join(source(data, "customer"), o, [("c_custkey", "o_custkey")])
    # the semi-join's matched inner rows (every line of a big order) are the
    # same rows the second join brings in
    j = join(j, li, [("o_orderkey", "l_orderkey")])
    gid, keys = groups(j, GROUP_KEYS["q18"])
    price = np.array([k[4] for k in keys])
    odate = np.array([k[3] for k in keys])
    kept = top([-price, odate], 100)
    return finish(kept, keys, group_lineage(gid, len(keys), j))


def q21(data) -> RefQuery:
    n = source(data, "nation")
    n = mask(n, n["n_name"] == code(data, "nation", "n_name", "SAUDI ARABIA"))
    full = source(data, "lineitem")
    late = full["l_receiptdate"] > full["l_commitdate"]
    l1 = mask(full, late)
    o = source(data, "orders")
    o = mask(o, o["o_orderstatus"] == code(data, "orders", "o_orderstatus", "F"))
    j = join(source(data, "supplier"), l1, [("s_suppkey", "l_suppkey")])
    j = join(j, o, [("l_orderkey", "o_orderkey")])
    j = join(j, n, [("s_nationkey", "n_nationkey")])
    # exists l2 in the order from another supplier (witnesses are lineage)
    ji, wi = match(j["l_orderkey"].astype(np.int64), full["l_orderkey"].astype(np.int64))
    ok = full["l_suppkey"][wi] != j["l_suppkey"][ji]
    ji, wi = ji[ok], wi[ok]
    has_l2 = np.zeros(len(j["l_orderkey"]), bool)
    has_l2[ji] = True
    # not exists late l3 in the order from another supplier
    lat = np.nonzero(late)[0]
    ki, li3 = match(j["l_orderkey"].astype(np.int64), full["l_orderkey"][lat].astype(np.int64))
    bad = full["l_suppkey"][lat][li3] != j["l_suppkey"][ki]
    has_l3 = np.zeros(len(j["l_orderkey"]), bool)
    has_l3[ki[bad]] = True
    keep = has_l2 & ~has_l3
    new_pos = np.cumsum(keep) - 1
    j = mask(j, keep)
    sel = keep[ji]
    wit_row, wit_rid = new_pos[ji[sel]], wi[sel]
    gid, keys = groups(j, GROUP_KEYS["q21"])
    numwait = np.bincount(gid, minlength=len(keys))
    name = np.array([k[0] for k in keys])
    kept = top([-numwait, name], 100)
    lineage = group_lineage(gid, len(keys), j,
                            extra={"lineitem": (gid[wit_row], wit_rid)})
    return finish(kept, keys, lineage)


QUERIES = {"q3": q3, "q5": q5, "q9": q9, "q10": q10, "q11": q11, "q16": q16,
           "q18": q18, "q21": q21}


def build(data, query: str) -> RefQuery:
    return QUERIES[query](data)
