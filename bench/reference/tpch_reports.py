"""Plain row-level lineage of six TPC-H summary reports, in numpy alone.

The reports are Q1 (pricing summary), Q4 (order priority checking), Q6
(forecasting revenue change), Q12 (shipping modes and order priority), Q14
(promotion effect) and Q15 (top supplier), with dbgen-lite's constants.
Few output rows, each with very wide lineage: the audit of a summary.

Same semantics as ``tpch_lineage`` (the paper's Definitions 3.1/3.2), and
its helpers: a group contributes its member rows; an inner join contributes
both sides; Q4's semi-join contributes the lineitems that matched; Q15's
uncorrelated max subquery contributes every row it aggregated.  Q6 and Q14
are global aggregates: one output group, keyed by the empty tuple.

It imports nothing of the program.  Input and output are as in
``tpch_lineage``: ``build(data, query)`` returns a ``RefQuery``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.reference.tpch_lineage import (RefQuery, code, finish, group_lineage,
                                          group_sum, groups, join, mask, revenue,
                                          source)

# output columns that identify a group, per query, as the pipeline names them
GROUP_KEYS: Dict[str, Tuple[str, ...]] = {
    "q1": ("l_returnflag", "l_linestatus"),
    "q4": ("o_orderpriority",),
    "q6": (),
    "q12": ("l_shipmode",),
    "q14": (),
    "q15": ("s_suppkey", "s_name"),
}


def every_group(j, query: str) -> RefQuery:
    """Each group of ``j`` by the query's keys, with its member rows; a
    global aggregate has the one group ``()``."""
    if GROUP_KEYS[query]:
        gid, keys = groups(j, GROUP_KEYS[query])
    else:
        gid, keys = np.zeros(len(j["@lineitem"]), np.int64), [()]
    return finish(np.arange(len(keys)), keys, group_lineage(gid, len(keys), j))


def q1(data) -> RefQuery:
    li = source(data, "lineitem")
    # date '1998-12-01' - interval '90' day
    return every_group(mask(li, li["l_shipdate"] <= 19980902), "q1")


def q4(data) -> RefQuery:
    o = source(data, "orders")
    o = mask(o, (o["o_orderdate"] >= 19930701) & (o["o_orderdate"] < 19931001))
    li = source(data, "lineitem")
    li = mask(li, li["l_commitdate"] < li["l_receiptdate"])
    # the semi-join's matched lineitems are what this inner join brings in;
    # an order with several of them is still one member of its group
    return every_group(join(o, li, [("o_orderkey", "l_orderkey")]), "q4")


def q6(data) -> RefQuery:
    li = source(data, "lineitem")
    li = mask(li, (li["l_shipdate"] >= 19940101) & (li["l_shipdate"] < 19950101)
              & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
              & (li["l_quantity"] < 24))
    return every_group(li, "q6")


def q12(data) -> RefQuery:
    li = source(data, "lineitem")
    modes = [code(data, "lineitem", "l_shipmode", m) for m in ("MAIL", "SHIP")]
    li = mask(li, np.isin(li["l_shipmode"], modes)
              & (li["l_commitdate"] < li["l_receiptdate"])
              & (li["l_shipdate"] < li["l_commitdate"])
              & (li["l_receiptdate"] >= 19940101)
              & (li["l_receiptdate"] < 19950101))
    return every_group(join(source(data, "orders"), li,
                            [("o_orderkey", "l_orderkey")]), "q12")


def q14(data) -> RefQuery:
    li = source(data, "lineitem")
    li = mask(li, (li["l_shipdate"] >= 19950901) & (li["l_shipdate"] < 19951001))
    # every joined part counts, promotional or not: the type only picks
    # which revenue goes into the numerator
    return every_group(join(li, source(data, "part"),
                            [("l_partkey", "p_partkey")]), "q14")


def q15(data) -> RefQuery:
    li = source(data, "lineitem")
    li = mask(li, (li["l_shipdate"] >= 19960101) & (li["l_shipdate"] < 19960401))
    supp, gid = np.unique(li["l_suppkey"], return_inverse=True)
    total = group_sum(gid.reshape(-1), len(supp), revenue(li))
    top = supp[total == total.max()] if len(supp) else supp
    j = join(source(data, "supplier"), li, [("s_suppkey", "l_suppkey")])
    j = mask(j, np.isin(j["s_suppkey"], top))
    gid, keys = groups(j, GROUP_KEYS["q15"])
    lineage = group_lineage(gid, len(keys), j)
    # the uncorrelated max aggregated the whole quarter: every kept row
    # derives from all of its lineitems
    lineage["lineitem"] = [np.unique(li["@lineitem"])] * len(keys)
    return finish(np.arange(len(keys)), keys, lineage)


QUERIES = {"q1": q1, "q4": q4, "q6": q6, "q12": q12, "q14": q14, "q15": q15}


def build(data, query: str) -> RefQuery:
    return QUERIES[query](data)
