"""The query templates, in the program's public Pig-style ``Dataflow`` DSL.

A copy of PigMix L2-L8 and L11 from ``repro.workloads.pigmix`` and of the
extra templates of ``repro.workloads.stream.default_templates`` (L3 with a
mean, L3F, ``hi_rev``, ``busy_users``), which share sub-jobs with L3 and
L5.  Each template has one output; ``FLOAT_COLS`` names the aggregate
columns that are compared within a tolerance, every other column exactly.
"""
from __future__ import annotations

from repro.dataflow.builder import Dataflow, col
from repro.dataflow.expr import Cast, Const


def L2():
    pv = Dataflow.load("page_views").project("user", "estimated_revenue")
    pu = Dataflow.load("power_users").project("name")
    return pv.join(pu, left_on="user", right_on="name").store("L2_out")


def _L3(agg):
    pv = Dataflow.load("page_views").project("user", "estimated_revenue")
    u = Dataflow.load("users").project("name")
    return (pv.join(u, left_on="user", right_on="name")
            .group_by("user", total=(agg, "estimated_revenue"))
            .store(f"L3_{agg}_out"))


def L3_sum():
    return _L3("sum")


def L3_mean():
    return _L3("mean")


def L3F():
    pv = Dataflow.load("page_views").project("user", "estimated_revenue")
    u = Dataflow.load("users").project("name")
    return (pv.join(u, left_on="user", right_on="name")
            .group_by("user", total=("sum", "estimated_revenue"),
                      cnt=("count", "estimated_revenue"))
            .foreach(user=col("user"), avg_rev=col("total") / col("cnt"))
            .store("L3F_out"))


def L4():
    return (Dataflow.load("page_views").project("user", "action")
            .distinct()
            .group_by("user", n_actions=("count", "action"))
            .store("L4_out"))


def L5():
    pv = Dataflow.load("page_views").project("user", "timespent")
    u = Dataflow.load("users").project("name", "phone", "zip")
    return pv.join(u, left_on="user", right_on="name").store("L5_out")


def L6():
    return (Dataflow.load("page_views")
            .project("user", "query_term", "timespent")
            .group_by("user", "query_term", total_time=("sum", "timespent"))
            .store("L6_out"))


def L7():
    ts, hour = col("timespent"), col("timestamp")
    return (Dataflow.load("page_views")
            .foreach(user=col("user"),
                     morning=Cast(hour < 12, "int32") * ts,
                     afternoon=Cast(hour >= 12, "int32") * ts)
            .group_by("user", m=("sum", "morning"), a=("sum", "afternoon"))
            .store("L7_out"))


def L8():
    return (Dataflow.load("page_views")
            .foreach(all=Const(1), timespent=col("timespent"),
                     estimated_revenue=col("estimated_revenue"))
            .group_by("all", t=("sum", "timespent"),
                      r=("mean", "estimated_revenue"))
            .store("L8_out"))


def L11():
    a = Dataflow.load("page_views").project("user").distinct()
    b = (Dataflow.load("power_users").project("name")
         .foreach(user=col("name")))
    return a.union(b).distinct().store("L11_power_users_out")


def hi_rev():
    return (Dataflow.load("page_views").project("user", "estimated_revenue")
            .filter(col("estimated_revenue") > 50.0)
            .group_by("user", hi=("count", "estimated_revenue"))
            .store("hi_rev_out"))


def busy_users():
    return (Dataflow.load("page_views").project("user", "timespent")
            .filter(col("timespent") > 50)
            .group_by("user", t=("sum", "timespent"))
            .store("busy_out"))


TEMPLATES = {"L2": L2, "L3_sum": L3_sum, "L3_mean": L3_mean, "L3F": L3F,
             "L4": L4, "L5": L5, "L6": L6, "L7": L7, "L8": L8, "L11": L11,
             "hi_rev": hi_rev, "busy_users": busy_users}

OUTPUT = {"L2": "L2_out", "L3_sum": "L3_sum_out", "L3_mean": "L3_mean_out",
          "L3F": "L3F_out", "L4": "L4_out", "L5": "L5_out", "L6": "L6_out",
          "L7": "L7_out", "L8": "L8_out", "L11": "L11_power_users_out",
          "hi_rev": "hi_rev_out", "busy_users": "busy_out"}

# aggregates over float32 values, or sums whose float32 result may round
FLOAT_COLS = {"L3_sum": ("total",), "L3_mean": ("total",),
              "L3F": ("avg_rev",), "L6": ("total_time",), "L7": ("m", "a"),
              "L8": ("t", "r"), "busy_users": ("t",)}


def plan(name: str):
    """A fresh plan object of template ``name``."""
    return TEMPLATES[name]().build()
