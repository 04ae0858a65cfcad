"""Planner and executor (``plan/planner.py``, ``api/dataset.py``): host
milliseconds of the program's ``plan`` (``plan_query``) and ``lint``
(``Context._pre_submit_lint``) spans per query, median over the window.
Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.seconds(run, ("plan", "lint")))
