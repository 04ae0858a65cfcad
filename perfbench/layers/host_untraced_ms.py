"""Whole query: host milliseconds of a query's wall time that no span of
the program covers (``program_spans.untraced``), median over the window:
what the harness itself does between its calls, and any phase of the
program that still has no span.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.untraced(run))
