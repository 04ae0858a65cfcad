"""Host IO (``io/store.py``): host milliseconds of the program's
``store.checksum`` span (fnv64 over every segment written) per query,
median over the window.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.seconds(run, "store.checksum"))
