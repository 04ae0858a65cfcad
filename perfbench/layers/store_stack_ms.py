"""Host IO (``io/store.py``): host milliseconds of the program's
``store.stack`` span (``_stack_partitions``: zeroed padded arrays and
the copies into them, up to ``put_batch``) per query, median over the
window.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.seconds(run, "store.stack"))
