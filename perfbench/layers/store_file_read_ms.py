"""Host IO (``io/store.py``): host milliseconds of the program's
``store.file_read`` span (allocate the partitions' arrays and
``native.read_files`` into them) per query, median over the window.
Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.seconds(run, "store.file_read"))
