"""Host IO (``io/store.py``, ``exec/data.py``): host milliseconds a query
spends bringing its result from the device to the host: the program's
``store.fetch`` spans (one a partition, device slice + copy + the
contiguous copy) where the result is stored, ``collect.fetch`` (the
shrink and ``pdata_to_host``) where it is collected; summed per query,
median over the window.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    return ps.ms(ps.seconds(run, ("store.fetch", "collect.fetch")))
