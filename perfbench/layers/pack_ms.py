"""Exchange (``parallel/shuffle.py``: ``_exchange_one_axis`` from the
column packing up to the ``all_to_all`` — ``hist_buckets``, the dest
sort, ``slot_expand``): device milliseconds a query of every op under the
phase scope ``exchange_pack``, at any depth — self time of the
``jit_stage_*`` programs on the busiest device, summed over the traced
queries ÷ their number (``perfbench/kernel_scopes.py``).  The
``all_to_all`` is ``a2a_ms``'s.  ``None`` off a real device, on a program
without the scope, or where no exchange ran.  Source: device trace."""

from perfbench import kernel_scopes


def read(run):
    return kernel_scopes.ms_per_query(run, "exchange_pack")
