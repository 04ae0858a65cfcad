"""Kernels (``ops/kernels.py``: ``searchsorted_big``, ``searchsorted_small``
— a sort of table and queries together and scatters of the ranks): device
milliseconds a query of the ops whose innermost kernel scope is ``search``
— self time of the ``jit_stage_*`` programs on the busiest device, summed
over the traced queries ÷ their number (``perfbench/kernel_scopes.py``).
``None`` off a real device, on a program without the scope, or where no
op ran under it (a query with no ``hash_join``).  Source: device trace."""

from perfbench import kernel_scopes


def read(run):
    return kernel_scopes.ms_per_query(run, "search")
