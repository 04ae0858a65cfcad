"""Kernels (``ops/kernels.py``: ``_gather_live``, ``_gather_lanes``,
``_packed_gather``, ``take``; the exchange's ``slot_expand`` and
``slot_compact`` takes): device milliseconds a query of the ops whose
innermost kernel scope is ``row_gather`` — self time of the ``jit_stage_*``
programs on the busiest device, summed over the traced queries ÷ their
number (``perfbench/kernel_scopes.py``).  ``None`` off a real device, on a
program without the scope, or where no op ran under it.  Source: device
trace."""

from perfbench import kernel_scopes


def read(run):
    return kernel_scopes.ms_per_query(run, "row_gather")
