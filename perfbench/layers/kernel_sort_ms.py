"""Kernels (``ops/kernels.py``: ``_sort_carrying``, ``_sort_fused2``,
``permute_by_sort``, ``sort_by_columns``, the segment sorts; the exchange's
``slot_compact`` argsort): device milliseconds a query of the ops whose
innermost kernel scope is ``index_sort`` — self time (nested ops left
out) of the ``jit_stage_*`` programs on the busiest device, summed over
the traced queries ÷ their number (``perfbench/kernel_scopes.py``).  A
gather inside a sort is ``kernel_gather_ms``'s.  ``None`` off a real
device, on a program without the scope, or where no op ran under it.
Source: device trace."""

from perfbench import kernel_scopes


def read(run):
    return kernel_scopes.ms_per_query(run, "index_sort")
