"""Kernels: the share of the ``jit_stage_*`` programs' device self time
(busiest device, traced queries) spent in ops under any of the ten scopes
of the kernel vocabulary (``perfbench/kernel_scopes.py``): 1 − unscoped ÷
all.  How much of a stage program the ``kernel_*_ms`` / ``pack_ms`` /
``unpack_ms`` readings can tell apart; the unscoped ops' names go to
standard error in a traced run.  ``None`` off a real device or on a
program without scopes.  Source: device trace."""

from perfbench import kernel_scopes


def read(run):
    return kernel_scopes.named_share(run)
