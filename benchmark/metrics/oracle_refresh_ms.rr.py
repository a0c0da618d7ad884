"""The supervisor's host-view refresh (``supervisor.oracle-refresh``:
CT snapshot under the engine lock, its decode, the host LPM build, the
policy state copy), mean per refresh in the window, in ms.  None where
no refresh ran in the window."""

import readers


def read(ctx):
    us = readers.stage_mean_us(ctx, "oracle-refresh", family="supervisor")
    return None if us is None else us / 1e3
