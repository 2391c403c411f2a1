"""Share of device time charged to none of the program's own scopes
(``tick/<phase>``, ``placement/...``, ``sweep/...``), in percent of all
device self time, averaged over the cell's chips.  None for a program
that names none of them."""

from fleetbench.scopes import PROGRAM, charged_seconds, self_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    named = charged_seconds(ctx.trace, PROGRAM)
    if named <= 0.0:
        return None
    return 100.0 * (1.0 - named / self_seconds(ctx.trace))
