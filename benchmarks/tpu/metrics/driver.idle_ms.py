"""Chip 0's idle time per round whose midpoint lies in a program span
other than `sync` (sampling, copies, launches: idle the driver's own host
work causes), in stretch (D), spans and device on one clock."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = pt.ensure(ctx)
    return None if stretch is None else pt.driver_idle_ms(stretch)
