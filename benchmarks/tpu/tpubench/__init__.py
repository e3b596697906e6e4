"""The on-chip benchmark's yardstick: specs, traffic, work counts, trace
reduction, the plain reference and the comparison that decides `correct`.

Nothing here is imported by the program under test; the program is
reached only from ``run.py`` (``repro.fl.runtime.Federation``)."""
