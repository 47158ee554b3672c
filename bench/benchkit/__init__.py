"""The chip benchmark's own code: cells, traffic, the plain reference, counts
of work, the trace reduction and the comparison that decides ``correct``.

Nothing here imports the program except :mod:`benchkit.serve` and
:mod:`benchkit.train`, which drive the system under test through its public
entry points.  :mod:`benchkit.reference` imports nothing of the program.
"""
