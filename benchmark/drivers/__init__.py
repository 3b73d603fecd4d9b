"""Traffic drivers, one module per ``driver`` a traffic mix names.

A driver's ``serve(cell, args, clock, t_start, trace_dir)`` runs the cell
in the parent, which holds the chip, and returns its result
(``harness``). A driver whose parent serves HTTP (``serve_http``) also
has the side that runs in the load generator's process, which never
imports JAX: ``drive`` sends the mix to the service's port and
``end_to_end`` turns its records into the cell's end-to-end metrics.
"""
