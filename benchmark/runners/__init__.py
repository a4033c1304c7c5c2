"""One module per kind of cell. ``run(ctx)`` sets the system up from the
seed, measures for ``ctx.seconds``, checks what the timed path produced
against the plain reference and returns what ``run.py`` prints."""
