"""One driver per kind of cell: ``run(ctx)`` sets up, measures the window, frees the program and compares."""
