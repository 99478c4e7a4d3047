"""One file per kind of cell: how it sets up, warms, and runs its window."""
