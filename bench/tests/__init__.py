"""CPU tests of the benchmark: the yardstick, the loading of cells and
metrics by name, and rehearsals of each cell on tiny shapes."""
