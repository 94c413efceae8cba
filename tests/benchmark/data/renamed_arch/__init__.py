"""A second architecture package, for ``test_benchmark_architectures.py``
alone: the one block the program can run today, stated under OTHER
published key names, with a plain reference of its own.  The test copies
it into a copy of the tree as ``benchmark/architectures/renamed/`` and runs
a cell on it without changing one file that was there: it proves the
route, not a model."""
