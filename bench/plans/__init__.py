"""How the benchmark drives each plan family of the program, one module per
family, found by the ``plan`` key of a configuration file."""
