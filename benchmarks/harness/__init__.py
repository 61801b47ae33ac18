"""The yardstick: what the benchmark keeps for itself so that a PR which
changes the program cannot change how it is measured."""
