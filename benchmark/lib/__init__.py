"""The yardstick: everything the benchmark computes with lives here, so
that a PR that changes the program cannot change how it is measured."""
