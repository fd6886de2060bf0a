"""The yardstick: everything the benchmark measures with lives here, where a
PR that changes the program cannot reach it."""
