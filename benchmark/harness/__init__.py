"""The yardstick: everything the benchmark computes itself.

Later PRs may change the program; they may not change these files.  From the
program the benchmark takes only the system under test (model, train loop),
its counters, and its program and kernel names.
"""
