"""The benchmark harness: everything here is cell-agnostic; what belongs to
one configuration, traffic mix or metric lives in a data file or a module
found by name."""
