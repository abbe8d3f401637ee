"""CPU tests of the on-chip benchmark (``chipbench/``)."""
