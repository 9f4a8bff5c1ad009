"""Chip benchmark of the DPASGD round (see ``chipbench/README.md``)."""
