"""The benchmark of mxtpu on the chip: see README.md beside this file."""
