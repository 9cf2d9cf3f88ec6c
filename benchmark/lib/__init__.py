"""The benchmark's own yardstick: traffic, trace reduction, shape
arithmetic, peaks, statistics and the comparison that decides ``correct``.
Nothing here is imported by the program, and only ``drivers/`` and
configuration hooks import the program."""
