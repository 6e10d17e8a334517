"""The benchmark's yardstick: loader, traffic generator, serving loops,
metric arithmetic, plain reference, trace reduction, operation counts and
the table of peaks. Only ``system.py`` imports the program under test."""
