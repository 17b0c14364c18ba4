"""The benchmark's own yardstick: data generation, peaks, operation and
byte counts, trace reduction and the harness that runs one cell.

Nothing here imports the program except where a traffic kind drives
the system under test.
"""
