"""The yardstick: cell resolution, traffic, traces, counts, peaks and the
references that decide ``correct``. Nothing here imports the program."""
