"""The benchmark's yardstick: cell discovery, the timed window, the device
gate and peaks, operation counts, seeded data and the trace reduction.
Nothing here imports the program."""
