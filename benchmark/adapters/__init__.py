"""Entry-point adapters, chosen by the traffic file's `adapter` key. An
adapter makes the cell's inputs and weights from the seed, warms up the
cell's own shapes, makes one timed call at a time through the entry point
a user calls, and compares what the timed calls produced with the plain
reference. These files are the only ones of the benchmark that import the
program."""
