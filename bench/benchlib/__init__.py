"""The benchmark's own library: traffic, weights, reference, trace reduction,
FLOP/byte counts and peaks. Nothing here imports the program under test
except ``harness``, ``serve_cell`` and ``train_cell``, which drive it."""
