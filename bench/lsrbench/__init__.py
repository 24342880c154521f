"""The benchmark's yardstick: data generation, load, reference,
comparison and trace reduction. Nothing here imports the program under
test except ``cell``, which drives it."""
