"""The benchmark's yardstick: graph synthesis, the plain reference,
peaks, operation counts, traffic generation and trace reduction. Kept
here, apart from the program, so that no change to the program can
move it."""
