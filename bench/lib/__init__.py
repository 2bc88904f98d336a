"""The benchmark's yardstick: graph synthesis, the precisions of the
plain reference and the numbers compared, peaks, traffic generation
and trace reduction; each model's plain forward and operation count
are in ``bench/models``. Kept here, apart from the program, so that no
change to the program can move it."""
