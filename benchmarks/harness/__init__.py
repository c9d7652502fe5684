"""The benchmark's own yardstick: traffic generation, metric arithmetic,
the table of peaks, the plain reference and the trace reduction. Nothing
here is read from the program under test."""
