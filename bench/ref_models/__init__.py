"""Plain float32 forward passes of the benchmark's model architectures,
written from their papers and published configs; they import nothing of
the program under test."""
