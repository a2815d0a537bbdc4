"""Plain references the benchmark judges the program with: copies, so
that no comparison imports the code under test."""
