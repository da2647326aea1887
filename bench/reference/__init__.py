"""Plain references the benchmark compares the program against.

Nothing here imports the program (``repro``): the reference regenerates
every input it needs from the same seeds and conventions, and computes the
same semantics in straightforward JAX/numpy."""
