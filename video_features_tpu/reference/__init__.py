"""Plain references: a family's forward pass in straightforward ``jax.numpy``
and float32, with no kernel, no packing and no batching, for the tests to hold
the program to."""
