"""Plain references: each architecture's forward pass in float32 `jax.numpy`
at `highest` matmul precision, written from the papers and the published
checkpoint layouts, with no cache, no kernels and no code of the program."""
