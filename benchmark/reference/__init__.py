"""Plain float32 references: ``jax.numpy`` only, nothing of the program."""
