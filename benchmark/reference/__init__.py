"""Plain references: straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision, no cache, no kernels, nothing imported from the program."""
