"""Plain references: jax.numpy in float32 at `highest` matmul precision, no
kernels, no cache, no batching tricks; nothing imported from paddle_tpu."""
