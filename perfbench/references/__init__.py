"""Plain references: each configuration's mathematics in straightforward
jax.numpy, float32 at `highest` matmul precision, no kernels, no cache, no
batching tricks. They import nothing of the program and take nothing it
made: weights come from the seed through perfbench.weights."""
