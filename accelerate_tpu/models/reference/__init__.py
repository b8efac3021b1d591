"""Plain ``jax.numpy`` float32 references of model families: no cache, no
kernels, no batching. The tests compare the served models with them."""
