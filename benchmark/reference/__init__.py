"""Plain references: a configuration's forward pass in straightforward
float32 jax.numpy, with no kernels, cache or batching tricks, kept here
where no later PR can change it."""
