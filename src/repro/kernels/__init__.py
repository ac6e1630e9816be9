"""Pallas TPU kernels (compiled on a TPU, interpret mode on the CPU) + jnp oracles."""
