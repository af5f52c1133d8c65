"""Pallas TPU kernels (``conv2d``, ``flash_attn``, ``ssd``) and their
pure-jnp oracles (``ref``).  Callers pass ``interpret=True`` to run a
kernel off a TPU; nothing here picks interpret mode on its own."""
