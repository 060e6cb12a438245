"""Pallas kernels for the chip's hot paths (attention, Eva's fused update),
their jnp twins (``ref``), and the backend rule that picks them
(``dispatch``)."""
