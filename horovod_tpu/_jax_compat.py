"""The one JAX configuration call the subprocess worker scripts in
``tests/`` share."""

from __future__ import annotations

import jax


def force_cpu_devices(n: int) -> None:
    """Configure an ``n``-device virtual CPU mesh. Call right after
    importing jax, before any device query initializes the backend."""
    jax.config.update("jax_num_cpu_devices", int(n))
