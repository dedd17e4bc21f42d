"""Runtime-configuration shims (see DESIGN.md §6).

The one thing left here is the x64 probe: repro-lint rule R5 confines
float64/x64 references to this module.
"""

from __future__ import annotations

import jax


def widest_float():
    """The widest float dtype the runtime allows: f64 under x64 mode,
    f32 otherwise. The only sanctioned way to consult x64 state —
    repro-lint rule R5 confines float64/x64 references to this module."""
    import jax.numpy as jnp
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
