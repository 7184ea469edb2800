"""The one copy of the force-CPU-backend recipe.

``JAX_PLATFORMS=cpu`` in the environment selects the CPU by itself; this
helper does the same from code (``jax.config`` after import, before any
backend initialization) for entry points that take a ``--cpu`` flag, and
adds the virtual-device count for multi-device-on-CPU testing, which rides
XLA_FLAGS and is read lazily by the CPU client at backend creation.

Used by tests/conftest.py, __graft_entry__.dryrun_multichip and the tools'
``--cpu`` flags.
"""

from __future__ import annotations

import os


def force_cpu_backend(n_devices: int | None = None):
    """Force the CPU backend; optionally request ``n_devices`` virtual
    devices.  Must run before any jax backend initialization (first device
    query / computation).  Returns the configured jax module."""
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        # replace (not skip) any existing count so the caller's request wins
        kept = [f for f in flags.split()
                if "xla_force_host_platform_device_count" not in f]
        kept.append(f"--xla_force_host_platform_device_count={n_devices}")
        os.environ["XLA_FLAGS"] = " ".join(kept)

    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax
