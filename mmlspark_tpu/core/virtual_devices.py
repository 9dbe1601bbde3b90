"""Force a virtual multi-device CPU platform for mesh testing.

The reference tests multi-node behavior on a single JVM via ``local[*]``
(SURVEY.md §4.4); the analog here is an n-device CPU platform via
``xla_force_host_platform_device_count`` so shard_map/collective paths
execute for real without multi-chip TPU hardware.

``JAX_PLATFORMS=cpu`` in the environment is honoured by itself; this
helper additionally forces the device COUNT, through both ``XLA_FLAGS``
and ``jax.config``, and must run before any XLA backend is
initialized. Used by ``tests/conftest.py`` and ``__graft_entry__``.
"""

import os


def force_cpu_devices(n_devices: int) -> None:
    """Make ``jax.devices()`` return ``n_devices`` virtual CPU devices.

    Must be called before any JAX computation executes in the process.
    Idempotent when the platform is already a CPU backend with at least
    ``n_devices`` devices; raises a clear error otherwise.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError as e:
        # Backends already initialized — fine only if they already satisfy
        # the request.
        devices = jax.devices()
        if devices[0].platform == "cpu" and len(devices) >= n_devices:
            return
        raise RuntimeError(
            f"force_cpu_devices({n_devices}) called after JAX backends "
            f"initialized with {len(devices)} {devices[0].platform} "
            "device(s); call it before any JAX computation runs in this "
            "process") from e
