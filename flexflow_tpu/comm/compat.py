"""Start-up helpers shared by tests and entry scripts: the virtual CPU
mesh and ``shard_map`` with the replication checker off by default.
Written for the one installation there is (jax 0.9, pyproject.toml)."""

from __future__ import annotations

import jax


def force_cpu_devices(n: int) -> None:
    """Select the CPU platform with ``n`` virtual devices — callable
    only BEFORE the jax backend initializes (conftest/boot time)."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def shard_map(f, mesh, in_specs, out_specs, check=False, axis_names=None):
    """``jax.shard_map`` with the replication checker off by default
    (every call site here runs hand-written collectives the checker
    cannot verify).

    ``axis_names`` — the MANUAL axes for a partial-manual region (the
    pipeline lowering: collectives over ``pp`` only, GSPMD elsewhere);
    None means every mesh axis."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check, axis_names=frozenset(axis_names or ()),
    )
