"""Host-process environment control for backend selection.

A child process (or this one, before jax initializes a backend) that must
run on the CPU backend — the bench supervisor's oracle phase, the multichip
dryrun, the test conftest — needs ``JAX_PLATFORMS=cpu`` set before its
first jax import, and ``XLA_FLAGS`` naming a virtual device count where a
mesh is wanted. These three helpers are the ONE rule for that.

Imports nothing heavier than ``os`` — safe for supervisors that must not
touch jax themselves.
"""

from __future__ import annotations

import os
from typing import Dict, Optional


def scrubbed_cpu_env(n_devices: Optional[int] = None,
                     base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Return a copy of ``base`` (default ``os.environ``) forcing the jax
    CPU backend, optionally with ``n_devices`` virtual host devices.

    Must be applied to a child process (or to ``os.environ`` before jax
    initializes a backend) — backend choice is latched at first init.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(
            f for f in flags.split()
            if "xla_force_host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def apply_cpu_env(n_devices: Optional[int] = None) -> None:
    """In-place variant for processes that have not yet initialized jax."""
    os.environ.update(scrubbed_cpu_env(n_devices))


def ensure_cpu_env(default_devices: int = 8) -> None:
    """Force the CPU env in-place, adding ``default_devices``
    virtual host devices unless the caller's ``XLA_FLAGS`` already pins a
    device count. The ONE entry-point rule shared by the test conftest
    and the standalone distributed tests, so the device-count handling
    cannot diverge between the pytest and standalone paths."""
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        apply_cpu_env(default_devices)
    else:
        apply_cpu_env()
