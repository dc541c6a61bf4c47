"""Forced-host-platform device helpers (the dry-run trick, shared).

jax locks the device count at first backend init, so anything that
wants N CPU "devices" (the multi-pod dry-run, the measurement harness,
comm tests) must put ``--xla_force_host_platform_device_count=N`` into
``XLA_FLAGS`` *before the first jax import* — usually in a fresh
subprocess.  This module is the one place that flag is spelled:

* :func:`host_device_flags` — an ``XLA_FLAGS`` value with the flag
  **appended** to whatever the caller already set (never clobbering
  user flags; an existing count flag is replaced, so the helper is
  idempotent);
* :func:`force_host_device_count` — apply it to ``os.environ`` (call
  before importing jax);
* :func:`child_env` — an environment dict for spawning a measurement /
  dry-run subprocess on the CPU platform (``JAX_PLATFORMS=cpu``: forced
  host devices are CPU devices, and a child must never race its parent
  for a host's chip).

Deliberately jax-free: importing this module must never initialize the
backend the flag is trying to configure.
"""
from __future__ import annotations

import os
import re
from typing import MutableMapping

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"

_FLAG_RE = re.compile(re.escape(HOST_DEVICE_FLAG) + r"=\d+")


def host_device_flags(n_devices: int, existing: str | None = None) -> str:
    """``XLA_FLAGS`` value forcing ``n_devices`` host devices.

    ``existing`` (the current ``XLA_FLAGS``, possibly ``None``/empty)
    is preserved verbatim apart from any previous host-device-count
    flag, which is replaced — repeated calls don't accumulate flags
    and user-set flags (e.g. ``--xla_cpu_enable_fast_math``) survive.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    flag = f"{HOST_DEVICE_FLAG}={n_devices}"
    if not existing:
        return flag
    kept = re.sub(r"\s+", " ", _FLAG_RE.sub("", existing)).strip()
    return f"{kept} {flag}" if kept else flag


def force_host_device_count(n_devices: int,
                            env: MutableMapping[str, str] | None = None) -> str:
    """Set ``XLA_FLAGS`` in ``env`` (default ``os.environ``) to force
    ``n_devices`` host devices, appending to any existing flags.  Must
    run before the first jax import; returns the value set."""
    env = os.environ if env is None else env
    value = host_device_flags(n_devices, env.get("XLA_FLAGS"))
    env["XLA_FLAGS"] = value
    return value


def child_env(n_devices: int,
              base: MutableMapping[str, str] | None = None) -> dict[str, str]:
    """A copy of ``base`` (default ``os.environ``) with ``XLA_FLAGS``
    forcing ``n_devices`` host devices and ``JAX_PLATFORMS=cpu`` — for
    ``subprocess.run(env=...)`` when the current process already
    initialized jax."""
    env = dict(os.environ if base is None else base)
    env["XLA_FLAGS"] = host_device_flags(n_devices, env.get("XLA_FLAGS"))
    env["JAX_PLATFORMS"] = "cpu"
    return env
