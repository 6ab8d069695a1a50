"""Multi-host JAX runtime bootstrap.

The reference scales across machines by HTTP port registration: every
worker is a separately-launched ComfyUI process that the master reaches
over the network (``workers/process/lifecycle.py:78-96``, config hosts).
On TPU the runtime-level membership is JAX's distributed runtime instead:
one coordinator, N host processes, after which ``jax.devices()`` returns
the GLOBAL device list and a single ``Mesh`` spans hosts — collectives
ride ICI within a slice and DCN across slices (SURVEY §5.8). The HTTP
control plane stays for orchestration/UI exactly like the reference's.

Deployment flow (see ``docs/deployment.md``):

    # host 0 (coordinator)
    python -m comfyui_distributed_tpu serve \
        --coordinator host0:9911 --num-hosts 4 --host-index 0
    # hosts 1..3
    python -m comfyui_distributed_tpu serve \
        --coordinator host0:9911 --num-hosts 4 --host-index i

Env-var equivalents (for k8s/pod launchers that template manifests):
``CDT_COORDINATOR``, ``CDT_NUM_HOSTS``, ``CDT_HOST_INDEX``.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Callable, Optional

from ..utils.logging import log

_initialized = False


def ensure_virtual_devices(n: Optional[int] = None) -> Optional[int]:
    """Stand up an ``n``-device virtual CPU mesh BEFORE jax initializes.

    The executed mesh tier (sp / dp×tp shard_map programs,
    docs/parallelism.md) is tier-1-testable off hardware by running on
    XLA's virtual host devices (``--xla_force_host_platform_device_count``).
    ``n`` falls back to ``CDT_VIRTUAL_DEVICES``; unset/0 is a no-op.

    XLA reads the flag once at backend init, so this MUST run before the
    first ``import jax`` anywhere in the process — a silent late call
    would leave the caller executing a "mesh" program on one device
    while believing it validated eight. Fails loudly instead.
    """
    from ..utils import constants

    n = n if n is not None else constants.VIRTUAL_DEVICES.get()
    if not n:
        return None
    if n < 2:
        raise ValueError(f"CDT_VIRTUAL_DEVICES={n}: a virtual mesh needs "
                         "at least 2 devices")
    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(
        r"xla_force_host_platform_device_count=(\d+)", flags)
    if existing:
        have = int(existing.group(1))
        if have != n:
            # silently proceeding would leave the caller executing an
            # n-device "mesh" on `have` devices — the exact state this
            # function exists to prevent
            raise RuntimeError(
                f"CDT_VIRTUAL_DEVICES={n} conflicts with XLA_FLAGS "
                f"already forcing {have} host devices")
        return n         # already configured (test conftest, driver env)
    if "xla_force_host_platform_device_count" in flags:
        raise RuntimeError(
            "XLA_FLAGS carries a malformed "
            "xla_force_host_platform_device_count; refusing to guess")
    if "jax" in sys.modules:
        raise RuntimeError(
            f"CDT_VIRTUAL_DEVICES={n} but jax is already imported — the "
            "virtual device count is frozen at backend init. Set the "
            "knob (or call ensure_virtual_devices) before anything "
            "imports jax.")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    # virtual devices exist only on the host platform; on a host with a
    # chip the default backend would be the chip
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    log(f"virtual mesh: {n} host devices "
        f"(--xla_force_host_platform_device_count)")
    return n


def multihost_env() -> dict:
    """The multi-host settings resolved from env (CLI flags override)."""
    from ..utils import constants

    return {
        "coordinator_address": constants.COORDINATOR.get() or None,
        "num_processes": constants.NUM_HOSTS.get(),
        "process_id": constants.HOST_INDEX.get(),
    }


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    initialize_fn: Optional[Callable] = None,
) -> bool:
    """Initialize JAX's distributed runtime when a coordinator is given.

    Arguments fall back to ``CDT_COORDINATOR`` / ``CDT_NUM_HOSTS`` /
    ``CDT_HOST_INDEX``. Returns True when the runtime was initialized,
    False for the single-host no-op. Must run before the first device
    query — JAX's backend is frozen once touched.

    ``initialize_fn`` exists for tests (the real
    ``jax.distributed.initialize`` wants a live coordinator).
    """
    global _initialized
    env = multihost_env()
    coordinator_address = coordinator_address or env["coordinator_address"]
    if not coordinator_address:
        return False
    if _initialized:
        log("multi-host runtime already initialized; skipping")
        return True
    num_processes = num_processes if num_processes is not None else env["num_processes"]
    process_id = process_id if process_id is not None else env["process_id"]
    if num_processes is None or process_id is None:
        raise ValueError(
            "multi-host bootstrap needs --num-hosts and --host-index "
            "(or CDT_NUM_HOSTS / CDT_HOST_INDEX) alongside the coordinator")
    if not (0 <= process_id < num_processes):
        raise ValueError(
            f"host index {process_id} out of range for {num_processes} hosts")

    if initialize_fn is None:                      # pragma: no cover - needs pod
        import jax

        initialize_fn = jax.distributed.initialize
    log(f"initializing multi-host runtime: coordinator={coordinator_address} "
        f"hosts={num_processes} index={process_id}")
    initialize_fn(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return True
