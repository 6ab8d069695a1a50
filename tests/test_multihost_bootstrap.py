"""REAL two-process ``jax.distributed`` bring-up (VERDICT r3 next #6).

Every other multi-host test injects ``initialize_fn``; this one runs the
genuine article: a coordinator + 2 OS processes on the CPU backend (gloo
collectives), ``init_multihost`` resolving everything from the CDT_* env
vars — the exact path ``serve`` takes on a pod (``docs/deployment.md``
§2) — then asserts global membership and one cross-host psum.
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow      # spawns two fresh JAX processes

REPO = str(Path(__file__).resolve().parent.parent)

CHILD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    sys.path.insert(0, os.environ["CDT_REPO"])
    from comfyui_distributed_tpu.parallel.bootstrap import init_multihost
    from jax import shard_map

    # no initialize_fn injection: the real jax.distributed.initialize,
    # config entirely from CDT_COORDINATOR/CDT_NUM_HOSTS/CDT_HOST_INDEX
    assert init_multihost() is True

    import numpy as np
    import jax.numpy as jnp

    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 2
    assert len(jax.devices()) == 4, jax.devices()   # GLOBAL device list

    from comfyui_distributed_tpu.parallel import build_mesh

    mesh = build_mesh({"dp": 4})                    # spans both processes
    from jax.sharding import NamedSharding, PartitionSpec as P

    # cross-host psum: each device contributes (process_index+1); the sum
    # 2*(0+1) + 2*(1+1) = 6 is only reachable if the collective crossed
    # the process boundary
    contrib = jnp.full((jax.local_device_count(), 1),
                       float(jax.process_index() + 1))
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), np.asarray(contrib), (4, 1))

    @jax.jit
    def total(x):
        return shard_map(
            lambda s: jax.lax.psum(s, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
        )(x)

    out = np.asarray(jax.device_get(
        [s.data for s in total(garr).addressable_shards][0]))
    assert out.ravel()[0] == 6.0, out
    print("MULTIHOST_OK", jax.process_index(), flush=True)
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_bringup(tmp_path):
    port = _free_port()
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    procs = []
    for idx in range(2):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_"))}
        env.update({
            "CDT_REPO": REPO,
            "CDT_COORDINATOR": f"127.0.0.1:{port}",
            "CDT_NUM_HOSTS": "2",
            "CDT_HOST_INDEX": str(idx),
            # each child compiles a trivial program; isolate caches so a
            # cross-flag AOT mismatch can't SIGILL
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / f"xla{idx}"),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for idx, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {idx} failed:\n{out[-3000:]}"
        assert f"MULTIHOST_OK {idx}" in out
