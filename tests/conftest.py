import os

# Force a virtual 8-device CPU mesh for all tests: multi-chip sharding code
# must compile and run without TPU hardware (chip_smoke.py drives the real
# one- and four-chip paths on the chip).  Pin the platform in the config as
# well as the env, so a test run on a machine with a chip still gets the
# virtual CPU mesh and never claims the chip.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

HEP_TH = "/root/reference/data/hep-th.dat"

#: cached verdict of the 2-process collectives probe (None = not yet run)
_CPU_MP_BLOCKED = None


def cpu_multiprocess_collectives_blocked() -> bool:
    """Probe (once per session) whether this jax CPU backend can run
    collectives across a 2-process coordination service.  The installed
    jax 0.9.0 CPU backend can, so the 6 two-process tests run; an older
    CPU backend that cannot ("Multiprocess computations aren't
    implemented on the CPU backend") makes them skip instead of fail.
    The probe runs the exact shape those tests need (a shard_map psum
    over a mesh spanning two processes).
    """
    global _CPU_MP_BLOCKED
    if _CPU_MP_BLOCKED is not None:
        return _CPU_MP_BLOCKED
    import socket
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    prog = (
        "import sys\n"
        "import numpy as np\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.distributed.initialize(sys.argv[1], 2, int(sys.argv[2]))\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(np.array(jax.devices()), ('i',))\n"
        "x = jax.make_array_from_process_local_data(\n"
        "    NamedSharding(mesh, P('i')), np.ones(1, np.float32),\n"
        "    (mesh.size,))\n"
        "from jax import shard_map\n"
        "out = shard_map(lambda v: jax.lax.psum(v, 'i'), mesh=mesh,\n"
        "                in_specs=(P('i'),), out_specs=P())(x)\n"
        "assert float(np.asarray(out.addressable_shards[0].data).sum()) \\\n"
        "    == mesh.size\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["SHEEP_CONNECT_TIMEOUT"] = "60"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", prog, coord, str(pid)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
        for pid in range(2)]
    try:
        for p in procs:
            p.wait(timeout=120)
        blocked = any(p.returncode != 0 for p in procs)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        blocked = True  # a hang is the same environmental verdict
    _CPU_MP_BLOCKED = blocked
    return blocked


@pytest.fixture(scope="session")
def cpu_multiprocess():
    """The skipif for the env-blocked two-process tests: skip (with the
    documented environmental reason) when the CPU backend cannot run
    multiprocess collectives; a no-op where it can."""
    if cpu_multiprocess_collectives_blocked():
        pytest.skip("environmental: this jax CPU backend cannot run "
                    "multiprocess collectives (probe in "
                    "conftest.cpu_multiprocess_collectives_blocked)")


@pytest.fixture(scope="session")
def hep_edges():
    from sheep_tpu.io import load_edges

    if not os.path.exists(HEP_TH):
        pytest.skip("hep-th.dat not available")
    return load_edges(HEP_TH)


def random_multigraph(rng, n_max=40, e_max=120, self_loops=True):
    """Random multigraph edge records (may include self-loops, multi-edges)."""
    n = int(rng.integers(2, n_max))
    e = int(rng.integers(1, e_max))
    tail = rng.integers(0, n, size=e).astype(np.uint32)
    head = rng.integers(0, n, size=e).astype(np.uint32)
    if not self_loops:
        fix = tail == head
        head[fix] = (head[fix] + 1) % n
    return tail, head
