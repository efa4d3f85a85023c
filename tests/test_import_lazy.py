"""Importing the package must NEVER initialize the jax backend.

Initializing the backend on a TPU host takes the chip, and a chip belongs
to one process: a launcher that merely imports the package (the
supervisor, the fleet manager, a chaos parent) must stay off it, or the
children it starts cannot get it.  One stray module-level
``jnp.<type>(...)`` constant silently breaks that by executing a primitive
at import time (regression: ops/guidance_device.py once held
``_BIG = jnp.int32(1 << 30)``).
"""

import subprocess
import sys


def test_package_import_does_not_init_backend():
    code = (
        "import distributedpytorch_tpu.train, distributedpytorch_tpu.ops, "
        "distributedpytorch_tpu.parallel, distributedpytorch_tpu.predict, "
        "distributedpytorch_tpu.data\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'package import executed a jax primitive (module-level jnp call?)'\n"
        "print('lazy-ok')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    assert "lazy-ok" in out.stdout
