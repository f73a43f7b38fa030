import os

# Smoke tests see the single real CPU device — the 512-device flag is
# reserved for the dry-run (launch/dryrun.py sets it before jax init).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

jax.config.update("jax_enable_x64", False)


def abstract_mesh(shape, axes):
    """Abstract mesh over DUPLICATED host devices — sharding METADATA
    only (specs, divisibility guards), never execution. Tests that need
    programs to actually SPMD-partition must go through a subprocess
    with virtual CPU devices and launch/mesh.make_serving_mesh instead
    (tests/test_shard_serve.py)."""
    n = int(np.prod(shape))
    devs = np.array(jax.devices() * n)[:n].reshape(shape)
    return Mesh(devs, axes)
