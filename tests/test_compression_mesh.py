"""compressed_psum correctness on a real (multi-host-device) mesh — needs
its own process for the device count."""
import json
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.train.compression import compressed_psum

mesh = jax.make_mesh((4,), ("d",))
x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0

def f(kind):
    def body(xl):
        return compressed_psum(xl[0], "d", kind)[None]
    return jax.shard_map(body, mesh=mesh, in_specs=(P("d", None),),
                         out_specs=P("d", None), check_vma=False)

want = np.asarray(x.sum(0))
out = {}
for kind in ("bf16", "int8"):
    got = np.asarray(jax.jit(f(kind))(x))[0]
    out[kind] = float(np.abs(got - want).max() / np.abs(want).max())
print("RESULT::" + json.dumps(out))
"""


@pytest.mark.slow
def test_compressed_psum_on_mesh():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT::")][0]
    out = json.loads(line[len("RESULT::"):])
    assert out["bf16"] < 0.01
    assert out["int8"] < 0.03
