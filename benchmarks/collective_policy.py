"""Mesh-level policy experiment: bulk all-gather vs COPIFTv2 ring matmul.

Runs in a subprocess with 8 host devices (the parent process must keep the
default device count for the other benchmarks).  The child is pinned to the
CPU: it measures host virtual devices, and an accelerator, if any, already
belongs to the parent.  Reports wall time and the HLO collective op counts
for both policies."""
import json
import os
import subprocess
import sys

_CHILD = r"""
import json, time
import jax, jax.numpy as jnp
from repro.distributed.collective_matmul import tp_matmul
from repro.core.policy import ExecutionPolicy as EP
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(2, 4)
x = jax.random.normal(jax.random.PRNGKey(0), (2048, 1024), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (1024, 2048), jnp.float32)
out = {}
for pol in (EP.COPIFT, EP.COPIFTV2):
    f = jax.jit(lambda a, b, p=pol: tp_matmul(a, b, mesh, policy=p))
    y = f(x, w); y.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        y = f(x, w)
    y.block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    hlo = f.lower(x, w).compile().as_text()
    out[pol.value] = {
        "us": us,
        "all_gather_ops": hlo.count(" all-gather("),
        "permute_ops": hlo.count(" collective-permute("),
    }
print(json.dumps(out))
"""


def run():
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"}
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    if res.returncode != 0:
        raise RuntimeError(f"collective_policy child exited "
                           f"{res.returncode}:\n{res.stderr[-2000:]}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    rows = []
    for pol, d in data.items():
        rows.append((f"collective_{pol}_us", d["us"], 0.0))
        rows.append((f"collective_{pol}_allgather_ops", 0.0,
                     d["all_gather_ops"]))
        rows.append((f"collective_{pol}_permute_ops", 0.0, d["permute_ops"]))
    return rows


def main():
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived:.4f}")


if __name__ == "__main__":
    main()
