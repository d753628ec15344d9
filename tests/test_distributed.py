"""Distribution-layer tests: sharding rules, collective matmul policies,
HLO collective-byte parsing, and cell lowering."""
import os
import subprocess
import sys

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.config import SHAPES, RunConfig
from repro.configs import get_config, get_reduced
from repro.distributed.sharding import _leaf_pspec, param_pspecs
from repro.roofline import Roofline, collective_bytes

MESH = AbstractMesh((16, 16), ("data", "model"))
RC = RunConfig()
RC_FSDP = RunConfig(fsdp=True)


def _find(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def test_attention_heads_sharded_over_model():
    specs = param_pspecs(get_config("phi3-mini-3.8b"), MESH, RC)
    wq = _find(specs, "blocks/attn/wq")          # (L, d, H, hd)
    assert wq == P(None, None, "model")


def test_fsdp_adds_data_axis_on_embed_dim():
    specs = param_pspecs(get_config("phi3-mini-3.8b"), MESH, RC_FSDP)
    wq = _find(specs, "blocks/attn/wq")
    assert wq == P(None, "data", "model")


def test_glm4_kv_heads_replicated_when_indivisible():
    specs = param_pspecs(get_config("glm4-9b"), MESH, RC)
    wk = _find(specs, "blocks/attn/wk")          # kv_heads=2 < model=16
    assert wk == P(None, None, None) or wk == P()


def test_olmoe_experts_sharded_granite_falls_back():
    olmoe = param_pspecs(get_config("olmoe-1b-7b"), MESH, RC)
    assert _find(olmoe, "blocks/ffn/wi") == P(None, "model")
    granite = param_pspecs(get_config("granite-moe-3b-a800m"), MESH, RC)
    # 40 experts % 16 != 0 -> the expert hidden dim takes the model axis
    assert _find(granite, "blocks/ffn/wi") == P(None, None, None, "model")


def test_vocab_sharded():
    specs = param_pspecs(get_config("phi3-mini-3.8b"), MESH, RC)
    assert specs["embed"] == P("model")
    assert specs["head"] == P("model")


def test_leaf_pspec_never_reuses_axis():
    spec = _leaf_pspec((64, 64), ("heads", "ff"), MESH, fsdp=False)
    used = [a for a in spec if a is not None]
    assert len(used) == len(set(used))


# --- collective bytes parser -------------------------------------------------

HLO_SNIPPET = """
HloModule test
ENTRY main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %p1 = bf16[8,256]{1,0} parameter(1)
  %ag = f32[64,128]{1,0} all-gather(%p0), dimensions={0}
  %ar = bf16[8,256]{1,0} all-reduce(%p1), to_apply=add
  %cp = bf16[8,256]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""


def test_collective_bytes_parser():
    out = collective_bytes(HLO_SNIPPET)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 8 * 256 * 2
    assert out["collective-permute"] == 8 * 256 * 2
    assert out["total"] == out["all-gather"] + out["all-reduce"] + \
        out["collective-permute"]


def test_roofline_terms():
    r = Roofline(arch="a", shape="s", mesh="m", chips=256,
                 per_device_flops=197e12, per_device_bytes=819e9,
                 per_device_coll_bytes=200e9, model_flops=197e12 * 256 * 0.5)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9
    assert abs(r.mfu - 0.5) < 1e-9
    assert r.useful_flops_ratio == 0.5


# --- cell lowering machinery (1-device mesh; the 512-chip sweep runs via
#     launch.dryrun against the production meshes) ---------------------------

@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k", "decode_32k"])
def test_lower_cell_reduced(shape_name, monkeypatch):
    import dataclasses
    from repro.launch.dryrun import lower_cell, default_runconfig
    from repro.launch.mesh import make_local_mesh
    cfg = get_reduced("glm4-9b")
    shape = dataclasses.replace(SHAPES[shape_name], seq_len=64, global_batch=2)
    mesh = make_local_mesh(1, 1)
    lowered = lower_cell(cfg, shape, mesh, default_runconfig(shape))
    compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_ring_matmul_multidevice_subprocess():
    """Ring (COPIFTv2) == bulk (COPIFT) numerically on an 8-device mesh, and
    their HLO uses collective-permute vs all-gather respectively."""
    child = (
        "import jax, jax.numpy as jnp, numpy as np\n"
        "from repro.distributed.collective_matmul import tp_matmul\n"
        "from repro.core.policy import ExecutionPolicy as EP\n"
        "from repro.launch.mesh import make_local_mesh\n"
        "mesh = make_local_mesh(2, 4)\n"
        "x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))\n"
        "w = jax.random.normal(jax.random.PRNGKey(1), (32, 48))\n"
        "ref = x @ w\n"
        "for pol in (EP.COPIFT, EP.COPIFTV2):\n"
        "    y = tp_matmul(x, w, mesh, policy=pol)\n"
        "    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),\n"
        "                               rtol=1e-5, atol=1e-5)\n"
        "t_b = jax.jit(lambda a, b: tp_matmul(a, b, mesh, policy=EP.COPIFT)"
        ").lower(x, w).compile().as_text()\n"
        "t_r = jax.jit(lambda a, b: tp_matmul(a, b, mesh, policy=EP.COPIFTV2)"
        ").lower(x, w).compile().as_text()\n"
        "assert 'all-gather' in t_b and 'collective-permute' not in t_b\n"
        "assert 'collective-permute' in t_r and 'all-gather' not in t_r\n"
        "print('SUBPROCESS_OK')\n")
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src"}
    res = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SUBPROCESS_OK" in res.stdout, res.stderr[-2000:]
