"""Integration: lower+compile smoke cells on a virtual multi-device mesh.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes —
the rest of the suite needs the real single-device CPU.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, {src!r})
import jax
from repro.configs import ARCHS, SHAPES
from repro.configs.shapes import ShapeCell
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
out = {{}}
shape = ShapeCell("train_mini", "train", 64, 8)
for arch in {archs!r}:
    spec = ARCHS[arch]
    cell = steps_mod.build_cell(arch, spec, shape, mesh, smoke=True)
    lowered = steps_mod.lower_cell(cell)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    has_coll = any(k in txt for k in ("all-reduce", "all-gather",
                                      "reduce-scatter", "all-to-all",
                                      "collective-permute"))
    out[arch] = {{"flops": float(ca.get("flops", 0)),
                  "collectives": bool(has_coll)}}
    # decode cell as well
    dshape = ShapeCell("decode_mini", "decode", 64, 8)
    dcell = steps_mod.build_cell(arch, spec, dshape, mesh, smoke=True)
    steps_mod.lower_cell(dcell).compile()
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_mini_dryrun_multidevice(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    archs = ["qwen3-4b", "mamba2-780m", "jamba-v0.1-52b", "whisper-medium",
             "grok-1-314b"]
    script = _SCRIPT.format(src=os.path.abspath(src), archs=archs)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=1200,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    for arch in archs:
        assert out[arch]["flops"] > 0, arch
        # a (2,4) mesh with model parallelism must produce collectives
        assert out[arch]["collectives"], arch


_SP_DECODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from repro.models import attention as A
from repro.models.config import ModelConfig
from repro.models.param import ParamBuilder
from repro.distributed import sharding as shardlib
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                  num_heads=8, num_kv_heads=2, d_ff=128, vocab_size=64,
                  head_dim=16, dtype="float32")
pb = ParamBuilder(jax.random.key(0), dtype=jnp.float32)
A.init_attention(pb.scope("a"), cfg)
p = pb.params["a"]
B, S = 4, 64
x = jax.random.normal(jax.random.key(1), (B, 1, 64))
ck = jax.random.normal(jax.random.key(2), (B, S, cfg.kv_dim)) * 0.5
cv = jax.random.normal(jax.random.key(3), (B, S, cfg.kv_dim)) * 0.5
pos = jnp.asarray([5, 17, 31, 63])
mesh = make_mesh((2, 4), ("data", "model"))
ref_out, _, _ = A.decode_attention(p, x, cfg, ck, cv, pos)
rules = shardlib.default_rules(mesh, overrides={{"kv_seq": "model"}})
def fn(p, x, ck, cv, pos):
    with shardlib.use_sharding(mesh, rules):
        return A.decode_attention(p, x, cfg, ck, cv, pos)
sp_out, _, _ = jax.jit(fn)(p, x, ck, cv, pos)
np.testing.assert_allclose(np.asarray(ref_out), np.asarray(sp_out),
                           rtol=2e-5, atol=2e-5)
print("SP_DECODE_OK")
"""


@pytest.mark.slow
def test_seq_parallel_decode_matches_reference():
    """Distributed LSE decode attention == single-device reference."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SP_DECODE.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SP_DECODE_OK" in proc.stdout
