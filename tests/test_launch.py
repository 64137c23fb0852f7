"""Launch layer: production mesh construction, step builders lower+compile
on a small fake mesh, dry-run record structure, HLO collective parsing."""
import os
import subprocess
import sys

from repro.launch.dryrun import (_shape_bytes, convert_artifact_bytes,
                                 parse_collectives)


def test_collective_parsing():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), channel_id=1
  %ar = f32[4,4]{1,0} all-reduce(%y), replica_groups=[2,4]<=[8]
  %tup = (f32[16], f32[16]) all-to-all(%a, %b)
  %cp = s32[2,2]{1,0} collective-permute(%z)
"""
    out = parse_collectives(hlo)
    assert out["count"] == {"all-gather": 1, "all-reduce": 1,
                            "all-to-all": 1, "collective-permute": 1}
    assert out["bytes"]["all-gather"] == 8 * 128 * 2
    assert out["bytes"]["all-reduce"] == 16 * 4
    assert out["bytes"]["all-to-all"] == 2 * 16 * 4
    assert out["total_bytes"] == sum(out["bytes"].values())


def test_shape_bytes_tuple():
    assert _shape_bytes("(bf16[4,4], f32[2])") == 32 + 8
    assert _shape_bytes("pred[100]") == 100


def test_convert_artifact_detection():
    big = 40_000_000  # 160MB f32
    hlo = f"%c = f32[{big}] convert(%param_1.3)\n%d = f32[10] convert(%param_2)"
    assert convert_artifact_bytes(hlo) == big * 4


CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import dataclasses
import jax
from repro.configs import get_config, INPUT_SHAPES
from repro.launch import steps as st
from repro.models import make_model

# production mesh shapes (as functions, no import-time device use)
from repro.launch.mesh import make_production_mesh

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# reduced config through every builder on the tiny mesh
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(d_model=128),
                          vocab_size=256)
model = make_model(cfg)
shape = dataclasses.replace(INPUT_SHAPES["decode_32k"], seq_len=64,
                            global_batch=4)
step, specs, donate, M = st.build_decode_step(model, shape, mesh)
with mesh:
    compiled = jax.jit(step, donate_argnums=donate).lower(*specs).compile()
assert compiled.memory_analysis() is not None

shape_t = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=64,
                              global_batch=4)
step, specs, donate, M = st.build_train_step(model, shape_t, mesh,
                                             microbatches=2)
with mesh:
    compiled = jax.jit(step, donate_argnums=donate).lower(*specs).compile()
ca = compiled.cost_analysis()
assert ca and ca.get("flops", 0) > 0

units = st.build_units(model, shape_t, mesh, microbatches=2)
names = {u.name for u in units}
assert "block_attn" in names and "opt_update" in names
assert "block_attn__act" in names
with mesh:
    for u in units:
        jax.jit(u.fn).lower(*u.specs).compile()
print("ALL_OK")
"""


def test_step_builders_compile_on_fake_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALL_OK" in out.stdout
