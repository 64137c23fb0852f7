"""Compile the serving path's programs for one described TPU v5e chip.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a chip
that is described, not attached.  That refuses what interpret mode cannot
see (Mosaic block-shape rules, VMEM limits, programs that do not fit the
chip).  Every program is compiled at real widths: the Pallas kernels at
qwen2-0.5b's attention heads and recurrentgemma-2b's recurrence width, and
the qwen2-0.5b score step at its published widths.

The topology is described inside a module-scoped fixture, never at import:
loading the TPU library takes a process-wide lock, so only the worker that
runs these tests may do it.  Keep every such compile in this one file.
"""
import pytest

V5E_HBM_BYTES = 16 * 1000 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    written for a described chip cannot be read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used <= V5E_HBM_BYTES, used
    return used


def _kernel_case(name):
    """(jitted kernel wrapper, argument shapes) at real widths."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops
    sds = jax.ShapeDtypeStruct
    qwen = get_config("qwen2-0.5b")
    Hq, Hkv, dh = qwen.n_heads, qwen.n_kv_heads, qwen.resolved_head_dim
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":
        B, S = 2, 512
        return ops.flash_attention, (sds((B, S, Hq, dh), bf16),
                                     sds((B, S, Hkv, dh), bf16),
                                     sds((B, S, Hkv, dh), bf16))
    if name == "decode_attention":
        B, L = 4, 1024
        return ops.decode_attention, (sds((B, 1, Hq, dh), bf16),
                                      sds((B, L, Hkv, dh), bf16),
                                      sds((B, L, Hkv, dh), bf16),
                                      sds((B,), jnp.int32))
    rg = get_config("recurrentgemma-2b")
    r = rg.rglru.d_rnn or rg.d_model
    assert r == 2560
    B, S = 2, 256
    return ops.rglru_scan, (sds((B, S, r), f32), sds((B, S, r), f32),
                            sds((B, r), f32))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "rglru_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    compiled = fn.lower(*_on(one_chip, shapes), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_qwen2_score_step_compiles_for_v5e(one_chip, tmp_path):
    import jax
    from repro.configs import get_config
    from repro.serving import ModelEndpoint, WeightStore
    ep = ModelEndpoint("qwen", get_config("qwen2-0.5b"),
                       WeightStore(str(tmp_path)), batch_size=4, seq_len=64)
    params, tokens = _on(one_chip, ep._specs())
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert n_params == 494_032_768
    compiled = jax.jit(ep._score_fn()).lower(params, tokens).compile()
    used = _fits_one_chip(compiled)
    assert used >= 2 * n_params                         # bf16 weights
    out = compiled.out_info
    assert out.shape == (4, 1, 151936)
