"""YaRN rope in multi-head latent attention, as DeepSeek-V2-Lite publishes
it: the blended inverse frequencies, the mscale numbers and the softmax
scale by hand, and prefill, naive decode and absorbed decode agreeing
with the full forward with YaRN on (reduced size, float32)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.models.mla import (rope_inv_freq, softmax_scale,
                              yarn_correction_range, yarn_mscale)

PUBLISHED = get_config("deepseek-v2-lite-16b")


def test_published_yarn_numbers_by_hand():
    y = PUBLISHED.mla.yarn
    assert (y.factor, y.original_max_position) == (40.0, 4096)
    # correction dims: 64 ln(4096 / (2 pi r)) / (2 ln 10000) at r = 32, 1
    assert yarn_correction_range(y, 64, 10000.0) == (10, 23)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.26080, abs=1e-5)
    assert softmax_scale(PUBLISHED) == pytest.approx(0.114721, abs=1e-6)
    assert softmax_scale(PUBLISHED) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)


def test_published_inv_freq_by_hand():
    i = np.arange(32)
    extrapolated = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)     # 0 up to dim 10, 1 from 23
    want = extrapolated * (1 - ramp) + extrapolated / 40 * ramp
    got = np.asarray(rope_inv_freq(PUBLISHED))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[31] == pytest.approx(
        extrapolated[31] / 40)


def _reduced(**mla_kw):
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False,
                              mla=dataclasses.replace(cfg.mla, **mla_kw))
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(0, cfg.moe.n_experts), dispatch="dropless"))


def _decode_errors(cfg, S=24, S0=8):
    m = make_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    x, _ = m.forward(params, toks)
    full = m._logits(params, x)
    lg, cache = m.prefill(params, toks[:, :S0], max_len=S)
    errs = [float(jnp.abs(lg - full[:, S0 - 1:S0]).max())]
    dec = jax.jit(m.decode_step)
    for t in range(S0, S):
        lg, cache = dec(params, cache, toks[:, t:t + 1],
                        jnp.full((2,), t, jnp.int32))
        errs.append(float(jnp.abs(lg - full[:, t:t + 1]).max()))
    return max(errs), np.asarray(full)


@pytest.mark.parametrize("mode", ["naive", "absorbed"])
def test_decode_paths_agree_with_forward_under_yarn(mode):
    cfg = _reduced(decode_mode=mode)
    assert cfg.mla.yarn is not None and cfg.moe.dispatch == "dropless"
    err, _ = _decode_errors(cfg)
    assert err < 1e-4, (mode, err)


def test_yarn_changes_the_attention():
    _, with_yarn = _decode_errors(_reduced())
    _, plain = _decode_errors(_reduced(yarn=None))
    assert np.abs(with_yarn - plain).max() > 1e-3
