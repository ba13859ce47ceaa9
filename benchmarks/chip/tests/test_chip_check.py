"""The check that decides ``correct``: the float8 control, put in the
program's place, comes out not correct, and so does each fault planted in
the timed path (CPU, smoke size): one token's condition altered where E
makes it, a DiT layer left out of D, a quarter of the pixels altered where
C makes them."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip.tests import smoke
from repro.models import diffusion, pipeline


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
def test_control_fails_the_limits(cell):
    result, _, control = smoke.run(smoke.smoke_cell(cell), control=True)
    assert result["correct"] is True
    assert control["correct"] is False


def _drop_a_layer(orig):
    def forward(cfg, params, latents, t, cond, cond_pooled=None):
        layers = jax.tree_util.tree_map(lambda w: w[:-1], params["layers"])
        return orig(cfg, dict(params, layers=layers), latents, t, cond)
    return forward


def _alter_a_token(orig):
    def encode(cfg, params, tokens):
        out = orig(cfg, params, tokens)
        return out.at[:, 0].set(-out[:, 0])
    return encode


def _alter_pixels(orig):
    def decode(cfg, params, latents, grid):
        img = orig(cfg, params, latents, grid)
        q = img.shape[2] // 4
        return img.at[:, :, :q].set(jnp.tanh(1.5 * img[:, :, :q]))
    return decode


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
@pytest.mark.parametrize("module,name,fault", [
    (diffusion, "forward", _drop_a_layer),
    (pipeline, "encode", _alter_a_token),
    (pipeline, "decode", _alter_pixels),
])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, cell, module, name, fault):
    monkeypatch.setattr(module, name, fault(getattr(module, name)))
    result, _, _ = smoke.run(smoke.smoke_cell(cell))
    assert result["correct"] is False
