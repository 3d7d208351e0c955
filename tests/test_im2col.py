"""``core.graph.im2col``: exact against the index-array gather formulation,
shared by the QAT model and the graph executor, and free of HLO gathers
(which the TPU compiler expands into ``while`` loops of dynamic slices).
The v5e-compiled form is checked in ``tests/test_tpu_compile.py``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Node, _ex_im2col, im2col
from repro.models import resnet9


def _gather_im2col(x, k, s, p):
    n, h, w, c = x.shape
    xp = np.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    oh, ow = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
    idx_h = (np.arange(oh) * s)[:, None] + np.arange(k)[None, :]
    idx_w = (np.arange(ow) * s)[:, None] + np.arange(k)[None, :]
    patches = xp[:, idx_h][:, :, :, idx_w].transpose(0, 1, 3, 2, 4, 5)
    return patches.reshape(n, oh, ow, k * k * c)


@pytest.mark.parametrize("hw", [4, 5, 7, 32])
@pytest.mark.parametrize("pad", [0, 1])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("dtype", ["int32", "int8", "float32"])
def test_im2col_equals_gather_formulation(dtype, k, stride, pad, hw):
    rng = np.random.default_rng(0)
    # non-square, odd channel count: a transposed axis would show
    x = rng.integers(-128, 128, (2, hw, hw + 1, 3)).astype(dtype)
    want = _gather_im2col(x, k, stride, pad)
    node = Node("im2col", ["x"], ["y"],
                dict(kernel=k, stride=stride, pad=pad))
    got = np.asarray(_ex_im2col(node, jnp.asarray(x)))
    assert got.dtype == want.dtype and np.array_equal(got, want)
    if (k, stride, pad) == (3, 1, 1):
        # the QAT model's convs run the executor's own function
        assert resnet9.im2col is im2col
        assert np.array_equal(np.asarray(resnet9.im2col(jnp.asarray(x))),
                              got)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("shape", [(64, 32, 32, 64), (64, 8, 8, 256)],
                         ids=["c1-bucket64", "c3-bucket64"])
def test_im2col_lowers_without_gather(shape):
    node = Node("im2col", ["x"], ["y"], dict(kernel=3, stride=1, pad=1))
    x = jax.ShapeDtypeStruct(shape, jnp.int32)
    assert "gather" not in _hlo(lambda a: _ex_im2col(node, a), x)


def test_resnet9_forward_lowers_without_gather():
    params = resnet9.init_params(jax.random.PRNGKey(0), 4)
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    hlo = _hlo(lambda a: resnet9.forward(params, a, width=4), x)
    assert "gather" not in hlo and "dot_general" in hlo
