"""API-surface diff against the reference's __all__ inventories.

The snapshot (tests/reference_api_all.json) was extracted by ast-parsing
the reference's `__all__` lists (paddle, paddle.nn, paddle.nn.functional,
paddle.vision.ops). The done-criterion of API parity: this diff reports
ZERO missing names for every namespace.
"""
import importlib
import json
import os

import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.vision.ops as vops

REF = json.load(open(os.path.join(os.path.dirname(__file__),
                                  "reference_api_all.json")))


@pytest.mark.parametrize("name", sorted(REF))
def test_namespace_complete(name):
    mod = importlib.import_module(name.replace("paddle", "paddle_tpu", 1))
    missing = [x for x in REF[name] if not hasattr(mod, x)]
    assert not missing, f"{name} missing {len(missing)}: {missing}"


def test_no_surviving_not_implemented_stubs():
    """The round-2 'planned' stubs are gone: the once-stubbed names now
    resolve and run (spot checks, cheap shapes)."""
    import numpy as np
    lin = nn.Linear(4, 3)
    nn.utils.weight_norm(lin)
    assert "weight_g" in dict(lin.named_parameters())
    nn.utils.remove_weight_norm(lin)
    assert "weight" in dict(lin.named_parameters())
    lin2 = nn.Linear(4, 3)
    nn.utils.spectral_norm(lin2)
    out = lin2(paddle.to_tensor(np.ones((2, 4), "float32")))
    assert out.shape == [2, 3]
    q = paddle.to_tensor(np.random.rand(4, 3).astype("float32"))
    tau = paddle.to_tensor(np.random.rand(3).astype("float32") * 0.5)
    hp = paddle.linalg.householder_product(q, tau)
    assert hp.shape == [4, 3]
    x = paddle.to_tensor(np.random.rand(1, 2, 6, 6).astype("float32"))
    off = paddle.to_tensor(np.zeros((1, 18, 4, 4), "float32"))
    w = paddle.to_tensor(np.random.rand(2, 2, 3, 3).astype("float32"))
    dc = vops.deform_conv2d(x, off, w)
    assert dc.shape == [1, 2, 4, 4]
