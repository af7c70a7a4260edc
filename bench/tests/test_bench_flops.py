"""Work counts from shapes agree with the program's own parameter trees."""
import json

import pytest

from benchlib import BENCH, harness
import flops


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_stablelm_parameter_count_matches_eval_shape():
    import jax
    import numpy as np

    from repro.models.api import build

    model_config = harness.load_module(BENCH / "drivers" / "dpsgd_lm.py").model_config

    cfg = _config("stablelm-3b-l2")
    api = build(model_config(cfg))
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    counted = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert counted == 416_179_200 == cfg["params_per_node"]
    assert flops.transformer_params(cfg) == pytest.approx(counted, rel=1e-4)
    # the closed form without the LayerNorm scales and biases
    assert counted - flops.transformer_params(cfg, norms=False) == 25_600


def test_stablelm_training_flops_per_token():
    cfg = _config("stablelm-3b-l2")
    per_token = flops.transformer_train_flops_per_token(cfg, 256)
    # 6 x the 287.3 M matmul weights, plus causal attention
    assert flops.transformer_matmul_params(cfg) == 287_375_360
    assert per_token == pytest.approx(6 * 287_375_360 + 3 * 2 * 2 * 128 * 2560 * 2)


def test_cnn_counts():
    from repro.models import cnn

    cfg = _config("cnn-paper")
    assert flops.cnn_params(cfg) == cnn.PARAM_COUNT == cfg["n_params"]
    assert cfg["message_bits"] == cnn.MODEL_BITS
    # 480,500 multiply-accumulates forward: about 2.9 MFLOP per training image
    assert flops.cnn_train_flops_per_sample(cfg) == 6 * 480_500
