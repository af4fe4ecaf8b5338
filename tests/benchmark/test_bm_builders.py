"""What the builders make from a configuration file and a seed."""
import json
import math
import os

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import (LLAMA_TINY,
                                     random_int8_generator_weights)

from benchmark.builders import serve, train
from benchmark.tracing import Tracer

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["mistral-7b-v0.3",
                                  "mistral-7b-v0.3-train-4chip"])
def test_llama_config_carries_the_published_widths(name):
    c = config(name)
    cfg = serve.llama_config(c)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.ffn_hidden,
            cfg.vocab_size) == (4096, 32, 8, 14336, 32768)
    assert cfg.rope_base == 1e6 and cfg.norm_eps == 1e-5
    assert cfg.dtype == "bfloat16" and cfg.moe_experts == 0
    assert cfg.n_layers == c["num_hidden_layers"]
    # no width is ever in `reduced`
    assert set(c["reduced"]) <= {"num_hidden_layers"}
    with pytest.raises(ValueError):
        serve.llama_config(dict(c, head_dim=64))


def test_serving_configuration_is_the_geometry_the_issue_states():
    e = config("mistral-7b-v0.3")["builder"]["engine"]
    assert e == {"quantize": True, "max_batch": 16,
                 "prompt_buckets": [128, 512], "max_new_tokens": 256,
                 "page_size": 16}
    assert config("mistral-7b-v0.3")["num_hidden_layers"] == 32


@pytest.mark.parametrize("quantize", [True, False])
def test_weights_have_the_generator_layouts_names_shapes_and_types(quantize):
    got = serve.make_generator_weights(LLAMA_TINY, 5, quantize)
    if quantize:
        scope = fluid.Scope()
        random_int8_generator_weights(LLAMA_TINY, fluid.Executor(), scope)
        want = {n: v for n, v in scope.vars.items() if v is not None}
        assert set(got) == set(want)
        for n, v in want.items():
            assert got[n].shape == v.shape and got[n].dtype == v.dtype, n
        w = np.asarray(got["blocks.w_up"])
        assert w.min() >= -100 and w.max() <= 100 and w.std() > 50
        assert float(got["blocks.wq@scale"][0, 0, 0]) \
            == pytest.approx(1.6e-4)
    else:
        assert got["blocks.wq"].dtype == np.float32
        assert not any(n.endswith("@scale") for n in got)
        assert abs(float(np.std(np.asarray(got["lm_head"]))) - 0.02) < 2e-3


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 1, 2 ** 31 + 12345])
def test_the_same_seed_gives_the_same_weights_and_any_seed_is_taken(seed):
    a = serve.make_generator_weights(LLAMA_TINY, seed, True)
    b = serve.make_generator_weights(LLAMA_TINY, seed, True)
    c = serve.make_generator_weights(LLAMA_TINY, seed + 1, True)
    assert np.array_equal(a["blocks.wq"], b["blocks.wq"])
    assert not np.array_equal(a["blocks.wq"], c["blocks.wq"])
    assert jax.random.key_data(serve.seed_key(seed)).shape == (2,)


def test_resnet_cell_trains_through_executor_with_fused_steps():
    c = config("resnet50-imagenet")
    assert (c["builder"]["batch"], c["builder"]["repeats"],
            c["builder"]["amp"]) == (256, 4, "O2")
    c.update(image_size=32, num_classes=10)
    c["builder"].update(batch=4, repeats=2, layout="NCHW", amp=None,
                        optimizer={"kind": "momentum", "lr": 0.001,
                                   "momentum": 0.9},
                        first_loss=math.log(10),
                        first_loss_tolerance=3.0)
    system = train.set_up(c, {}, 3)
    try:
        assert system.pe is None and system.items_per_step == 4
        # one step alone (its loss is the one checked), then the fused
        # dispatch twice
        assert len(system.losses) == 3
        assert system.feed["img"].shape == (4, 3, 32, 32)
        run = train.measure(system, {"lead_in_dispatches": 1}, 0.5, 3,
                            Tracer(False))
    finally:
        system.close()
    assert run["steps"] == 2 * run["dispatches"] >= 2
    assert run["window_s"] >= 0.5
    assert run["edges"]["end"]["compiles"] \
        == run["edges"]["start"]["compiles"]
    assert np.isfinite(run["losses"]).all()
    assert not run["problems"], run["problems"]
    # XLA's own account of the step's memory, larger than its state alone
    assert run["step_footprint_bytes"] > 4 * 3 * 32 * 32 * 4
    assert run["untraced"] is None

