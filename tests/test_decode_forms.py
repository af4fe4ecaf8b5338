"""The two forms of a block-kind model's decode program: one Program, two
fetch sets (models/latent_moe.py ``build_block_programs``). The engine's
loop and ``warmup`` dispatch the serving form, which fetches tokens, pools
and counters; ``_run_decode_program`` called from outside gets the probe
form, which also leaves every step's float32 logits and picks under
``kept["decode"]`` and is compiled at its first use. Each of the six
families at its small configuration."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_conv_moe import HYBRID_CONV_TINY
from paddle_tpu.models.hybrid_delta import HYBRID_DELTA_TINY
from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY
from paddle_tpu.models.hybrid_ssm import HYBRID_SSM_TINY
from paddle_tpu.models.latent_moe import LATENT_MOE_TINY
from paddle_tpu.models.looped import LOOPED_TINY
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders.serve_blocks import make_weights

FAMILIES = {"latent_moe": LATENT_MOE_TINY, "hybrid_moe": HYBRID_MOE_TINY,
            "hybrid_ssm": HYBRID_SSM_TINY, "hybrid_delta": HYBRID_DELTA_TINY,
            "hybrid_conv_moe": HYBRID_CONV_TINY, "looped": LOOPED_TINY}
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16), max_new_tokens=8,
              page_size=4, decode_block=2, prefill_batch=1,
              default_timeout_s=120.0)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def engine(request):
    """(the family's small configuration, a warmed engine of it, what its
    executor had compiled when ``warmup`` returned)."""
    cfg = FAMILIES[request.param]
    scope = fluid.Scope()
    for name, value in make_weights(cfg, 3).items():
        scope.set(name, value * (1 if name.endswith("norm") else 10))
    eng = DecodeEngine(cfg, scope=scope, config=DecodeConfig(**ENGINE),
                       auto_start=False)
    eng.warmup()
    yield cfg, eng, eng.exe.total_compiles()
    eng.close()


def _prefilled(cfg, eng):
    """Every row's prompt of seven tokens in pages of its own: (the decode
    program's arrays for the step behind them, the pools as numpy)."""
    rng = np.random.RandomState(5)
    rows = eng.config.max_batch
    helds = [eng._alloc(eng.pages_per_seq) for _ in range(rows)]
    table = np.asarray([h["sequence"] for h in helds], np.int32)
    kinds = eng._kind_tables(helds)
    prompts = rng.randint(0, cfg.vocab_size, (rows, 8)).astype(np.int64)
    first = eng._run_prefill_program(
        8, prompts, np.full((rows,), 7, np.int32), table, *kinds)
    for held in helds:
        eng._free(held)
    arrays = (first.astype(np.int64), np.full((rows,), 7, np.int32), table,
              *kinds)
    return arrays, [np.asarray(p) for p in eng._pools]


def test_a_dispatch_is_the_same_tokens_and_pools_in_either_form(engine):
    cfg, eng, _ = engine
    arrays, pools = _prefilled(cfg, eng)
    eng._pools = [jnp.asarray(p) for p in pools]
    served = eng._run_decode_program(*arrays, loop=True)
    served_pools = [np.asarray(p) for p in eng._pools]
    assert "decode" not in eng.kept
    eng._pools = [jnp.asarray(p) for p in pools]
    probed = eng._run_decode_program(*arrays)
    assert served.shape == (3, 2)
    np.testing.assert_array_equal(served, probed)
    for got, want, was in zip(eng._pools, served_pools, pools):
        assert (want != was).any()                  # the step wrote
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      want.view(np.uint8))
    # and the logits the probe form leaves are the ones the tokens came of
    logits = np.asarray(eng.kept["decode"]["logits"])
    np.testing.assert_array_equal(np.argmax(logits, -1), probed)


def test_the_loop_keeps_no_logits_and_the_probe_form_compiles_when_asked(
        engine):
    cfg, eng, warmed = engine
    eng.start()
    prompt = np.random.RandomState(2).randint(0, cfg.vocab_size, 6)
    want = np.asarray(eng.generate(prompt, max_new=5))
    s = eng.stats()
    assert s["decode_batches_total"] >= 2
    probes = s["decode_probe_dispatches_total"]
    assert "decode" not in eng.kept
    # whatever an earlier test of this engine compiled, the loop added none
    compiles = eng.exe.total_compiles()
    assert compiles - warmed == (probes > 0)
    arrays = (np.zeros((3,), np.int64), np.ones((3,), np.int32),
              np.zeros((3, eng.pages_per_seq), np.int32),
              *eng._kind_tables([None] * 3))
    for n in (1, 2):
        eng._run_decode_program(*arrays)
        assert eng.stats()["decode_probe_dispatches_total"] == probes + n
        assert eng.exe.total_compiles() == warmed + 1   # once, not in warmup
    kept = eng.kept["decode"]
    assert sorted(kept) == ["logits", "picks"]
    assert kept["logits"].shape == (3, 2, cfg.vocab_size)
    assert kept["logits"].dtype == np.float32
    eng.assert_no_recompiles()
    # more serving behind a comparison: the same tokens, nothing compiled,
    # and the loop's next dispatch drops what the probe left
    np.testing.assert_array_equal(eng.generate(prompt, max_new=5), want)
    assert "decode" not in eng.kept
    assert eng.stats()["decode_probe_dispatches_total"] == probes + 2
    assert eng.exe.total_compiles() == warmed + 1
    eng.assert_no_recompiles()
