"""Static-analysis subsystem tests: one per diagnostic code, the
inference engine, registry hygiene, executor integration
(PADDLE_TPU_VALIDATE), the lowering error context, the get_var
near-miss KeyError, and the model-zoo sweep (every builder verifies
with zero errors — warnings allowed)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import (VerifyError, VerifyWarning, errors,
                                 infer_program, verify_program)
from paddle_tpu.core import registry
from paddle_tpu.models.zoo import build_zoo_program, zoo_model_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(diags, level=None):
    return [d.code for d in diags if level is None or d.level == level]


# ---------------------------------------------------------------------------
# shape/dtype inference engine
# ---------------------------------------------------------------------------

class TestInference:
    def test_mlp_shapes_propagate(self):
        x = fluid.layers.data(name="x", shape=[784], dtype="float32")
        h = fluid.layers.fc(x, size=128, act="relu")
        p = fluid.layers.fc(h, size=10, act="softmax")
        loss = fluid.layers.mean(p)
        res = infer_program(fluid.default_main_program())
        assert res.info(0, h.name).shape == (-1, 128)
        assert res.info(0, p.name).shape == (-1, 10)
        assert res.info(0, loss.name).shape == (1,)
        assert res.info(0, p.name).dtype == "float32"
        assert res.info(0, p.name).confident

    def test_conv_pool_shapes(self):
        img = fluid.layers.data(name="img", shape=[3, 32, 32],
                                dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=8, filter_size=5,
                                padding=2)
        pl = fluid.layers.pool2d(c, pool_size=2, pool_stride=2)
        res = infer_program(fluid.default_main_program())
        assert res.info(0, c.name).shape == (-1, 8, 32, 32)
        assert res.info(0, pl.name).shape == (-1, 8, 16, 16)

    def test_unknown_op_falls_to_lattice_bottom(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        gb = fluid.default_main_program().global_block()
        out = gb.create_var(name="mystery_out", dtype="float32")
        gb.append_op("warpctc", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]})
        res = infer_program(fluid.default_main_program())
        info = res.info(0, "mystery_out")
        assert info.shape is None and not info.confident

    def test_reshape_infers_minus_one(self):
        a = fluid.layers.data(name="a", shape=[4, 6], dtype="float32",
                              append_batch_size=False)
        r = fluid.layers.reshape(a, shape=[-1, 3])
        res = infer_program(fluid.default_main_program())
        assert res.info(0, r.name).shape == (8, 3)

    def test_grad_vars_take_param_shapes(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        gb = fluid.default_main_program().global_block()
        w = [p.name for p in gb.all_parameters() if p.shape == (8, 1)][0]
        res = infer_program(fluid.default_main_program())
        assert res.info(0, w + "@GRAD").shape == (8, 1)


# ---------------------------------------------------------------------------
# one test per diagnostic code
# ---------------------------------------------------------------------------

class TestDiagnostics:
    def test_use_before_def(self):
        fluid.layers.data(name="x", shape=[8], dtype="float32")
        gb = fluid.default_main_program().global_block()
        gb.append_op("relu", inputs={"X": ["never_defined"]},
                     outputs={"Out": ["r"]})
        diags = fluid.default_main_program().verify()
        assert "use-before-def" in _codes(diags, "error")

    def test_dangling_fetch_with_near_miss_hint(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=4)
        diags = fluid.default_main_program().verify(
            fetch_list=[h.name + "_typo"])
        errs = [d for d in diags if d.code == "dangling-fetch"]
        assert errs and errs[0].level == "error"
        assert h.name in (errs[0].hint or "")

    def test_dangling_feed(self):
        fluid.layers.data(name="unused", shape=[8], dtype="float32")
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        fluid.layers.fc(x, size=4)
        diags = fluid.default_main_program().verify()
        assert "dangling-feed" in _codes(diags, "warning")

    def test_dtype_mismatch(self):
        a = fluid.layers.data(name="a", shape=[8], dtype="float32")
        b = fluid.layers.data(name="b", shape=[8], dtype="int64")
        fluid.layers.elementwise_add(a, b)
        diags = fluid.default_main_program().verify()
        assert "dtype-mismatch" in _codes(diags, "error")

    def test_shape_mismatch_mul(self):
        a = fluid.layers.data(name="a", shape=[4, 6], dtype="float32",
                              append_batch_size=False)
        gb = fluid.default_main_program().global_block()
        w = gb.create_parameter("w_bad", shape=[7, 3])
        out = gb.create_var(name="mm_out", dtype="float32")
        gb.append_op("mul", inputs={"X": [a.name], "Y": [w.name]},
                     outputs={"Out": [out.name]})
        diags = fluid.default_main_program().verify()
        assert "shape-mismatch" in _codes(diags, "error")

    def test_shape_mismatch_reshape(self):
        a = fluid.layers.data(name="a", shape=[4, 6], dtype="float32",
                              append_batch_size=False)
        fluid.layers.reshape(a, shape=[5, 5])
        diags = fluid.default_main_program().verify()
        assert "shape-mismatch" in _codes(diags, "error")

    def test_param_shape_drift(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            fluid.layers.fc(x, size=4)
        sv = next(iter(startup.global_block().vars.values()))
        sv.shape = (7, 7)
        diags = main.verify(startup_program=startup)
        assert "param-shape-drift" in _codes(diags, "error")

    def test_dead_op(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        live = fluid.layers.fc(x, size=4)
        fluid.layers.fc(x, size=2)          # never fetched or consumed
        diags = fluid.default_main_program().verify(
            fetch_list=[live.name])
        assert "dead-op" in _codes(diags, "warning")

    def test_dead_op_silent_without_fetch_list(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        fluid.layers.fc(x, size=4)
        diags = fluid.default_main_program().verify()
        assert "dead-op" not in _codes(diags)

    def test_grad_name_mismatch(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.append_backward(loss)
        gb = fluid.default_main_program().global_block()
        bwd = [op for op in gb.ops if op.type == "backward"][0]
        bwd.attrs["parameter_names"] = \
            list(bwd.attrs["parameter_names"]) + ["ghost_param"]
        diags = fluid.default_main_program().verify()
        assert "grad-name-mismatch" in _codes(diags, "error")

    def test_grad_var_missing(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.append_backward(loss)
        gb = fluid.default_main_program().global_block()
        gname = [n for n in gb.vars if n.endswith("@GRAD")][0]
        del gb.vars[gname]
        diags = fluid.default_main_program().verify()
        msgs = [d for d in diags if d.code == "grad-name-mismatch"
                and d.level == "error"]
        assert any(gname in d.message for d in msgs)

    def test_donation_alias(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=8)
        gb = fluid.default_main_program().global_block()
        gb.append_op("relu", inputs={"X": [h.name]},
                     outputs={"Out": [x.name]})   # writes the feed var
        diags = fluid.default_main_program().verify()
        assert "donation-alias" in _codes(diags, "warning")

    def test_no_lowering_rule(self):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        gb = fluid.default_main_program().global_block()
        gb.append_op("totally_made_up_op", inputs={"X": [x.name]},
                     outputs={"Out": ["o"]})
        diags = fluid.default_main_program().verify()
        assert "no-lowering-rule" in _codes(diags, "error")

    def test_tpu_pad_lint(self):
        x = fluid.layers.data(name="x", shape=[100], dtype="float32")
        fluid.layers.fc(x, size=7)
        diags = fluid.default_main_program().verify()
        assert "tpu-pad" in _codes(diags, "warning")

    def test_tpu_pad_silent_when_aligned(self):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        fluid.layers.fc(x, size=128, bias_attr=False)
        diags = fluid.default_main_program().verify()
        assert "tpu-pad" not in _codes(diags)

    def test_recompile_hazard(self):
        fluid.layers.data(name="ragged", shape=[-1, -1, 8],
                          dtype="float32", append_batch_size=False)
        diags = fluid.default_main_program().verify()
        assert "recompile-hazard" in _codes(diags, "warning")


# ---------------------------------------------------------------------------
# registry hygiene (satellite)
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_duplicate_lowering_registration_rejected(self):
        with pytest.raises(ValueError, match="registered twice"):
            @registry.register_op("relu")
            def shadow(ctx, ins, attrs):
                return {}

    def test_duplicate_infer_registration_rejected(self):
        with pytest.raises(ValueError, match="registered twice"):
            @registry.register_infer("relu")
            def shadow(op, ins, attrs):
                return {}

    def test_registered_op_types_accessor(self):
        types = registry.registered_op_types()
        assert "mul" in types and "conv2d" in types
        assert types == sorted(types)
        assert types == registry.registered_ops()


# ---------------------------------------------------------------------------
# executor integration (tentpole integration layer)
# ---------------------------------------------------------------------------

class TestExecutorValidation:
    def _corrupt_program(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            fluid.layers.fc(x, size=4)
        return main

    def test_strict_env_raises_before_lowering(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_VALIDATE", "strict")
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(VerifyError):
            exe.run(self._corrupt_program(),
                    feed={"x": np.zeros((2, 8), np.float32)},
                    fetch_list=["not_produced"])

    def test_strict_arg_overrides_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_VALIDATE", "0")
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(VerifyError):
            exe.run(self._corrupt_program(),
                    feed={"x": np.zeros((2, 8), np.float32)},
                    fetch_list=["not_produced"], validate="strict")

    def test_default_mode_warns_not_raises(self):
        # the same corrupted fetch dies inside lowering, but the cheap
        # validator must have surfaced a VerifyWarning FIRST, not
        # raised
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.warns(VerifyWarning):
            with pytest.raises(Exception):
                exe.run(self._corrupt_program(),
                        feed={"x": np.zeros((2, 8), np.float32)},
                        fetch_list=["not_produced"])

    def test_validation_cached_per_program_version(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(x, size=4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.zeros((2, 8), np.float32)}
        exe.run(main, feed=feed, fetch_list=[h])
        n = len(exe._validated)
        exe.run(main, feed=feed, fetch_list=[h])
        assert len(exe._validated) == n   # second run: cache hit

    def test_strict_passes_clean_program(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_VALIDATE", "strict")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(x, size=4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                      fetch_list=[h])
        assert out[0].shape == (2, 4)


# ---------------------------------------------------------------------------
# lowering error context (satellite)
# ---------------------------------------------------------------------------

def said(e):
    """An exception's message and its notes: ``core/lowering.py`` attaches
    the op's context with ``add_note`` (every Python since 3.11), which a
    traceback prints and ``str(e)`` does not."""
    return "\n".join([str(e), *getattr(e, "__notes__", ())])


class TestLoweringErrorContext:
    def test_failure_names_op_and_wiring(self):
        a = fluid.layers.data(name="a", shape=[4, 6], dtype="float32",
                              append_batch_size=False)
        r = fluid.layers.reshape(a, shape=[5, 5])
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(Exception) as ei:
            exe.run(feed={"a": np.zeros((4, 6), np.float32)},
                    fetch_list=[r], validate="0")
        msg = said(ei.value)
        assert "while lowering op 'reshape'" in msg
        assert "block 0" in msg and a.name in msg

    def test_exception_type_preserved(self):
        x = fluid.layers.data(name="x", shape=[2, 3], dtype="float32",
                              append_batch_size=False)
        out = fluid.default_main_program().global_block().create_var(
            name="t_out", dtype="float32")
        fluid.default_main_program().global_block().append_op(
            "transpose", inputs={"X": [x.name]},
            outputs={"Out": [out.name]}, attrs={"axis": [0, 1, 2, 3]})
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(Exception) as ei:
            exe.run(feed={"x": np.zeros((2, 3), np.float32)},
                    fetch_list=[out.name], validate="0")
        assert not isinstance(ei.value, (SystemExit, KeyboardInterrupt))
        assert "while lowering op 'transpose'" in said(ei.value)


# ---------------------------------------------------------------------------
# get_var near-miss (satellite)
# ---------------------------------------------------------------------------

class TestGetVar:
    def test_miss_names_program_and_near_misses(self):
        fluid.layers.data(name="images", shape=[8], dtype="float32")
        with pytest.raises(KeyError) as ei:
            fluid.get_var("imags")
        msg = str(ei.value)
        assert "images" in msg           # near-miss listed
        assert "uid=" in msg             # program named

    def test_hit_still_works(self):
        v = fluid.layers.data(name="xyz", shape=[8], dtype="float32")
        assert fluid.get_var("xyz") is v


# ---------------------------------------------------------------------------
# model-zoo sweep — tier-1 (fast, CPU-only, no jit)
# ---------------------------------------------------------------------------

@pytest.mark.analysis
@pytest.mark.parametrize("name", zoo_model_names())
def test_zoo_model_verifies_clean(name, monkeypatch):
    """Every model in the zoo builds a program that passes
    Program.verify() with zero errors (warnings allowed) — and the
    analysis provably never traces or compiles: jax.jit is booby-
    trapped for the duration of the verify."""
    import jax
    zp = build_zoo_program(name)

    def no_jit(*a, **k):
        raise AssertionError("analysis code invoked jax.jit")

    monkeypatch.setattr(jax, "jit", no_jit)
    diags = verify_program(zp.main, startup=zp.startup,
                           fetch_list=zp.fetch_list,
                           feed_names=zp.feed_names, level="full")
    errs = errors(diags)
    assert not errs, "\n".join(d.format() for d in errs)
    assert "pass-crashed" not in _codes(diags)


@pytest.mark.analysis
def test_fluidlint_cli_mnist_exits_zero():
    """Acceptance: `python tools/fluidlint.py --model mnist` exits 0
    with zero error-level diagnostics (JSON output checked)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fluidlint.py"),
         "--model", "mnist", "--json"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    import json
    doc = json.loads(out.stdout)
    assert doc["n_errors"] == 0


@pytest.mark.analysis
def test_fluidlint_cli_fails_on_corrupt_program(tmp_path):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        fluid.layers.fc(x, size=4)
    path = tmp_path / "prog.json"
    path.write_text(main.to_json())
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fluidlint.py"),
         "--program", str(path), "--fetch", "nonexistent", "--json"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 1, out.stdout + out.stderr
    import json
    doc = json.loads(out.stdout)
    assert "dangling-fetch" in doc["codes"]
