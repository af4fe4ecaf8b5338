"""Trainer / Inferencer / profiler / WeightedAverage (reference
python/paddle/fluid/{trainer,inferencer,profiler,average}.py)."""
import numpy as np

import paddle_tpu as fluid


def _batch_reader(n_batches=8, batch_size=32):
    def reader():
        rng = np.random.RandomState(0)
        centers = np.eye(4, 16, dtype=np.float32) * 4.0
        for _ in range(n_batches):
            labels = rng.randint(0, 4, size=(batch_size,))
            xs = centers[labels] + rng.normal(
                scale=0.5, size=(batch_size, 16)).astype(np.float32)
            yield [(xs[i], np.array([labels[i]], dtype=np.int64))
                   for i in range(batch_size)]
    return reader


def _train_func():
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    logits = fluid.layers.fc(input=x, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    acc = fluid.layers.accuracy(fluid.layers.softmax(logits), label)
    return [loss, acc]


def _optimizer_func():
    return fluid.optimizer.Adam(learning_rate=0.05)


class TestTrainer:
    def test_train_loss_drops_and_events_fire(self):
        events = []
        losses = []

        def handler(event):
            events.append(type(event).__name__)
            if isinstance(event, fluid.EndStepEvent):
                losses.append(float(np.ravel(event.metrics[0])[0]))

        trainer = fluid.Trainer(_train_func, _optimizer_func,
                                place=fluid.CPUPlace())
        trainer.train(num_epochs=2, event_handler=handler,
                      reader=_batch_reader(), feed_order=["x", "label"])

        assert events[0] == "BeginEpochEvent"
        assert events[-1] == "EndEpochEvent"
        assert "BeginStepEvent" in events and "EndStepEvent" in events
        assert losses[-1] < losses[0]

    def test_test_and_save_params_then_infer(self, tmp_path):
        trainer = fluid.Trainer(_train_func, _optimizer_func,
                                place=fluid.CPUPlace())
        trainer.train(num_epochs=2, event_handler=lambda e: None,
                      reader=_batch_reader())
        loss, acc = trainer.test(reader=_batch_reader(n_batches=2))
        assert acc > 0.5

        path = str(tmp_path / "params")
        trainer.save_params(path)

        def _infer_func():
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            return fluid.layers.softmax(fluid.layers.fc(input=x, size=4))

        inferencer = fluid.Inferencer(_infer_func, path,
                                      place=fluid.CPUPlace())
        xs = np.eye(4, 16, dtype=np.float32) * 4.0
        [probs] = inferencer.infer({"x": xs})
        assert probs.shape == (4, 4)
        assert np.array_equal(np.argmax(probs, axis=1), np.arange(4))

    def test_stop_and_checkpoint(self, tmp_path):
        cfg = fluid.CheckpointConfig(checkpoint_dir=str(tmp_path / "ck"),
                                     max_num_checkpoints=2, step_interval=2)

        def handler(event):
            if isinstance(event, fluid.EndStepEvent) and event.step >= 3:
                trainer.stop()

        trainer = fluid.Trainer(_train_func, _optimizer_func,
                                place=fluid.CPUPlace(),
                                checkpoint_config=cfg)
        trainer.train(num_epochs=5, event_handler=handler,
                      reader=_batch_reader())
        import os
        cks = [d for d in os.listdir(cfg.checkpoint_dir)
               if d.startswith("ckpt_")]
        assert 1 <= len(cks) <= 2


class TestProfilerAverage:
    def test_weighted_average(self):
        wa = fluid.average.WeightedAverage()
        wa.add(1.0, 1.0)
        wa.add(3.0, 3.0)
        assert abs(wa.eval() - 2.5) < 1e-9
        wa.reset()
        import pytest
        with pytest.raises(ValueError):
            wa.eval()

    def test_profiler_context(self, capsys):
        with fluid.profiler.profiler("All", sorted_key="total"):
            with fluid.profiler.record_event("step"):
                pass
        out = capsys.readouterr().out
        assert "Event" in out and "step" in out
        fluid.profiler.reset_profiler()

    def test_profiler_chrome_trace_export(self, tmp_path, capsys):
        """The host timeline (executor dispatches + record_event
        regions) exports as chrome://tracing JSON — the reference's
        chrome-trace path (python/paddle/fluid/profiler.py:221)."""
        import json
        fluid.profiler.reset_profiler()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            y = fluid.layers.fc(x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            with fluid.profiler.profiler(
                    "All", profile_path=str(tmp_path)):
                with fluid.profiler.record_event("feed"):
                    feed = {"x": np.ones((2, 4), np.float32)}
                exe.run(main, feed=feed, fetch_list=[y])
                exe.run(main, feed=feed, fetch_list=[y])
        capsys.readouterr()
        trace = json.load(open(tmp_path / "host_timeline.json"))
        evs = trace["traceEvents"]
        names = [e["name"] for e in evs]
        assert "feed" in names
        assert sum(n.startswith("dispatch step") for n in names) >= 2
        for e in evs:   # chrome tracing spec essentials
            assert e["ph"] == "X" and "ts" in e and "dur" in e
        # ts are EPOCH-anchored microseconds (not raw perf_counter,
        # whose origin is arbitrary per process): timelines from
        # different processes must share a timebase
        import time
        now_us = time.time_ns() / 1e3
        assert all(abs(e["ts"] - now_us) < 3600e6 for e in evs), (
            evs[0]["ts"], now_us)
        fluid.profiler.reset_profiler()

    def test_device_kernel_profile(self, tmp_path):
        """device_kernel_profile (the reference device_tracer's role,
        paddle/fluid/platform/device_tracer.cc): no trace dir -> None;
        a trace written by the profiler session parses without error —
        on the CPU backend there may be no device plane, which must
        report gracefully, not crash. (The TPU path is exercised by
        tools/device_profile.py on the real chip.)"""
        assert fluid.profiler.device_kernel_profile(
            str(tmp_path / "missing")) is None
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [64], dtype="float32")
            y = fluid.layers.fc(x, size=32)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            with fluid.profiler.profiler(
                    "All", profile_path=str(tmp_path)):
                exe.run(main, feed={"x": np.ones((8, 64), np.float32)},
                        fetch_list=[y])
        r = fluid.profiler.device_kernel_profile(str(tmp_path))
        if r is not None:               # trace captured: sane shape
            assert set(r) == {"planes", "device_total_ms",
                              "n_kernels", "top_kernels"}
            assert isinstance(r["planes"], list)
        fluid.profiler.reset_profiler()
