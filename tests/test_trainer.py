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
        """One timeline: a session's own trace (the xplane TensorBoard
        reads; a .trace.json.gz beside it that chrome://tracing and
        Perfetto open — the reference's chrome-trace path,
        python/paddle/fluid/profiler.py:221) holds the record_event
        regions and the executor's dispatches as host spans."""
        import glob
        from jax.profiler import ProfileData
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
        xplanes = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        assert len(xplanes) == 1
        assert glob.glob(str(tmp_path / "**" / "*.trace.json.gz"),
                         recursive=True)
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
                 for plane in ProfileData.from_file(xplanes[0]).planes
                 for line in plane.lines for ev in line.events
                 if ev.name == "feed" or ev.name.startswith("pt:")]
        names = [s[0] for s in spans]
        assert names.count("feed") == 1
        assert names.count("pt:executor/dispatch") == 2
        runs = sorted(s for s in spans if s[0] == "pt:executor/run")
        assert [r[3]["repeats"] for r in runs] == [1, 1]
        assert runs[1][3]["step"] == runs[0][3]["step"] + 1
        for d in (s for s in spans if s[0] == "pt:executor/dispatch"):
            assert any(r[1] <= d[1] and d[2] <= r[2] for r in runs)
        # one clock: the region that came first starts first
        feed_span = next(s for s in spans if s[0] == "feed")
        assert feed_span[2] <= runs[0][1]
        # and no second timeline is written beside the profiler's own
        assert [p.name for p in tmp_path.iterdir()] == ["plugins"]
        fluid.profiler.reset_profiler()
