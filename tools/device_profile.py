"""Per-kernel DEVICE-TIME profile of the ResNet-50 bench step (VERDICT
r4 task 3): wrap one measured dispatch in jax.profiler.trace, parse the
xplane proto, and print the top kernels by actual device duration.

Every prior perf argument leaned on compiled_stats' bytes/flops
ESTIMATES; this is the reference device_tracer's role
(/root/reference/paddle/fluid/platform/device_tracer.cc — CUPTI
activity records → per-op device spans) done the XLA way.

If the backend returns no device trace data, the script prints the
planes it DID get and exits 3.

Run on the chip:  python tools/device_profile.py [model] [batch]
(model: resnet50 | vgg16). Prints one JSON line: {"planes": [...],
"top_kernels_by_time": [{name, total_ms, count}...], "step_ms": ...}.
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.transpiler import amp_transpile

    on_tpu = jax.default_backend() == "tpu"
    layout = "NHWC" if on_tpu else "NCHW"
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        if model == "vgg16":
            from paddle_tpu.models.vgg import vgg16
            avg_cost, _, _ = vgg16(img, label, layout=layout)
        else:
            from paddle_tpu.models.resnet import resnet50
            avg_cost, _, _ = resnet50(img, label, layout=layout)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(avg_cost)
    if on_tpu:
        amp_transpile(main_p, level="O2")

    exe = fluid.Executor()
    scope = fluid.Scope()
    reps = 8 if on_tpu else 1
    trace_dir = "/tmp/ptpu_device_trace"
    import shutil
    shutil.rmtree(trace_dir, ignore_errors=True)
    # racecheck: ok(global-mutation) — single-process profiling
    # entrypoint: owns the whole process, no serving threads exist
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        rng = np.random.RandomState(0)
        feed = {"img": jax.device_put(
                    rng.rand(batch, 3, 224, 224).astype(np.float32)),
                "label": jax.device_put(
                    rng.randint(0, 1000, (batch, 1)).astype(np.int64))}
        # warm: compile happens OUTSIDE the trace
        # racecheck: ok(run-without-scope) — scope_guard above binds a
        # private Scope; single-threaded profiler, nothing to race
        exe.run(main_p, feed=feed, fetch_list=[avg_cost], repeats=reps)
        exe.run(main_p, feed=feed, fetch_list=[avg_cost], repeats=reps)
        import time
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        # racecheck: ok(run-without-scope) — same private scope_guard
        out = exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                      repeats=reps)
        step_ms = (time.perf_counter() - t0) * 1e3 / reps
        jax.profiler.stop_trace()
        assert np.isfinite(float(np.asarray(out[0]).reshape(())))

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        print(json.dumps({"error": "no xplane.pb produced",
                          "trace_dir": trace_dir}))
        sys.exit(3)
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(paths[0], "rb") as f:
        space.ParseFromString(f.read())

    planes = [p.name for p in space.planes]
    device_planes = [p for p in space.planes
                     if "TPU" in p.name or "device" in p.name.lower()]
    kernels = {}
    for plane in device_planes:
        # XPlane: event_metadata id -> name; events carry duration_ps
        meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
        for line in plane.lines:
            for ev in line.events:
                name = meta.get(ev.metadata_id, str(ev.metadata_id))
                ms = ev.duration_ps / 1e9
                agg = kernels.setdefault(name, [0.0, 0])
                agg[0] += ms
                agg[1] += 1
    top = sorted(kernels.items(), key=lambda kv: -kv[1][0])[:25]
    rec = {
        "model": model, "batch": batch, "repeats": reps,
        "backend": jax.default_backend(),
        "host_step_ms": round(step_ms, 2),
        "planes": planes,
        "n_device_kernels": len(kernels),
        "top_kernels_by_time": [
            {"name": n[:120], "total_ms": round(t, 3), "count": c}
            for n, (t, c) in top],
    }
    print(json.dumps(rec))
    if not kernels:
        sys.exit(3)


if __name__ == "__main__":
    main()
