"""Input-pipeline microbench: native C++ FixedBatcher vs the python
reader-decorator path on the same recordio bytes. Prints one JSON line
per pipeline; run anywhere (no TPU needed)."""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"      # host-side tool

import numpy as np                                         # noqa: E402


def main(n_samples=20000, batch=128, img_elems=3072):
    from paddle_tpu.io import recordio
    from paddle_tpu.io.batcher import FixedBatcher, write_fixed
    from paddle_tpu import reader as rdr

    specs = [((img_elems,), "float32"), ((1,), "int64")]
    rng = np.random.RandomState(0)
    samples = [(rng.randn(img_elems).astype(np.float32),
                np.array([i % 10], np.int64)) for i in range(512)]

    tmp = tempfile.mkdtemp()
    fixed_path = os.path.join(tmp, "fixed.rec")
    npy_path = os.path.join(tmp, "npy.rec")
    write_fixed(fixed_path, (samples[i % 512] for i in range(n_samples)),
                specs)
    recordio.write_arrays(npy_path,
                          (samples[i % 512] for i in range(n_samples)))

    t0 = time.perf_counter()
    n = 0
    for imgs, labels in FixedBatcher(fixed_path, specs, batch,
                                     shuffle_buf=4 * batch, n_threads=2):
        n += len(imgs)
    dt_native = time.perf_counter() - t0

    # sharded: one worker thread per file
    shard_paths = [os.path.join(tmp, f"shard-{i}.rec") for i in range(4)]
    per = n_samples // 4
    for i, sp in enumerate(shard_paths):
        write_fixed(sp, (samples[j % 512]
                         for j in range(i * per, (i + 1) * per)), specs)
    t2 = time.perf_counter()
    k = 0
    for imgs, labels in FixedBatcher(shard_paths, specs, batch,
                                     shuffle_buf=4 * batch, n_threads=4):
        k += len(imgs)
    dt_sharded = time.perf_counter() - t2
    assert k == per * 4

    t1 = time.perf_counter()
    m = 0
    batched = rdr.batch(rdr.shuffle(recordio.array_reader(npy_path),
                                    4 * batch), batch)
    for rows in batched():
        imgs = np.stack([r[0] for r in rows])
        labels = np.stack([r[1] for r in rows])
        m += len(imgs)
    dt_python = time.perf_counter() - t1

    assert n == m == n_samples, (n, m)
    for name, dt, cnt in (("native_fixed_batcher", dt_native, n),
                          ("native_fixed_batcher_4shards", dt_sharded,
                           per * 4),
                          ("python_reader_decorators", dt_python, m)):
        print(json.dumps({
            "metric": f"{name}_samples_per_sec",
            "value": round(cnt / dt, 1),
            "unit": "samples/sec",
            "mb_per_sec": round(cnt * (img_elems * 4 + 8)
                                / dt / 1e6, 1)}))
    print(json.dumps({"metric": "native_vs_python_speedup",
                      "value": round(dt_python / dt_native, 2),
                      "sharded": round(dt_python * per * 4
                                       / (n_samples * dt_sharded), 2),
                      "unit": "x"}))


if __name__ == "__main__":
    main()
