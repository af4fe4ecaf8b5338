"""What a dispatch of one of a DecodeEngine's programs does with the
pools it is handed, for any model's engine (test_decode_serving.py: the
dense Llama block; test_latent_moe.py: latent attention and routed
experts): the arrays fed are consumed, the engine holds live ones
afterwards, and tokens and pool bytes are those of the same lowered
program under a plain ``jax.jit`` that donates nothing."""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.executor import make_stepped, step_arg
from paddle_tpu.core.lowering import lower_program


def program_arrays(eng, label, rng, vocab):
    """Feeds for one dispatch of the program ``label`` names (all but the
    pools): every row active, on pages of its own."""
    c, pps = eng.config, eng.pages_per_seq
    rows = 1 if "prefill" in label or label == "chunk" else c.max_batch
    table = (1 + np.arange(rows * pps).reshape(rows, pps)).astype(np.int32)
    assert table.max() < eng.allocator.n_pages
    if "prefill" in label or label == "chunk":
        width = eng.programs.chunk_size if label == "chunk" \
            else int(label.rsplit("_", 1)[1])
        tokens = rng.randint(0, vocab, (1, width)).astype(np.int64)
        lens = np.asarray([width - 1], np.int32)
        if label == "chunk":
            return tokens, lens, np.asarray([width], np.int32), table
        return tokens, lens, table
    tokens = rng.randint(0, vocab, (rows,)).astype(np.int64)
    positions = rng.randint(1, c.prompt_buckets[0], (rows,)).astype(np.int32)
    if label == "spec":
        prev = rng.randint(0, vocab, (rows,)).astype(np.int64)
        return tokens, prev, positions, table
    return tokens, positions, table


def check_dispatch_donates(eng, label, vocab, seed=0):
    """One dispatch of ``label`` from pools full of noise; the engine must
    have no worker running. Returns the bundle, its arrays and the pools
    fed (consumed)."""
    rng = np.random.RandomState(seed)
    b = eng._bundles()[label]
    arrays = program_arrays(eng, label, rng, vocab)
    noise = [[rng.standard_normal(p.shape).astype(p.dtype) for p in pools]
             for pools in (eng._pools, eng._draft_pools)]
    eng._pools, eng._draft_pools = (
        [jnp.asarray(x) for x in pools] for pools in noise)
    which = b.get("pools", "target")
    fed = eng._pools_of(b)
    host = ([] if which == "draft" else noise[0]) \
        + ([] if which == "target" else noise[1])

    # the same lowered program, nothing donated
    fetch_names, mode, rw, ro, feed = eng.exe._prepare(
        b["program"], dict(zip(b["feeds"], (*arrays, *host))), b["fetch"],
        eng.scope, "test")
    assert not rw          # the engine's programs write no persistable
    want = jax.jit(make_stepped(lower_program(
        b["program"], fetch_names, mode)))(
            rw, ro, feed, step_arg(1, b["program"].random_seed))[1]

    before = eng.stats()
    head = eng._run_program(label, b, arrays)
    after = eng.stats()
    assert all(p.is_deleted() for p in fed)
    assert after["pools_consumed_total"] \
        == before["pools_consumed_total"] + 1
    assert after["pools_lost_total"] == before["pools_lost_total"]
    live = eng._pools + eng._draft_pools
    assert live and not any(p.is_deleted() for p in live)
    for got, ref in zip(head, want):
        np.testing.assert_array_equal(got, np.asarray(ref))
    for got, ref, was in zip(eng._pools_of(b), want[len(head):], host):
        ref = np.asarray(ref)
        assert (ref != was).any()                  # the dispatch wrote
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint8), ref.view(np.uint8))
    # the pools the program did not take are the arrays they were
    if which == "draft":
        assert not any(p.is_deleted() for p in eng._pools)
    return b, arrays, fed


def aliased_bytes(eng, b, arrays, pools):
    """What XLA says the program's outputs share with its donated pools."""
    return eng.exe.compiled_stats(
        b["program"], feed=dict(zip(b["feeds"], (*arrays, *pools))),
        fetch_list=b["fetch"], scope=eng.scope, mode="test", top_k=0,
        donate_feeds=b["feeds"][len(arrays):])["aliased_bytes"]
