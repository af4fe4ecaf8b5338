"""What storing a latent cache entry at whole lane tiles must leave as it
was, for either residual kind (test_latent_moe.py: hyper-connections;
test_latent_share.py: plain): the same paged programs over a pool as wide
as the entry and over one padded to ``stored_dim`` give bitwise the same
logits, picks and tokens, the pools agree in the entry's columns, and the
pad columns hold zeros on every page, the null page included."""
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import transformer_ops as T


def through_pages(run_op, cfg, width, form, page_size, pages_per_seq):
    """A 19-token prompt into pages ``width`` wide, whole or in chunks of
    8, then 4 decode steps: every output of both programs."""
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, 19)
    table = np.zeros((1, pages_per_seq), np.int32)
    table[0, :6] = 1 + np.arange(6)
    pools = [jnp.zeros((cfg.n_layers, 12, page_size, width), jnp.float32)]
    kept = {}
    if form == "whole":
        tokens = np.zeros((1, 24), np.int64)
        tokens[0, :19] = prompt
        out = run_op(T._block_paged_prefill, Tokens=tokens,
                     Lens=np.asarray([19], np.int32), Table=table,
                     Pools=pools)
        kept["prefill"] = out
    for off in range(0, 19, 8) if form == "chunked" else ():
        sl = prompt[off:off + 8]
        tokens = np.zeros((1, 8), np.int64)
        tokens[0, :sl.size] = sl
        out = run_op(T._block_paged_prefill_chunk, Tokens=tokens,
                     Lens=np.asarray([sl.size], np.int32),
                     Offsets=np.asarray([off], np.int32), Table=table,
                     Pools=pools)
        pools = out["PoolsOut"]
        kept[f"chunk_{off}"] = out
    kept["decode"] = run_op(
        T._block_paged_decode, steps=4, Tokens=np.asarray(out["NextTok"]),
        Positions=np.asarray([19], np.int32), Table=table,
        Pools=out["PoolsOut"])
    return kept


def check_padding_changes_no_bit(run_op, cfg, form, page_size,
                                 pages_per_seq):
    assert cfg.stored_dim > cfg.entry_dim
    plain, padded = (through_pages(run_op, cfg, width, form, page_size,
                                   pages_per_seq)
                     for width in (cfg.entry_dim, cfg.stored_dim))
    assert list(plain) == list(padded)
    for label, want in plain.items():
        got = padded[label]
        for slot in sorted(set(want) - {"PoolsOut"}):
            assert np.array_equal(np.asarray(got[slot]),
                                  np.asarray(want[slot])), (label, slot)
        stored, = got["PoolsOut"]
        assert stored.shape[-1] == cfg.stored_dim
        assert np.array_equal(np.asarray(stored)[..., :cfg.entry_dim],
                              np.asarray(want["PoolsOut"][0])), label
        assert not np.asarray(stored)[..., cfg.entry_dim:].any(), label
    # the run wrote: 19 prompt positions and 4 decoded ones, six pages
    assert np.asarray(stored)[:, 1:7, :, :cfg.entry_dim].any()


def check_engines_agree(make_engine, monkeypatch, cfg, page_size):
    """The engine as ``make_engine`` builds it (``padded``) against one
    built with the lane tile patched to 1, its pool as wide as the entry
    (``plain``; no switch selects that: the patch is the test's): the
    same tokens and, through the path the chip comparison takes
    (benchmark/builders/serve_blocks.py engine_logits), bitwise the same
    logits and picks; each engine counts the bytes ITS pages store; and a
    handoff blob of the one is refused by the other, by the shapes."""
    padded = make_engine()
    with monkeypatch.context() as m:
        m.setattr(T, "_LANE_TILE", 1)
        assert cfg.stored_dim == cfg.entry_dim
        plain = make_engine()
    try:
        _compare_engines(plain, padded, cfg, page_size)
    finally:
        plain.close()
        padded.close()


def _compare_engines(plain, padded, cfg, page_size):
    from benchmark.builders import serve_blocks
    from paddle_tpu.serving.batching import ServingError
    from paddle_tpu.serving.kv_pages import PageAllocator

    widths = {plain: cfg.entry_dim, padded: cfg.stored_dim}
    prompts = [np.random.RandomState(11).randint(0, cfg.vocab_size, n)
               for n in (21, 6)]
    tokens, held = {}, {}
    for eng, width in widths.items():
        pool, = eng._pools
        assert pool.shape == (cfg.n_layers, eng.allocator.n_pages,
                              page_size, width)
        page = cfg.n_layers * page_size * width * pool.dtype.itemsize
        assert eng._page_bytes[PageAllocator.SEQUENCE] == page
        before = eng.stats()
        tokens[eng] = [eng.generate(p, max_new=6) for p in prompts]
        after = eng.stats()
        held[eng] = after["cache_bytes_held_total"] \
            - before["cache_bytes_held_total"]
        # pages whole, once a decode dispatch: a multiple of a page
        assert held[eng] > 0 and held[eng] % page == 0
    for a, b in zip(tokens[plain], tokens[padded]):
        assert np.array_equal(a, b)
    # the same pages through the same dispatches, each at its own width
    assert held[plain] * cfg.stored_dim == held[padded] * cfg.entry_dim
    stored, lean = (np.asarray(eng._pools[0]) for eng in (padded, plain))
    assert lean[:, 1:].any()
    assert np.array_equal(stored[:, 1:, :, :cfg.entry_dim], lean[:, 1:])
    assert not stored[..., cfg.entry_dim:].any()

    blob = plain.submit(prompts[0], max_new=5, prefill_only=True).result(60)
    assert blob["cache"][0].shape[2:] == (page_size, cfg.entry_dim)
    try:
        padded.import_handoff(blob)
    except ServingError as e:
        assert "cache entries" in str(e) and str(cfg.stored_dim) in str(e)
    else:
        raise AssertionError("a blob 24 wide went into pools 128 wide")

    probes = {}
    for eng in widths:
        eng.close()
        probes[eng] = serve_blocks.engine_logits(eng, prompts[0], 8)
    for got, want in zip(probes[padded], probes[plain]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
