"""A latent model's decode program, the reference or the kernel behind it.

A decode op over a latent model's ONE pool carries the pool: each layer
writes its entry into its page and ``paged_latent_decode`` attends the
row's pages to the row's own length (PERF.md section 6, PR 45 and 46). On
a backend that runs the Pallas kernels (the chip; here the hook
``pallas_attention._FORCE_INTERPRET``), over an entry stored at whole lane
tiles, that call is the kernel; everywhere else the jax.numpy reference of
the same promise, which gathers a layer's rows where it attends them.
tests/test_latent_moe.py (under hyper-connections) and
tests/test_latent_share.py (the plain residual path, a share of the
router) run these checks on their own model, weights and reference; the
kernel itself is held in tests/test_paged_gqa_decode.py.
"""
import re

import numpy as np

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import transformer_ops as T

import program_text


def kernel_on(monkeypatch, page_size):
    """The Pallas kernels through the interpreter, a block of any of the
    paged kernels two pages long: a row of a few pages folds several
    blocks, and the interpreter unrolls two page copies a block where the
    chip's 512 positions over pages of 4 would be 128 (a minute of
    lowering a decode program)."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    for block in ("PAGED_BLOCK_KEYS", "PAGED_FLAT_BLOCK_KEYS",
                  "PAGED_LATENT_BLOCK_KEYS"):
        monkeypatch.setattr(pa, block, 2 * page_size)


def rel_l2(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


def check_a_dispatch_in_both_forms(run_op, cfg, prompts, lens, table,
                                   empty_pool, monkeypatch, limit,
                                   steps=4):
    """Rows of unequal length prefilled, then ``steps`` steps of every
    slot behind the reference (``dense``) and behind the kernel
    (``in_place``) (float32: the same sums in another order): the same
    tokens and picks, logits within the comparison's limit, and THE SAME
    POOL: the same positions written (the live rows' steps, nothing else
    off the null page) with the same entries, zeros behind ``entry_dim``
    in both."""
    page_size = empty_pool[0].shape[2]
    pre = run_op(T._block_paged_prefill, Tokens=prompts, Lens=lens,
                 Table=table, Pools=empty_pool)
    live = [r for r in range(len(lens)) if table[r, 0] > 0]
    first = np.where(table[:, 0] > 0, np.asarray(pre["NextTok"]), 0)
    pos = np.where(table[:, 0] > 0, lens, 1).astype(np.int32)
    feeds = dict(steps=steps, Tokens=first, Positions=pos, Table=table,
                 Pools=pre["PoolsOut"])
    shapes = [tuple(p.shape) for p in pre["PoolsOut"]]
    assert not T.decode_in_place("latent", None, shapes)
    dense = run_op(T._block_paged_decode, **feeds)
    with monkeypatch.context() as m:
        kernel_on(m, page_size)
        assert T.decode_in_place("latent", None, shapes)
        in_place = run_op(T._block_paged_decode, **feeds)
    for name in ("OutTokens", "Picks"):
        assert np.array_equal(np.asarray(in_place[name])[live],
                              np.asarray(dense[name])[live]), name
    err = rel_l2(np.asarray(in_place["Logits"])[live],
                 np.asarray(dense["Logits"])[live])
    assert err.max() < limit, err.max()
    assert np.array_equal(np.asarray(in_place["Stats"]),
                          np.asarray(dense["Stats"]))
    was = np.asarray(pre["PoolsOut"][0])[:, 1:]
    a, b = (np.asarray(x["PoolsOut"][0])[:, 1:] for x in (in_place, dense))
    np.testing.assert_allclose(a, b, rtol=limit, atol=limit)
    # [pages, offsets] any layer changed: the live rows' steps
    wrote = {tuple(int(i) for i in at) for at in np.argwhere(
        (a != was).any(axis=(0, 3)))}
    assert wrote == {tuple(int(i) for i in at) for at in np.argwhere(
        (b != was).any(axis=(0, 3)))}
    assert wrote == {(int(table[r, p // page_size]) - 1, p % page_size)
                     for r in live
                     for p in range(pos[r], pos[r] + steps)}
    assert np.abs(a[..., :cfg.entry_dim]).max() > 0
    assert not a[..., cfg.entry_dim:].any()


def check_the_program_holds_no_view(cfg, geometry, monkeypatch):
    """The decode program's text, reference or kernel behind the call: no
    view of the layers, ``[layers, rows, kmax, entry]``, in either.
    Behind the reference a layer's rows as they are gathered and the
    scores over every position; behind the kernel neither, no array with
    a ``kmax`` axis of the pool's entries at all, and ONE instance of the
    kernel a layer body (the leading layer's and the scan's) under the
    absorbed form's scope."""
    rows = geometry["max_batch"]
    kmax = geometry["pages_per_seq"] * geometry["page_size"]
    views = [f"tensor<{n}x{rows}x{kmax}x{cfg.stored_dim}xf32>"
             for n in (1, cfg.n_layers)]
    gathered = f"tensor<{rows}x{kmax}x1x{cfg.stored_dim}xf32>"
    scores = f"tensor<{rows}x1x{cfg.n_heads}x1x{kmax}xf32>"
    for hook in (False, True):
        with monkeypatch.context() as m:
            if hook:
                kernel_on(m, geometry["page_size"])
            programs = cfg.build_paged_programs(**geometry)
            assert programs.decode["in_place"] is hook
            lowered = program_text.lower_bundle(programs.decode, 1)
        text = lowered.as_text()
        assert not [v for v in views if v in text]
        assert (gathered in text, scores in text) == (not hook,) * 2
        if hook:
            wide = re.findall(
                rf"tensor<(?:\d+x)*{kmax}x(?:\d+x)*{cfg.stored_dim}xf32>",
                text)
            assert not wide, sorted(set(wide))
        # the interpreter leaves the kernel's name in its scopes alone
        assert ("mla/absorb/paged_latent_decode"
                in lowered.as_text(debug_info=True)) is hook


def check_an_engines_tokens_and_its_counter(make_engine, cfg,
                                            reference_logits, monkeypatch,
                                            hook, page_size):
    """An engine built where the kernel runs (``hook``) attends every
    dispatch through it, ``decode_in_place_total == decode_batches_total``,
    and one built where it does not counts 0; in both its tokens are the
    reference's, a request alone and the same request co-scheduled, and no
    pool is lost."""
    with monkeypatch.context() as m:
        if hook:
            kernel_on(m, page_size)
        engine = make_engine()
        try:
            assert engine.programs.decode["in_place"] is hook
            engine.warmup()
            rng = np.random.RandomState(17)
            prompts = [rng.randint(0, cfg.vocab_size, n)
                       for n in (5, 21, 13, 8)]
            alone = [engine.generate(p, max_new=6) for p in prompts]
            together = [h.result(120) for h in [
                engine.submit(p, max_new=6) for p in prompts]]
            engine.assert_no_recompiles()
            stats = engine.stats()
        finally:
            engine.close()
    for p, out, again in zip(prompts, alone, together):
        want, _ = reference_logits(np.concatenate([p, out]))
        assert np.array_equal(out, np.argmax(want, -1)[p.size - 1:-1])
        assert np.array_equal(out, again)
    assert stats["decode_batches_total"] > 0
    assert stats["decode_in_place_total"] == (
        stats["decode_batches_total"] if hook else 0)
    assert stats["pools_lost_total"] == 0
