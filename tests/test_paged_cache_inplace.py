"""The paged ops keep their KV caches in place.

``_PagedRunner``'s layer scan carries the whole [L, ...] K and V arrays
and updates them where they lie. The form it replaced passed them as the
scan's ``xs`` and rebuilt them as its ``ys``: a fresh buffer a call, so
the decode op copied its whole dense cache on every token (PERF.md
section 6, PR 25). Two guards:

- structure: no cache- or pool-shaped array is an ``xs`` or ``ys`` of a
  scan in any of the four ops' jaxprs, and the compiled module holds no
  whole-cache ``copy`` inside a loop;
- bit parity: the replaced form, frozen below as ``_XsYsRunner``, gives
  the same tokens and the same pools, bit for bit.

A dense-form dispatch writes back the entries it wrote, not its whole
dense view (PERF.md section 6, PR 28). The same two guards: no write
into a pool outside the step loop has an update of the view's size, and
the whole-view scatter, frozen below as ``_WholeViewRunner``, gives the
same tokens and the same pools on every page but the null one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import transformer_ops as T

L, LD = 3, 2                      # target / draft depth
D, NH, NKV, HD, F, V = 32, 4, 2, 8, 64, 50
B, PS, MP, NP = 4, 4, 5, 13       # slots, page size, pages a row, pool
KMAX = PS * MP
ATTRS = dict(n_heads=NH, n_kv_heads=NKV, draft_n_heads=NH,
             draft_n_kv_heads=NKV, rope_base=10000.0, epsilon=1e-5,
             page_size=PS, gamma=3)

# rows of unequal length; row 0 crosses from its 2nd to its 3rd page
# inside a 4-step dispatch (positions 7, 8, 9, 10); row 2 is an inactive
# slot: token 0, position 1, the all-null table
TABLE = np.array([[1, 2, 3, 0, 0], [4, 5, 6, 7, 0], [0, 0, 0, 0, 0],
                  [8, 9, 10, 11, 12]], np.int32)
POS = np.array([7, 13, 1, 2], np.int32)
TOK = np.array([5, 17, 0, 33], np.int32)
PREV = np.array([9, 2, 0, 41], np.int32)
# the same rows with row 3 at the end of its table: its positions run
# past KMAX (18, 19, then 20 and up, which are dropped). Row 1 runs off
# its allocated pages onto a null entry of its table (position 16).
POS_END = np.array([7, 13, 1, 18], np.int32)


class _XsYsRunner(T._PagedRunner):
    """The form this repo ran until PR 25, kept as the plain reference:
    the layer scan takes each layer's cache as ``xs`` and stacks the
    updated caches as ``ys``."""

    def _stack_forward(self, h, k_caches, v_caches, q_pos, t_len,
                       attend_write):
        def layer(h, xs):
            p, kc, vc = xs
            caches = {}

            def attend(q, k, v):
                out, caches["k"], caches["v"] = attend_write(
                    q, k, v, kc, vc)
                return out

            h = T.decoder_block(p, h, n_heads=self.n_heads,
                                n_kv=self.n_kv, base=self.base,
                                eps=self.eps, pos=q_pos, attend_fn=attend,
                                moe_top_k=self.moe_top_k)
            return h, (caches["k"], caches["v"])

        h, (k_caches, v_caches) = jax.lax.scan(
            layer, h, (self.params, k_caches, v_caches))
        return h, k_caches, v_caches

    def forward(self, h, k_pages, v_pages, table, pos0, t_len):
        b, kmax = h.shape[0], table.shape[1] * self.page_size
        q_pos = pos0[:, None] + jnp.arange(t_len, dtype=jnp.int32)[None]

        def attend_write(q, k, v, kp, vp):
            pg = jnp.take_along_axis(table, q_pos // self.page_size, axis=1)
            kp2 = kp.at[pg, q_pos % self.page_size].set(k)
            vp2 = vp.at[pg, q_pos % self.page_size].set(v)
            k_all = kp2[table].reshape(b, kmax, self.n_kv, self.hd)
            v_all = vp2[table].reshape(b, kmax, self.n_kv, self.hd)
            return self._attend_math(q, k_all, v_all, q_pos, t_len), kp2, vp2

        return self._stack_forward(h, k_pages, v_pages, q_pos, t_len,
                                   attend_write)

    def forward_dense(self, h, k_dense, v_dense, pos0, t_len):
        rows = jnp.arange(h.shape[0])
        q_pos = pos0[:, None] + jnp.arange(t_len, dtype=jnp.int32)[None]

        def attend_write(q, k, v, kd, vd):
            kd2 = kd.at[rows[:, None], q_pos].set(k)
            vd2 = vd.at[rows[:, None], q_pos].set(v)
            return self._attend_math(q, kd2, vd2, q_pos, t_len), kd2, vd2

        return self._stack_forward(h, k_dense, v_dense, q_pos, t_len,
                                   attend_write)


class _WholeViewRunner(T._PagedRunner):
    """The write-back this repo ran until PR 28, kept as the plain
    reference: the whole dense view goes back through the table, every
    null-table entry colliding on page 0."""

    def write_back(self, pages, dense, table, pos0, n):
        lyr, b = dense.shape[0], dense.shape[1]
        return pages.at[:, table].set(
            dense.reshape((lyr, b, table.shape[1], self.page_size)
                          + dense.shape[3:]))


def _model(key, n_layers, prefix="", quant=False):
    """One toy model's op inputs: bf16, or int8 with ``<Slot>Scale``."""
    shapes = {"Wq": (D, NH * HD), "Wk": (D, NKV * HD), "Wv": (D, NKV * HD),
              "Wo": (NH * HD, D), "WGate": (D, F), "WUp": (D, F),
              "WDown": (F, D)}
    keys = iter(jax.random.split(key, 16))
    ins = {}
    for slot, (m, n) in shapes.items():
        w = jax.random.normal(next(keys), (n_layers, m, n)) * 0.2
        if quant:
            scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
            ins[prefix + slot] = jnp.round(w / scale).astype(jnp.int8)
            ins[prefix + slot + "Scale"] = scale.astype(jnp.float32)
        else:
            ins[prefix + slot] = w.astype(jnp.bfloat16)
    ones = jnp.ones((n_layers, D), jnp.bfloat16)
    ins[prefix + "AttnNorm"] = ins[prefix + "MlpNorm"] = ones
    ins[prefix + "Emb"] = jax.random.normal(
        next(keys), (V, D)).astype(jnp.bfloat16)
    ins[prefix + "FinalNorm"] = jnp.ones((D,), jnp.bfloat16)
    head = jax.random.normal(next(keys), (D, V)) * 0.2
    if quant:
        hs = jnp.max(jnp.abs(head), axis=0) / 127.0
        ins[prefix + "LmHead"] = jnp.round(head / hs).astype(jnp.int8)
        ins[prefix + "LmHeadScale"] = hs.astype(jnp.float32)
    else:
        ins[prefix + "LmHead"] = head.astype(jnp.bfloat16)
    pools = jax.random.normal(next(keys), (2, n_layers, NP, PS, NKV, HD))
    pages = "DraftKPages DraftVPages" if prefix else "KPages VPages"
    for name, pool in zip(pages.split(), pools.astype(jnp.bfloat16)):
        ins[name] = pool
    return ins


def _case(op_name, steps=4, quant=False, pos=POS):
    """(op, inputs, attrs) of one paged op at the toy shapes."""
    ins = _model(jax.random.PRNGKey(0), L, quant=quant)
    ins["Table"] = jnp.asarray(TABLE)
    attrs = dict(ATTRS, steps=steps)
    if op_name == "llama_paged_decode":
        ins.update(Tokens=jnp.asarray(TOK), Positions=jnp.asarray(pos))
    elif op_name == "llama_paged_spec_step":
        ins.update(_model(jax.random.PRNGKey(1), LD, prefix="Draft",
                          quant=quant))
        ins.update(Tokens=jnp.asarray(TOK), Prev=jnp.asarray(PREV),
                   Positions=jnp.asarray(pos))
    else:
        width = 6                 # a window that crosses a page boundary
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, width), 0, V)
        ins.update(Tokens=toks, Lens=jnp.asarray([6, 3, 1, 5], jnp.int32))
        if op_name == "llama_paged_prefill_chunk":
            ins["Offsets"] = jnp.asarray([3, 10, 0, 6], jnp.int32)
    return getattr(T, "_" + op_name), ins, attrs


def _jit(op, attrs):
    """The op as one jitted function of its inputs."""
    def fn(ins):
        out = op(None, {k: [v] for k, v in ins.items()}, attrs)
        return {k: v[0] for k, v in out.items()}

    return jax.jit(fn)


PAGED_OPS = ("llama_paged_decode", "llama_paged_prefill",
             "llama_paged_prefill_chunk", "llama_paged_spec_step")


def _cache_shapes(ins):
    """Every pool shape among the inputs, and its dense view's."""
    shapes = set()
    for name, x in ins.items():
        if name.endswith("Pages"):
            shapes.add(tuple(x.shape))
            shapes.add((x.shape[0], B, KMAX) + tuple(x.shape[-2:]))
    return shapes


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _scan_cache_use(closed_jaxpr, shapes):
    """(names of scans with a cache as xs or ys, number that carry one)."""
    streamed, carried = [], 0
    for eqn in _scans(closed_jaxpr.jaxpr):
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        xs_ys = eqn.invars[nc + nk:] + eqn.outvars[nk:]
        if any(tuple(v.aval.shape) in shapes for v in xs_ys):
            streamed.append(str(eqn.source_info.name_stack) or "scan")
        carried += any(tuple(v.aval.shape) in shapes
                       for v in eqn.invars[nc:nc + nk])
    return streamed, carried


def _loop_copies(hlo_text, shapes):
    """``copy`` instructions over a cache shape in any computation a
    ``while`` reaches."""
    comps = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \(.*\) -> .*\{\n)",
                          hlo_text):
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if m:
            comps[m.group(1)] = block
    called = re.compile(
        r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
    todo = [n for blk in comps.values()
            for n in re.findall(r"body=%?([\w.\-]+)", blk)]
    reached = set()
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached.add(name)
        todo += called.findall(comps[name])
    dims = {",".join(map(str, s)) for s in shapes}
    found = []
    for name in reached:
        for line in comps[name].splitlines():
            m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                         r"copy\(", line)
            if m and m.group(1) in dims:
                found.append(line.strip()[:120])
    return found


def _structure(op, ins, attrs):
    """(scans streaming a cache, scans carrying one, in-loop copies)."""
    shapes = _cache_shapes(ins)
    fn = _jit(op, attrs)
    streamed, carried = _scan_cache_use(jax.make_jaxpr(fn)(ins), shapes)
    copies = _loop_copies(fn.lower(ins).compile().as_text(), shapes)
    return streamed, carried, copies


@pytest.mark.parametrize("op_name", PAGED_OPS)
def test_caches_are_carried_not_streamed(op_name):
    streamed, carried, copies = _structure(*_case(op_name))
    assert not streamed, (
        f"{op_name}: a scan takes or returns a KV cache as xs/ys, which "
        f"copies the whole cache on every call: {streamed}")
    assert carried, f"{op_name}: no scan carries a KV cache"
    assert not copies, f"{op_name}: whole-cache copy inside a loop: {copies}"


def _latent_case(op_name, steps=4, pos=POS):
    """(op, inputs, attrs) of one block_paged_* op at LATENT_MOE_TINY:
    latent attention over ONE pool, routed experts, four residual
    streams, a leading dense layer before the scan."""
    from paddle_tpu.models.latent_moe import LATENT_MOE_TINY as cfg
    shapes = cfg.param_shapes()
    keys = jax.random.split(jax.random.PRNGKey(3), len(shapes))
    w = {name: (0.2 * jax.random.normal(k, shape)).astype(dt)
         for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items()))}
    ins = {"Emb": w["tok_emb"], "FinalNorm": w["final_norm"],
           "LmHead": w["lm_head"], "Table": jnp.asarray(TABLE)}
    for prefix, scope, n, routed in (
            ("Lead", "lead", cfg.n_dense_layers, False),
            ("", "blocks", cfg.n_layers - cfg.n_dense_layers, True)):
        for slot, (suffix, _, _) in cfg.layer_params(n, routed).items():
            ins[prefix + slot] = w[f"{scope}.{suffix}"]
    ins["Pools"] = jax.random.normal(
        jax.random.PRNGKey(4), (cfg.n_layers, NP, PS, cfg.entry_dim))
    if op_name == "block_paged_decode":
        ins.update(Tokens=jnp.asarray(TOK), Positions=jnp.asarray(pos))
    else:
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, 6), 0,
                                  cfg.vocab_size)
        ins.update(Tokens=toks, Lens=jnp.asarray([6, 3, 1, 5], jnp.int32))
        if op_name == "block_paged_prefill_chunk":
            ins["Offsets"] = jnp.asarray([3, 10, 0, 6], jnp.int32)
    return (getattr(T, "_" + op_name), ins,
            dict(cfg.block_attrs(PS), steps=steps))


@pytest.mark.parametrize("op_name", [
    "block_paged_decode", "block_paged_prefill",
    "block_paged_prefill_chunk"])
def test_latent_cache_is_carried_not_streamed(op_name):
    """The same two guards for the model with one [L, pages, page, 576]-
    kind pool: no pool- or dense-view-shaped xs/ys, no whole-cache copy
    in a loop (the expanded attention's key-block loop among them)."""
    op, ins, attrs = _latent_case(op_name)
    pool = ins["Pools"]
    shapes = {tuple(pool.shape),
              (pool.shape[0], B, KMAX) + tuple(pool.shape[3:])}

    def fn(ins):
        out = op(None, {k: [v] for k, v in ins.items()}, attrs)
        return {k: v[0] for k, v in out.items()}

    fn = jax.jit(fn)
    streamed, carried = _scan_cache_use(jax.make_jaxpr(fn)(ins), shapes)
    copies = _loop_copies(fn.lower(ins).compile().as_text(), shapes)
    assert not streamed, (op_name, streamed)
    assert carried, f"{op_name}: no scan carries the latent cache"
    assert not copies, f"{op_name}: whole-cache copy in a loop: {copies}"
    # nor are the experts' stacks sliced by the scan: they ride whole
    expert_stacks = {tuple(ins[s].shape) for s in T._EXPERT_SLOTS}
    assert not _scan_cache_use(jax.make_jaxpr(fn)(ins), expert_stacks)[0]


def test_structure_check_sees_the_replaced_form(monkeypatch):
    """The detector is not vacuous: the frozen xs/ys form trips both
    halves of it, on this backend too."""
    monkeypatch.setattr(T, "_PagedRunner", _XsYsRunner)
    streamed, _, copies = _structure(*_case("llama_paged_decode"))
    assert streamed and copies


@pytest.mark.parametrize("op_name,steps,quant", [
    ("llama_paged_decode", 1, False), ("llama_paged_decode", 4, False),
    ("llama_paged_decode", 1, True), ("llama_paged_decode", 4, True),
    ("llama_paged_prefill", 1, False), ("llama_paged_prefill", 1, True),
    ("llama_paged_prefill_chunk", 1, False),
    ("llama_paged_spec_step", 1, False)])
def test_bit_parity_with_the_replaced_form(op_name, steps, quant,
                                           monkeypatch):
    op, ins, attrs = _case(op_name, steps=steps, quant=quant)
    new = _jit(op, attrs)(ins)
    # from here ``_make_paged_runner`` builds the frozen reference
    monkeypatch.setattr(T, "_PagedRunner", _XsYsRunner)
    old = _jit(op, attrs)(ins)
    assert sorted(new) == sorted(old)
    for name in sorted(new):
        a, b = np.asarray(new[name]), np.asarray(old[name])
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), (
            f"{op_name} steps={steps} quant={quant}: {name} differs")
    if op_name == "llama_paged_decode":
        assert new["OutTokens"].shape == (B, steps)
        # the dispatch wrote: the pools are not what went in
        assert not np.array_equal(np.asarray(new["KPagesOut"], np.float32),
                                  np.asarray(ins["KPages"], np.float32))


# ---------------------------------------------------------------------
# The write-back of a dense-form dispatch (PR 28)
# ---------------------------------------------------------------------

def _pool_writes(jaxpr, pools):
    """The update shape of every scatter or dynamic-update-slice into a
    pool-shaped array outside any loop: ``jaxpr`` is searched through
    its calls, not into a scan or a while."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("scan", "while"):
            continue
        if (name.startswith("scatter") or name == "dynamic_update_slice") \
                and tuple(eqn.invars[0].aval.shape) in pools:
            update = eqn.invars[2 if name.startswith("scatter") else 1]
            found.append(tuple(update.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pool_writes(sub, pools)
    return found


def _n_written(op_name, steps=4):
    """Positions a row a dense-form dispatch writes."""
    return ATTRS["gamma"] + 1 if op_name == "llama_paged_spec_step" \
        else steps


def _pool_inputs(ins):
    return [k for k in ins if k.endswith("Pages") or k == "Pools"]


def _dense_op_pools(op_name, ins):
    """{pool shape: (entries a dispatch writes, entries of its view)}
    of a decode or speculative op's inputs."""
    n = _n_written(op_name)
    out = {}
    for x in (ins[name] for name in _pool_inputs(ins)):
        entry = int(np.prod(x.shape[3:]))
        out[tuple(x.shape)] = (x.shape[0] * B * n * entry,
                               x.shape[0] * B * KMAX * entry)
    return out


def _write_back_sizes(op, ins, attrs, op_name):
    """(pool writes of the size a dispatch writes, of any other size,
    the pools there are): the sizes are element counts of the update."""
    pools = _dense_op_pools(op_name, ins)
    jaxpr = jax.make_jaxpr(_jit(op, attrs))(ins).jaxpr
    written, other = [], []
    for shape in _pool_writes(jaxpr, set(pools)):
        size = int(np.prod(shape))
        (written if size in {w for w, _ in pools.values()}
         else other).append(shape)
    return written, other, pools


def _dense_case(op_name, **kw):
    if op_name == "block_paged_decode":
        kw.pop("quant", None)
        return _latent_case(op_name, **kw)
    return _case(op_name, **kw)


@pytest.mark.parametrize("op_name", [
    "llama_paged_decode", "llama_paged_spec_step", "block_paged_decode"])
def test_write_back_holds_the_entries_written(op_name):
    """Outside the step loop every write into a pool has an update of
    [L, B, n, *entry], the entries the dispatch wrote: none of the dense
    view's size, and one for each pool."""
    op, ins, attrs = _dense_case(op_name)
    written, other, _ = _write_back_sizes(op, ins, attrs, op_name)
    assert not other, f"{op_name}: a pool write of another size: {other}"
    assert len(written) == len(_pool_inputs(ins)), (op_name, written)


def test_write_back_check_sees_the_whole_view_form(monkeypatch):
    """The detector is not vacuous: the frozen whole-view scatter trips
    it with an update as large as the dense view."""
    monkeypatch.setattr(T, "_PagedRunner", _WholeViewRunner)
    op, ins, attrs = _case("llama_paged_decode")
    written, other, pools = _write_back_sizes(op, ins, attrs,
                                              "llama_paged_decode")
    assert not written
    assert len(other) == 2          # the K pool's and the V pool's
    assert {int(np.prod(s)) for s in other} == {
        view for _, view in pools.values()}


@pytest.mark.parametrize("op_name,steps,quant", [
    ("llama_paged_decode", 1, False), ("llama_paged_decode", 4, False),
    ("llama_paged_decode", 1, True), ("llama_paged_decode", 4, True),
    ("llama_paged_spec_step", 1, False),
    ("llama_paged_spec_step", 1, True),
    ("block_paged_decode", 1, False), ("block_paged_decode", 4, False)])
def test_write_back_parity_with_the_whole_view_scatter(op_name, steps,
                                                       quant, monkeypatch):
    """Tokens (and whatever else the op returns) bit for bit, and every
    pool bit for bit on pages 1 and up. Inside the case: rows of unequal
    length; row 0 crossing a page boundary within the dispatch; row 1
    running onto a null entry of its table; row 2 an inactive slot on
    the all-null table; row 3 running past KMAX, where its writes are
    dropped and do not come back into its last page."""
    op, ins, attrs = _dense_case(op_name, steps=steps, quant=quant,
                                 pos=POS_END)
    new = _jit(op, attrs)(ins)
    monkeypatch.setattr(T, "_PagedRunner", _WholeViewRunner)
    old = _jit(op, attrs)(ins)
    assert sorted(new) == sorted(old)
    for name in sorted(new):
        a, b = np.asarray(new[name]), np.asarray(old[name])
        assert a.dtype == b.dtype and a.shape == b.shape, name
        if name[:-3] in _pool_inputs(ins):      # <pool>Out: pages 1 and up
            a, b = a[:, 1:], b[:, 1:]
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), (
            f"{op_name} steps={steps} quant={quant}: {name} differs")
    n = _n_written(op_name, steps)
    for src in _pool_inputs(ins):
        name = src + "Out"
        got = np.asarray(new[name]).astype(np.float32)
        was = np.asarray(ins[src]).astype(np.float32)
        first = POS_END - src.startswith("Draft")       # draft: pos - 1
        # row 3's last page: the positions inside KMAX are written, the
        # head of the page (where a clamped table lookup would put
        # positions KMAX and up) is as it was
        last = TABLE[3, -1]
        head = first[3] % PS
        assert np.array_equal(got[:, last, :head], was[:, last, :head]), name
        if first[3] + n > KMAX:
            assert not np.array_equal(got[:, last, head:],
                                      was[:, last, head:]), name
        # row 0 wrote on both sides of its page boundary when it crossed
        if first[0] + n > 8:
            for page, off in ((TABLE[0, 1], 3), (TABLE[0, 2], 0)):
                assert not np.array_equal(got[:, page, off],
                                          was[:, page, off]), name
        # pages 1 and up that no live position of the dispatch reaches
        # are as they went in: the inactive slot touched none of them
        touched = {TABLE[r, p // PS] for r in (0, 1, 3)
                   for p in range(first[r], min(first[r] + n, KMAX))}
        for page in set(range(1, NP)) - touched:
            assert np.array_equal(got[:, page], was[:, page]), (name, page)
