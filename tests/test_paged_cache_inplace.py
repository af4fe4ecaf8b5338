"""The paged ops keep their KV caches in place.

``_PagedRunner``'s layer scan carries the whole [L, ...] K and V arrays
and updates them where they lie. The form it replaced passed them as the
scan's ``xs`` and rebuilt them as its ``ys``: a fresh buffer a call, so
the decode op copied its whole dense cache on every token (PERF.md
section 6, PR 25). Held by structure: no cache- or pool-shaped array is an
``xs`` or ``ys`` of a scan in any of the ops' jaxprs, and the compiled
module holds no whole-cache ``copy`` inside a loop.

A decode or speculative dispatch runs against the pools themselves (PR
46: no view of a sequence kind, nothing gathered, nothing written back).
Held by value: the pools that come back differ from the pools given at
exactly the live rows' step positions under ``kmax``, the null page
aside.
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
    """Every pool shape among the inputs, and a view of its layers'."""
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


@pytest.mark.parametrize("pos", [POS, POS_END], ids=["pos", "pos_end"])
@pytest.mark.parametrize("op_name,quant", [
    ("llama_paged_decode", False), ("llama_paged_decode", True),
    ("llama_paged_spec_step", False), ("llama_paged_spec_step", True),
    ("block_paged_decode", False)])
def test_a_dispatch_writes_its_steps_entries_and_nothing_else(op_name,
                                                              quant, pos):
    """Every pool that comes back differs from the pool given at the
    positions the live rows' steps wrote and nowhere else off the null
    page: 4 steps from ``pos``; a speculative round's ``gamma + 1`` from
    ``pos`` (the target's) and from ``pos - 1`` (the draft's). Inside the
    case: row 0 crossing a page boundary within the dispatch; row 1 running
    onto a null entry of its table (position 16: page 0, not held); row 2
    an inactive slot on the all-null table, which touches no page of
    another row; under POS_END row 3 running past KMAX, where its writes
    are dropped and do not come back into the head of its last page."""
    op, ins, attrs = _latent_case(op_name, pos=pos) \
        if op_name == "block_paged_decode" \
        else _case(op_name, quant=quant, pos=pos)
    out = _jit(op, attrs)(ins)
    n = ATTRS["gamma"] + 1 if op_name == "llama_paged_spec_step" else 4
    pools = [k for k in ins if k.endswith("Pages") or k == "Pools"]
    assert pools
    for src in pools:
        first = pos - src.startswith("Draft")
        got = np.asarray(out[src + "Out"]).astype(np.float32)[:, 1:]
        was = np.asarray(ins[src]).astype(np.float32)[:, 1:]
        # [page, offset] that any layer changed, over the entry's axes
        wrote = {(int(pg) + 1, int(off)) for pg, off in np.argwhere(
            (got != was).any(axis=tuple(range(3, got.ndim))).any(axis=0))}
        want = {(int(TABLE[r, p // PS]), int(p % PS)) for r in (0, 1, 3)
                for p in range(first[r], min(first[r] + n, KMAX))
                if TABLE[r, p // PS] > 0}
        assert wrote == want, (src, sorted(wrote ^ want))
        # every layer wrote each of them
        for pg, off in want:
            assert (got[:, pg - 1, off] != was[:, pg - 1, off]).any(
                axis=tuple(range(1, got.ndim - 2))).all(), (src, pg, off)
