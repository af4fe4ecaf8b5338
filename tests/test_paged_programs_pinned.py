"""The paged programs of the models whose cache entries are whole lane
tiles already (the dense Llama block's ``[kv_heads, head_dim]``, the
hybrid model's flat ``heads * width``) are what they were before latent
attention's entry was padded (PERF.md section 6, PR 34): the pad is made
only where a pool is wider than the entry written into it, so a program
whose pools are as wide as its entries lowers to the same text.

PINNED was taken on PR 33's tree (tests/program_text.py ``fingerprint``
of each program at GEOMETRY: the sha256 of the StableHLO text, the
instructions of the module the CPU compiler leaves) and read the same on
PR 34's. The two decode programs were taken again on PR 46's tree, which
meant to change them: on a CPU they ran the dense view of ``kmax`` that
PR took away, and now run their steps against the pools, the paged
attention calls' jax.numpy reference behind them (fewer instructions in
both). The hybrid model's three were taken again on PR 48's tree, which
meant to change them: its experts are a share of the router's, and the
sum of the held pairs back to their tokens (ops/moe.py) went from one
float32 product at ``HIGHEST`` to three exact bfloat16 passes. Since PR 59
the hybrid model's decode bundle has two fetch sets over its one Program:
``hybrid/decode.probe``, the whole set, is what ``hybrid/decode`` was
(c6847f9bf2f412c7, 3140), and ``hybrid/decode``, the form the loop
dispatches, lost the ``Logits`` and ``Picks`` results and nothing else. A
PR that means to change one of these programs takes the new values from
this test's failure message."""
import pytest

from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY
from paddle_tpu.models.llama import LLAMA_TINY

import program_text

GEOMETRY = dict(max_batch=3, page_size=4, n_pages=40, pages_per_seq=8,
                prompt_buckets=(8, 16), decode_block=2, chunk_size=8)
MODELS = {"llama": LLAMA_TINY, "hybrid": HYBRID_MOE_TINY}
PINNED = {
    "llama/prefill_8": ("937237aae36f26bc", 601),
    "llama/decode": ("802c97bcd03ac9e5", 671),
    "llama/chunk": ("4c190e5db1d62819", 636),
    "hybrid/prefill_8": ("a57c3e0c186498e5", 3167),
    "hybrid/decode": ("ba4d9793df059f99", 3082),
    "hybrid/decode.probe": ("c6847f9bf2f412c7", 3140),
    "hybrid/chunk": ("8155cd1015a013a3", 3403),
}


@pytest.fixture(scope="module")
def programs():
    return {name: cfg.build_paged_programs(**GEOMETRY)
            for name, cfg in MODELS.items()}


@pytest.mark.parametrize("which", sorted(PINNED))
def test_a_program_over_whole_tile_entries_is_what_it_was(programs, which):
    model, label = which.split("/")
    progs = programs[model]
    bundle = program_text.bundles_of(progs)[label.split(".")[0]]
    if label.endswith(".probe"):
        bundle = program_text.probe_form(bundle)
    got = program_text.fingerprint(program_text.lower_bundle(
        bundle, len(progs.pool_specs)))
    assert got == PINNED[which], (which, got)
