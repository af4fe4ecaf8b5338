"""An engine's prefill programs in both forms of their attention fold, a
family a row (prefill_forms.py has the widened configurations and the
comparison; test_prefill_fold.py the kernel alone). A fifth family whose
prefill folds through ``prefill_fold`` adds its configuration there and a
row here."""
import pytest

import prefill_forms
from benchmark.builders import serve_blocks, serve_hybrid, serve_ssm

# whose head the widths are, the builder's probe of the engine's own
# programs, and what the fold is a part of
FAMILIES = {
    # xing4's 128 | 64 rotated | 128, where ``_latent_expanded`` hands its
    # visits to the kernel, under hyper-connections
    "latent_moe": (prefill_forms.LATENT_WIDE, serve_blocks.engine_logits),
    # DeepSeek-V3's 128 | 64 rotated | 128, the share, under the plain
    # residual path
    "latent_share": (prefill_forms.SHARE_WIDE, serve_blocks.engine_logits),
    # MiMo's keys of 192 beside values of 128, the full layers' entries
    # flat at whole lane tiles, ``_gqa_blocked`` (the hook also takes the
    # full layers' decode steps in place: test_paged_gqa_decode.py holds
    # that form alone)
    "hybrid_moe": (prefill_forms.MOE_WIDE, serve_hybrid.engine_logits),
    # Jamba's 20 query heads over ONE key/value head of 128: its two
    # attention layers' ``_gqa_blocked``, the state layers beside them in
    # both forms
    "hybrid_ssm": (prefill_forms.SSM_WIDE, serve_ssm.engine_logits),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engines_dispatches_with_the_fold_in_the_kernel(family,
                                                            monkeypatch):
    """The chip comparison's probe, whole and chunked and decoded, with the
    fold in jax.numpy and through the kernel."""
    prefill_forms.check_both_forms(*FAMILIES[family], monkeypatch)
