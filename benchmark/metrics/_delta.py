"""What the readers of a gated delta-rule model's counters have in common:
they read only a serving run of a configuration with such layers
(``linear_key_head_dim`` in its file). On any other run they return
None."""


def is_delta(run):
    return run.get("kind") == "serve" \
        and "linear_key_head_dim" in run.get("config", {})
