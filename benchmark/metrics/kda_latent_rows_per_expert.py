"""Pairs a REACHED held expert gets in a decode step, the window's mean:
the active rows of a step (KDA_LATENT_STATS, over the kda layers) x
num_experts_per_tok x the held share of the router's width, over the held
experts of one routed layer that a row reached (PAGED_STATS). The regime's
own number: 4 where 256 rows pick 8 of 512 experts evenly and 128 are
held; the deployment's four chips would give a held expert 16."""
from benchmark import work_kda_latent
from benchmark.metrics._kda import decode_rows, experts_touched


def read(run):
    touched, rows = experts_touched(run), decode_rows(run)
    if not touched or not rows:
        return None
    m = run["config"]
    steps, updates = rows
    held = m["experts_held"]
    return updates / work_kda_latent.layers_of(m, work_kda_latent.KDA) \
        / steps * m["num_experts_per_tok"] * held["count"] / held["of"] \
        / touched
