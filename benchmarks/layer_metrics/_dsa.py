"""Shared by the ``sparse_attn_moe`` core's readers: the scopes of its
sparse attention (``models/cores/sparse_attn_moe.py``), and the chosen
(query, key) pairs of the steps the last flush closed, which the program
writes as tags of its ``learner/diag_flush`` span (``dsa_steps``,
``dsa_pairs``): in a traced run the runner's last flush closes the traced
dispatches."""

from benchmarks.layer_metrics._setup_span import rows

SCOPES = ("dsa_indexer", "dsa_select", "dsa_attn", "dsa_kl")
# the indexer's share of them: its scores, the threshold, its loss
INDEXER = ("dsa_indexer", "dsa_select", "dsa_kl")


def pairs_per_step(ctx):
    """Chosen pairs a step, summed over the layers, of the last flush that
    counted any; None where the program wrote none."""
    found = [r["tags"] for r in rows(ctx, "learner/diag_flush")
             if r.get("tags", {}).get("dsa_steps")]
    if not found:
        return None
    return found[-1]["dsa_pairs"] / found[-1]["dsa_steps"]
